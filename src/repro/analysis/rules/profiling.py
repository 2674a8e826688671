"""Rule family 7 — profiling hygiene (NDPP7xx).

The performance observatory (``repro.obs.prof``) attributes engine time
to named phases by parsing captured trace spans.  That attribution is
only as honest as the instrumentation discipline:

  NDPP701  blocking device read (``jax.device_get`` /
           ``.block_until_ready()``) inside a phase scope other than the
           designated ``harvest`` phase.  A block inside ``admission``
           or ``round_dispatch`` charges device wait time to a host
           phase, inflating that phase's wall span and hiding the
           dispatch/compute overlap the profiler exists to measure.
           The engine's contract is one sanctioned sync point per tick
           (``repro.obs.prof.phases.BLOCKING_ALLOWED``).
  NDPP702  ``jax.profiler.TraceAnnotation`` constructed outside the
           ``repro.obs.trace`` gate.  Direct construction bypasses the
           ``NDPP_PROFILE`` env gate (annotations leak into production
           runs) and the ``ndpp_phase/`` naming convention the trace
           parser keys on — route through ``repro.obs.trace.span`` (or
           ``annotation``, the gated constructor it uses) instead.

NDPP701 matches both spellings of the sanctioned phase: the string
literal ``phase("harvest")`` (as in ``drive_rounds``) and the catalog
constant ``self._phase(prof_phases.HARVEST)`` (as in the serving
engine).  A phase opener whose name is dynamic (a variable) is skipped
— the rule never guesses.
"""
from __future__ import annotations

import ast
from typing import Iterator, Optional, Union

from ..common import Finding, Module
from ..registry import rule

# callables that open a profile phase scope when used as a context
# manager: the engine's ``self._phase``, drive_rounds's ``phase`` hook,
# and the underlying ``repro.obs.trace.phase_annotation``
_PHASE_OPENERS = {"phase", "_phase", "phase_annotation"}

# the only phase inside which a blocking device read is sanctioned —
# mirrors repro.obs.prof.phases.BLOCKING_ALLOWED (string-literal copy:
# the analyzer must not import runtime modules)
_BLOCKING_ALLOWED = {"harvest"}

_WITH = (ast.With, ast.AsyncWith)


def _phase_scope_name(expr: ast.AST) -> Optional[str]:
    """If ``expr`` is a phase-opener call, its phase name (lower-cased),
    else None.  ``phase("harvest")`` → "harvest";
    ``self._phase(prof_phases.HARVEST)`` → "harvest"; dynamic → None."""
    if not isinstance(expr, ast.Call) or not expr.args:
        return None
    fn = expr.func
    if isinstance(fn, ast.Attribute):
        opener = fn.attr
    elif isinstance(fn, ast.Name):
        opener = fn.id
    else:
        return None
    if opener not in _PHASE_OPENERS:
        return None
    arg = expr.args[0]
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return arg.value.lower()
    if isinstance(arg, ast.Attribute):
        return arg.attr.lower()   # prof_phases.HARVEST → "harvest"
    return None


def _enclosing_phase(mod: Module,
                     node: ast.AST) -> Optional[Union[str, None]]:
    """Name of the innermost phase scope lexically enclosing ``node``,
    or None when no phase scope encloses it."""
    cur = mod.parents.get(node)
    child = node
    while cur is not None:
        if isinstance(cur, _WITH) and child in cur.body:
            for item in cur.items:
                name = _phase_scope_name(item.context_expr)
                if name is not None:
                    return name
        child = cur
        cur = mod.parents.get(cur)
    return None


def _blocking_call(mod: Module, node: ast.Call) -> Optional[str]:
    """Human-readable spelling of a blocking device read, or None."""
    d = mod.call_dotted(node)
    if d in ("jax.device_get", "jax.block_until_ready"):
        return f"{d}()"
    if (isinstance(node.func, ast.Attribute)
            and node.func.attr in ("device_get", "block_until_ready")):
        return f".{node.func.attr}()"
    return None


# ------------------------------------------------------------------ NDPP701
@rule("NDPP701", "block-outside-harvest",
      "a blocking device read inside a non-harvest phase scope charges "
      "device wait to the wrong phase — the engine's one sanctioned "
      "sync point is the harvest device_get",
      kinds=("src", "script", "fixture"))
def block_outside_harvest(mod: Module) -> Iterator[Finding]:
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        what = _blocking_call(mod, node)
        if what is None:
            continue
        # walk statement ancestry to the innermost enclosing phase scope
        stmt: ast.AST = node
        while (stmt in mod.parents
               and not isinstance(stmt, ast.stmt)):
            stmt = mod.parents[stmt]
        phase = _enclosing_phase(mod, stmt)
        if phase is None or phase in _BLOCKING_ALLOWED:
            continue
        yield Finding(
            "NDPP701", mod.rel, node.lineno, node.col_offset,
            f"{what} inside the '{phase}' phase scope blocks on the "
            f"device there, so attribution charges device wait to "
            f"'{phase}' instead of overlap — move the read into the "
            f"designated harvest phase (the engine's one sanctioned "
            f"sync point per tick)")


# ------------------------------------------------------------------ NDPP702
@rule("NDPP702", "raw-trace-annotation",
      "TraceAnnotation constructed outside the repro.obs.trace gate "
      "bypasses the NDPP_PROFILE env gate and the ndpp_phase/ naming "
      "the trace parser keys on",
      kinds=("src", "script", "fixture"))
def raw_trace_annotation(mod: Module) -> Iterator[Finding]:
    rel = mod.rel.replace("\\", "/")
    if rel.endswith("obs/trace.py"):
        return  # the one sanctioned constructor site
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        d = mod.call_dotted(node)
        if d is None or not (d == "TraceAnnotation"
                             or d.endswith(".TraceAnnotation")):
            continue
        yield Finding(
            "NDPP702", mod.rel, node.lineno, node.col_offset,
            "TraceAnnotation constructed directly — production runs "
            "would pay annotation overhead with NDPP_PROFILE unset, and "
            "ad-hoc names are invisible to the attribution parser; use "
            "repro.obs.trace.span (the gated span primitive) or "
            "repro.obs.trace.annotation")
