"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

It reads the trace through ``jax.profiler.ProfileData`` and takes device
time from the TPU device planes (``/device:TPU:<n>``), never from host
executor markers.  The window is the host annotation ``bench.window``
that the harness holds open around the measured window.

- busy: the union of the device-plane op intervals inside the window,
  averaged over the chips used; idle share is 1 - busy / window.
- kernels: device seconds per HLO op name with its numeric suffix
  dropped (a Pallas kernel's op carries the kernel's ``name``).  Ops that
  hold other ops (``while``, ``conditional``, ``call``) are left out, so
  no second is counted twice.
- scopes: device seconds per innermost ``ndpp.*`` component of an op's
  ``jax.named_scope`` path, joined through the compiled HLO text of the
  program (``hlo_scope_map``: the trace names the op, the HLO text holds
  its scope path).
- idle gaps: the longest stretches with no op on the first chip, each
  named by the innermost benchmark or engine annotation (``bench.*``,
  ``ndpp_*``) open at its midpoint.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
#: lines of a TPU device plane that hold one event per executed op
OP_LINES = ("XLA Ops",)
#: ops whose time is the time of the ops they hold
CONTAINERS = ("while", "conditional", "call")
#: host annotations that name an idle gap
HOST_PREFIXES = ("bench.", "ndpp_")
_SUFFIX = re.compile(r"\.\d+$")
_OP = re.compile(r"^\s*(?:ROOT\s+)?%?([A-Za-z0-9_.\-]+)")
_SCOPE = re.compile(r"(?:^|/)(ndpp\.[A-Za-z0-9_]+)")
# HLO text: "  %name.3 = f32[..] op(..), metadata={op_name="..." ...}"
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([A-Za-z0-9_.\-]+)\s*=.*?"
                    r"metadata=\{[^}]*op_name=\"([^\"]*)\"")


def union(spans: List[Tuple[float, float]]) -> float:
    """Total length covered by possibly overlapping (start, end) spans."""
    total, cur = 0.0, None
    for a, b in sorted(spans):
        if cur is None or a > cur[1]:
            if cur is not None:
                total += cur[1] - cur[0]
            cur = [a, b]
        else:
            cur[1] = max(cur[1], b)
    if cur is not None:
        total += cur[1] - cur[0]
    return total


def gaps(spans: List[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """Stretches of [lo, hi] that no span covers."""
    out, cur = [], lo
    for a, b in sorted(spans):
        if a > cur:
            out.append((cur, min(a, hi)))
        cur = max(cur, b)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


def op_name(event_name: str) -> str:
    """The HLO instruction name of a device op event: TPU traces name an
    op by its whole HLO line (``%fusion.3 = f32[..] fusion(..)``)."""
    m = _OP.match(event_name)
    return m.group(1) if m else event_name


def base_name(name: str) -> str:
    """``ndpp_tree_descent.3`` -> ``ndpp_tree_descent``."""
    return _SUFFIX.sub("", name)


def hlo_scope_map(compiled_text: str) -> Dict[str, str]:
    """{instruction name: innermost ``ndpp.*`` scope} from compiled HLO
    text, whose ``metadata={op_name="jit(f)/.../ndpp.<x>/..."}`` carries
    each instruction's ``jax.named_scope`` path."""
    out = {}
    for line in compiled_text.splitlines():
        m = _INSTR.match(line)
        if m:
            scopes = _SCOPE.findall(m.group(2))
            if scopes:
                out[m.group(1)] = scopes[-1]
    return out


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # averaged over the chips
    chips: int
    kernels: Dict[str, float]           # base op name -> device seconds
    scopes: Dict[str, float]            # ndpp.* scope -> device seconds
    idle_gaps: List[Tuple[str, float]]  # longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_seconds(self, name: str) -> float:
        return self.kernels.get(name, 0.0)

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.kernels.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps[:n]]}


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.duration_ns), e


def reduce(profile, chips: int,
           scope_map: Optional[Dict[str, str]] = None) -> TraceSummary:
    """``profile``: a ``jax.profiler.ProfileData``; ``chips``: the device
    planes to read (``/device:TPU:0`` .. ``chips - 1``); ``scope_map``:
    ``hlo_scope_map`` of the programs the window ran."""
    scope_map = scope_map or {}
    host_spans: List[Tuple[float, float, str]] = []
    window: Optional[Tuple[float, float]] = None
    device: Dict[int, List[Tuple[str, float, float, object]]] = {}
    for plane in profile.planes:
        name = plane.name
        m = re.fullmatch(r"/device:TPU:(\d+)", name)
        if m and int(m.group(1)) < chips:
            evs = device.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name in OP_LINES:
                    evs.extend((n, t, t + d, e) for n, t, d, e in _events(line))
        elif name.startswith("/host:"):
            for line in plane.lines:
                for n, t, d, _ in _events(line):
                    if n == WINDOW:
                        window = (t, t + d)
                    elif d > 0 and n.startswith(HOST_PREFIXES):
                        host_spans.append((t, t + d, n))
    if window is None:
        raise RuntimeError(f"no {WINDOW!r} annotation in the trace")
    if len(device) < chips:
        raise RuntimeError(f"trace holds op lines for {sorted(device)}, "
                           f"expected {chips} chips")
    lo, hi = window
    kernels: Dict[str, float] = {}
    scopes: Dict[str, float] = {}
    busy = []
    for dev, evs in sorted(device.items()):
        spans = []
        for n, a, b, _ in evs:
            a, b = max(a, lo), min(b, hi)
            if b <= a:
                continue
            spans.append((a, b))
            name = op_name(n)
            if base_name(name) in CONTAINERS:
                continue
            kernels[base_name(name)] = kernels.get(base_name(name), 0.0) + (b - a)
            scope = scope_map.get(name)
            if scope is not None:
                scopes[scope] = scopes.get(scope, 0.0) + (b - a)
        busy.append(union(spans))
        if dev == 0:
            idle = gaps(spans, lo, hi)
    named = []
    for a, b in idle:
        mid = 0.5 * (a + b)
        open_ = [(s, n) for s, e, n in host_spans if s <= mid <= e]
        label = max(open_)[1] if open_ else "no annotation open"
        named.append((label, (b - a) * 1e-9))
    named.sort(key=lambda kv: -kv[1])
    ns = 1e-9
    return TraceSummary(
        window_s=(hi - lo) * ns, busy_s=sum(busy) / len(busy) * ns,
        chips=chips, kernels={k: v * ns for k, v in kernels.items()},
        scopes={k: v * ns for k, v in scopes.items()}, idle_gaps=named)

