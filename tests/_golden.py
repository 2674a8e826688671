"""Golden-file regression harness.

A golden file freezes the *discrete* outcome of a fixed-seed sampler call
— subset item indices, validity masks, trial/step counts — as committed
JSON.  Distribution-shifting refactors (a changed key schedule, a
reordered proposal loop, an off-by-one in the speculative rounds) then
fail loudly against the stored draws instead of sliding under the
chi-square tests' statistical tolerance.

Regeneration is explicit: ``pytest tests/test_golden.py --regen-golden``
rewrites the files, so a deliberate distribution change is a reviewed
diff of ``tests/golden/*.json``, never a silent drift.

Only discrete outputs belong in a golden payload (ints and booleans):
they are stable under last-bit float jitter across BLAS builds, while raw
log-probabilities would not be.

The committed files were generated under JAX 0.9.0 with its default
``jax_threefry_partitionable=True`` (the default since JAX 0.5.0).  That
flag chooses how threefry keys are split and bits are drawn, so it moves
every draw of every backend at once: when all files differ together,
compare the JAX version and that flag before suspecting the samplers.
"""
import json
import pathlib

GOLDEN_DIR = pathlib.Path(__file__).parent / "golden"


def golden_path(name: str) -> pathlib.Path:
    return GOLDEN_DIR / f"{name}.json"


def canonical(payload):
    """Round-trip through JSON so in-memory payloads compare exactly the
    way they deserialize (tuples -> lists, numpy ints -> ints)."""
    return json.loads(json.dumps(payload))


def save_golden(name: str, payload) -> None:
    GOLDEN_DIR.mkdir(exist_ok=True)
    with open(golden_path(name), "w") as f:
        json.dump(canonical(payload), f, indent=1, sort_keys=True)
        f.write("\n")


def load_golden(name: str):
    p = golden_path(name)
    if not p.exists():
        return None
    with open(p) as f:
        return json.load(f)


def diff_payload(expect, got, path=""):
    """Human-readable list of leaf differences between two payloads."""
    diffs = []
    if isinstance(expect, dict) and isinstance(got, dict):
        for k in sorted(set(expect) | set(got)):
            if k not in expect:
                diffs.append(f"{path}.{k}: unexpected key")
            elif k not in got:
                diffs.append(f"{path}.{k}: missing key")
            else:
                diffs.extend(diff_payload(expect[k], got[k], f"{path}.{k}"))
    elif isinstance(expect, list) and isinstance(got, list):
        if len(expect) != len(got):
            diffs.append(f"{path}: length {len(got)} != {len(expect)}")
        else:
            for i, (e, g) in enumerate(zip(expect, got)):
                diffs.extend(diff_payload(e, g, f"{path}[{i}]"))
    elif expect != got:
        diffs.append(f"{path}: {got!r} != {expect!r}")
    return diffs


def assert_matches_golden(name: str, payload, regen: bool) -> None:
    """Compare ``payload`` to the stored golden file bit-for-bit.

    ``regen=True`` (the ``--regen-golden`` pytest flag) rewrites the file
    and passes.  A missing golden file fails with the regeneration
    command rather than silently passing.
    """
    payload = canonical(payload)
    if regen:
        save_golden(name, payload)
        return
    expect = load_golden(name)
    assert expect is not None, (
        f"no golden file {golden_path(name)} — run "
        f"`pytest tests/test_golden.py --regen-golden` and commit the result")
    diffs = diff_payload(expect, payload)
    assert not diffs, (
        f"golden mismatch for {name!r} ({len(diffs)} differing leaves) — "
        f"if the distribution change is intentional, regenerate with "
        f"`pytest tests/test_golden.py --regen-golden` and review the "
        f"golden diff:\n" + "\n".join(diffs[:20]))
