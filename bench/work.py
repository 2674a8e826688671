"""Work that the algorithm needs, per kernel, and the chip's peaks.

The counts are of what the rejection sampler must do for the proposals
requests consumed, not of the lanes, padding or copies an implementation
runs.  A proposal draws ``|Y|`` items (``E|Y| = sum lam / (1 + lam)``);
each item step descends ``depth`` tree levels and then scores one leaf
block.  Floats are float32 (4 bytes); R = 2K.
"""
from __future__ import annotations

import json
from pathlib import Path

_PEAKS = Path(__file__).resolve().parent / "peaks.json"
F32 = 4


def peaks(device_kind: str) -> dict:
    """The peak table's row for a device kind; a kind not in the table is
    an error."""
    table = json.loads(_PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{_PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def descent(proposals: float, e_size: float, depth: int, r: int) -> tuple:
    """``ndpp_tree_descent``: each item step reads one R x R node per level
    (the left child of the current node; the parent's mass is carried)
    and takes its inner product with the step's R x R projector.
    Returns (flops, bytes)."""
    nodes = proposals * e_size * depth
    return nodes * 2.0 * r * r, nodes * r * r * F32


def leaf(proposals: float, e_size: float, block: int, r: int) -> tuple:
    """``ndpp_bilinear_batched``: each item step reads the block's
    ``block x R`` rows and forms ``w^T Q w`` for each of them.
    Returns (flops, bytes)."""
    steps = proposals * e_size
    return steps * block * (2.0 * r * r + 2.0 * r), steps * block * r * F32


def least_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """The least time the chip could take, and which bound sets it."""
    t_mem = nbytes / float(peak["hbm_bytes_per_s"])
    t_flop = flops / float(peak["bf16_flops_per_s"])
    return (t_mem, "memory") if t_mem >= t_flop else (t_flop, "compute")
