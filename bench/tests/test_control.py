"""The control — the program with its lower-precision path switched on —
fails the comparison.  Precision is a property of the chip's matrix unit
(the CPU computes float32 products exactly at every setting), so this test
runs on a TPU, at the smaller cell's size and a short window, and is
skipped elsewhere; ``bench/control.py`` runs the full-length control."""
import pytest

from tiny import BENCH  # noqa: F401  (puts bench/ on the path)

from control import control_numbers


def test_control_is_not_correct():
    import jax

    if jax.devices()[0].platform != "tpu":
        pytest.skip("the control's precision exists only on the chip")
    for seed in (1, 2, 3):
        numbers = control_numbers("instacart-50k.rej-backlog", seed, 5.0,
                                  log=lambda m: None)
        assert any(not v <= lim for v, lim in numbers.values()), numbers
