"""The control at test widths on the CPU, through the run's own verdict:
the reference computed in float8 in the program's place puts tokens first
that lie further below the float32 reference's best than any the program
served, and past the limit, so its ``correct`` is false.

Readings here (tiny widths, 2 layers, seeds 1-3): the program's widest gap
0.004-0.010, the control's 0.16-0.20; the tiny configuration's limit is
0.05. The chip's readings at the cells' own sizes are in PERF.md."""
import jax

from bench import control, harness as H


def test_control_fails_where_the_program_passes(tiny_root):
    cell = H.load_cell("tiny.teams", tiny_root)
    r = control.readings(cell, 2, 14.0, jax.devices(), H.train_predictor(),
                         H.compile_counter())
    assert r["program"]["tokens"] > 100
    assert r["program"]["correct"], r["program"]["checks"]
    assert not r["control"]["correct"], r["control"]["checks"]
