"""The comparison that decides ``correct`` at a size a test run holds:
the bfloat16 control comes out not correct where the program comes out
correct, and a run whose timed path is broken underneath comes out not
correct.

On the chip the control is read at the cell's own size
(``calibrate.py``); here, on the CPU at the program's reduced size, every
float32 numerics of the reference is float32 throughout, so the
program's gaps read about 0 while the control's do not.
"""

import time

import pytest

import reduced_cell as rc

import calibrate  # noqa: E402
import faults  # noqa: E402
import harness  # noqa: E402
from repro.serving import continuous  # noqa: E402

CELLS = ["qwen3-0.6b.e0.long_gen", "qwen3-0.6b.e0.short_chat"]


@pytest.mark.parametrize("workload", CELLS)
def test_control_reads_worse_than_the_program(workload):
    cell = rc.short_cell(workload)
    with rc.no_compile_cache():
        rs = calibrate.readings(cell, [1, 2, 3], 2.0, log=lambda s: None)
    ref = calibrate.reference.REFERENCE
    assert all(r["program"][ref]["tokens_compared"] >= 100 for r in rs)
    assert all(r["program_correct"] for r in rs)
    assert not any(r["control_correct"] for r in rs)


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    """The harness's run, minus its look for a chip, over a decode step
    broken underneath the executor."""
    workload = CELLS[0]
    cell = rc.short_cell(workload)
    monkeypatch.setattr(continuous, "coded_pool_decode_step",
                        faults.FAULTS[fault](
                            continuous.coded_pool_decode_step))
    with rc.no_compile_cache():
        out = harness.run_cell(cell, 7, 2.0, False, [rc.StandInTPU()],
                               time.perf_counter(), log=lambda s: None)
    assert out["correct"] is False, out["checks"]


def test_sound_run_is_correct():
    cell = rc.short_cell(CELLS[0])
    with rc.no_compile_cache():
        out = harness.run_cell(cell, 7, 2.0, False, [rc.StandInTPU()],
                               time.perf_counter(), log=lambda s: None)
    assert out["correct"] is True, out["checks"]
