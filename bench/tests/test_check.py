"""A run with the timed path broken underneath reads ``correct`` false,
and a sound run reads it true: the harness's whole run, at the
configurations' small sizes on the CPU, past its look for a chip, judged
by the limits set from readings at that size (``smoke_limits`` in
``bench/cells/<workload>.json``)."""
import time

import pytest

from harness.spec import load_cell
from harness.train import run

from conftest import ROOT

SEED = 2 ** 31 + 29


def _run(workload, **kw):
    cell = load_cell(ROOT, workload)
    return run(cell, SEED, 0.2, False, t_start=time.time(), smoke=True, **kw)


@pytest.mark.parametrize("workload", ["mamba2-370m.offload-2k",
                                      "lstm-paper.bptt-64k"])
def test_sound_run_is_correct(workload):
    out = _run(workload)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert list(out)[-1] == "check"
    assert set(out["metrics"]) == {"tokens_per_s", "device_mem_gb",
                                   "setup_s"}
    assert out["device"]["platform"] == "cpu"


@pytest.mark.parametrize("workload", ["mamba2-370m.offload-2k",
                                      "lstm-paper.bptt-64k"])
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "grad_doubled"])
def test_fault_is_not_correct(workload, fault):
    out = _run(workload, fault=fault)
    assert out["correct"] is False, out["check"]


@pytest.mark.parametrize("workload", ["mamba2-370m.offload-2k",
                                      "lstm-paper.bptt-64k"])
def test_control_is_not_correct(workload):
    """The reference in the precision below the configuration's (float8
    operands for mamba2's bfloat16 matmuls; three bfloat16 passes for the
    LSTM's fp32 "highest") in the program's place."""
    out = _run(workload, control=True)
    assert out["correct"] is False, out["check"]
