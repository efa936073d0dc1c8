"""The four-chip cell's mesh at its small size, on four virtual CPU
devices: a sound run is correct, and the control and each fault its timed
path can have, the exchange between chips left out among them, read
``correct`` false.
The runs go in one child process, since the device count is fixed when
JAX starts."""
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

CELL = "mamba2-370m.dp4-offload-2k"
CHILD = f"""
import json, sys, time
import jax
import numpy as np
sys.path[:0] = [{BENCH!r}, {os.path.join(ROOT, "src")!r}]
from harness import check, tokens
from harness.spec import load_cell
from harness.train import faults_of, key_of, run, shape_of, sizes_of
cell = load_cell({ROOT!r}, {CELL!r})
out = {{}}
ref, sizes, shape = cell.reference(), sizes_of(cell, True), shape_of(cell, True)
params = ref.init_params(key_of(11), sizes)
rows = tokens.batch(11, 0, shape.rows, shape.seq_len, sizes["vocab_size"])
one = ref.loss_and_grad(params, rows["tokens"], sizes)
four = check.split_rows(ref.loss_and_grad, jax.devices())(
    params, rows["tokens"], sizes)
out["split"] = {{
    "loss": [float(one[0]), float(four[0])],
    "grad_rel": max(float(np.linalg.norm(np.asarray(a) - np.asarray(b))
                          / np.linalg.norm(np.asarray(a)))
                    for a, b in zip(jax.tree_util.tree_leaves(one[1]),
                                    jax.tree_util.tree_leaves(four[1]))),
    "devices": sorted({{str(d) for g in jax.tree_util.tree_leaves(four[1])
                       for d in g.devices()}})}}
for kind in (None, "control") + faults_of(cell):
    r = run(cell, 2 ** 31 + 41, 0.2, False, t_start=time.time(), smoke=True,
            control=kind == "control",
            fault=None if kind == "control" else kind)
    out[str(kind)] = {{"correct": r["correct"], "check": r["check"],
                       "count": r["device"]["count"],
                       "metrics": sorted(r["metrics"])}}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", CHILD], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_run_on_four_devices_is_correct(runs):
    r = runs["None"]
    assert r["correct"] is True, r["check"]
    assert r["count"] == 4
    assert r["metrics"] == ["device_mem_gb", "setup_s", "tokens_per_s"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "grad_doubled", "exchange_left_out"])
def test_fault_on_four_devices_is_not_correct(runs, fault):
    assert runs[fault]["correct"] is False, runs[fault]["check"]


def test_control_on_four_devices_is_not_correct(runs):
    assert runs["control"]["correct"] is False, runs["control"]["check"]


def test_reference_split_across_devices_equals_one_device(runs):
    """The reference's rows split across the four devices give the loss
    and gradient of all rows on one, to fp32 rounding."""
    r = runs["split"]
    assert r["loss"][1] == pytest.approx(r["loss"][0], rel=1e-6)
    assert r["grad_rel"] < 1e-5
    assert len(r["devices"]) == 1
