"""A cell, a configuration, a traffic mix and a per-layer metric are added
by new files and new entries alone: in a copy of the benchmark, no file
that is there changes, and the harness picks them up by name."""
import hashlib
import json
import os
import shutil
import time

from harness import spec
from harness.train import _per_layer, run

from conftest import BENCH, ROOT


def _digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_cell_from_new_files(tmp_path):
    root = tmp_path / "checkout"
    bench = root / "bench"
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    before = _digests(bench)

    # a configuration: the paper's LSTM at half its hidden size
    cfg = json.loads((bench / "configs" / "lstm-paper.json").read_text())
    cfg["sizes"]["hidden_size"] = 128
    cfg["smoke"] = {"vocab_size": 64, "embed_dim": 16, "hidden_size": 32}
    (bench / "configs" / "lstm-half.json").write_text(json.dumps(cfg))
    shutil.copy(bench / "configs" / "lstm-paper.py",
                bench / "configs" / "lstm-half.py")
    shutil.copy(bench / "flops" / "lstm-paper.py",
                bench / "flops" / "lstm-half.py")
    # a traffic mix, the cell's limits and a metric reader
    (bench / "traffic" / "bptt-1k.json").write_text(json.dumps({
        "why": "a short chain", "batch": 8, "seq_len": 1024,
        "strategy": "multistage_async", "engine": "scan", "interval": 32,
        "smoke": {"batch": 4, "seq_len": 64, "interval": 8}}))
    (bench / "cells" / "lstm-half.bptt-1k.json").write_text(json.dumps({
        "limits": {"grad_gap": 1e-3, "update_gap": 1e-3, "grad_err": 1e-3},
        "smoke_limits": {"grad_gap": 1e-3, "update_gap": 1e-3,
                         "grad_err": 1e-3}}))
    (bench / "metrics" / "steps_per_s.py").write_text(
        "def read(ctx):\n    return ctx['tokens_per_s'] / 64\n")
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "lstm-half", "source": "test",
                         "file": "bench/configs/lstm-half.json",
                         "reduced": ["hidden_size"], "why": "test"})
    b["workloads"].append({"name": "lstm-half.bptt-1k", "config": "lstm-half",
                           "traffic": "bptt-1k", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "steps_per_s", "unit": "1/s",
                           "better": "higher", "source": "host_clock",
                           "layer": "train step", "moves": "tokens_per_s",
                           "workloads": ["lstm-half.bptt-1k"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))

    cell = spec.load_cell(str(root), "lstm-half.bptt-1k", str(bench))
    assert cell.config["sizes"]["hidden_size"] == 128
    assert cell.traffic["seq_len"] == 1024
    assert [m.name for m in cell.per_layer] == ["mfu", "device_idle_share",
                                                "steps_per_s"]
    out = run(cell, 2 ** 31 + 3, 0.2, False, t_start=time.time(),
              smoke=True)
    assert out["correct"] is True, out["check"]
    got = _per_layer(cell, {"tokens_per_s": 6400.0, "peaks": None,
                            "trace": None})
    assert got == {"steps_per_s": {"value": 100.0, "unit": "1/s"}}

    after = _digests(bench)
    assert {k: v for k, v in after.items() if k in before} == before
