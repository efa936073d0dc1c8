"""A cell, a configuration, a traffic mix and a per-layer metric are added
by new files and new entries alone: in a copy of the benchmark, no file
that is there changes, and the harness picks them up by name."""
import hashlib
import json
import os
import shutil
import time
import types

from harness import scopes, spec
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


# every cell reports these, those that later PRs add too: no ``workloads``
# list names the cells
ALL_CELLS = ["mfu", "device_idle_share", "l2_copy_ms", "l2_host_gb",
             *scopes.PARTS]


def _copy(tmp_path):
    root = tmp_path / "checkout"
    bench = root / "bench"
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    return root, bench


def test_new_cell_from_new_files(tmp_path):
    root, bench = _copy(tmp_path)
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
    assert [m.name for m in cell.per_layer] == ALL_CELLS + ["steps_per_s"]
    out = run(cell, 2 ** 31 + 3, 0.2, False, t_start=time.time(),
              smoke=True)
    assert out["correct"] is True, out["check"]
    no_host = types.SimpleNamespace(
        host_argument_size_in_bytes=0, host_output_size_in_bytes=0,
        host_temp_size_in_bytes=0, host_alias_size_in_bytes=0)
    got = _per_layer(cell, {"tokens_per_s": 6400.0, "peaks": None,
                            "trace": None, "memory": no_host,
                            "host_copy_names": set()})
    assert got == {"steps_per_s": {"value": 100.0, "unit": "1/s"}}

    after = _digests(bench)
    assert {k: v for k, v in after.items() if k in before} == before


def test_a_cell_added_by_entry_alone_reports_parts_and_level2(tmp_path):
    """A third cell written into ``BENCHMARK.json``, on files that are
    there, gets the six part metrics and both Level-2 metrics, and no
    metric that a ``workloads`` list keeps to other cells."""
    root, bench = _copy(tmp_path)
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["workloads"].append({"name": "mamba2-370m.offload-2k-again",
                           "config": "mamba2-370m", "traffic": "offload-2k",
                           "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    shutil.copy(bench / "cells" / "mamba2-370m.offload-2k.json",
                bench / "cells" / "mamba2-370m.offload-2k-again.json")
    cell = spec.load_cell(str(root), "mamba2-370m.offload-2k-again",
                          str(bench))
    assert [m.name for m in cell.per_layer] == ALL_CELLS
    assert [m.name for m in cell.end_to_end] == ["tokens_per_s",
                                                 "device_mem_gb", "setup_s"]
