"""Regression tests for the benchmark harness (benchmarks/run.py).

The harness used to import every bench module eagerly at module import —
one broken module aborted the whole run — and an import failure inside a
section could drop that section without a trace.  These tests pin the
fixed contract: lazy per-section import, an import failure reported with
its traceback and counted as a failure (nonzero exit) while the other
sections still run, and the kernel payload merged into the overhead JSON
artifact.
"""
import json
import textwrap

import pytest

from benchmarks import run as bench_run


def _write_module(tmp_path, monkeypatch, name, body):
    (tmp_path / f"{name}.py").write_text(textwrap.dedent(body))
    monkeypatch.syspath_prepend(str(tmp_path))
    return name


@pytest.fixture
def fake_modules(tmp_path, monkeypatch):
    good = _write_module(tmp_path, monkeypatch, "bench_fake_good", """
        def main(smoke=False):
            return {"ok": True, "smoke": smoke}
    """)
    broken = _write_module(tmp_path, monkeypatch, "bench_fake_broken", """
        raise ImportError("synthetic: missing optional dependency")
    """)
    failing = _write_module(tmp_path, monkeypatch, "bench_fake_failing", """
        def main():
            raise AssertionError("synthetic paper-claim violation")
    """)
    return good, broken, failing


def test_import_failure_is_loud_skip_not_abort(fake_modules, tmp_path, capsys):
    good, broken, _ = fake_modules
    code = bench_run.run(sections=[("good", good), ("broken", broken)],
                         out_path=str(tmp_path / "out.json"))
    out = capsys.readouterr().out
    assert code == 1  # an unimportable section fails the run...
    assert "FAILED broken" in out
    assert "synthetic: missing optional dependency" in out  # traceback shown
    assert "== good ==" in out and "-- ok in" in out  # ...without aborting it


def test_all_sections_skipped_exits_nonzero(fake_modules, tmp_path, capsys):
    _, broken, _ = fake_modules
    code = bench_run.run(sections=[("b1", broken), ("b2", broken)],
                         out_path=str(tmp_path / "out.json"))
    assert code == 1
    out = capsys.readouterr().out
    assert "FAILED b1" in out and "FAILED b2" in out


def test_section_failure_still_exits_nonzero(fake_modules, tmp_path):
    good, _, failing = fake_modules
    code = bench_run.run(sections=[("good", good), ("bad", failing)],
                         out_path=str(tmp_path / "out.json"))
    assert code == 1


def test_only_filter_selects_lazily(fake_modules, tmp_path, capsys):
    # --only must not even import the deselected (broken) module
    good, broken, _ = fake_modules
    code = bench_run.run(only="good",
                         sections=[("good", good), ("broken", broken)],
                         out_path=str(tmp_path / "out.json"))
    out = capsys.readouterr().out
    assert code == 0
    assert "FAILED" not in out and "broken" not in out


def test_kernel_payload_merged_into_overhead_json(tmp_path, monkeypatch):
    fig5 = _write_module(tmp_path, monkeypatch, "bench_fake_fig5", """
        def main(smoke=False):
            return {"journal_overhead": {"journal_tax": 1.2}}
    """)
    kern = _write_module(tmp_path, monkeypatch, "bench_fake_kern", """
        def main():
            return {"fused_vs_compiled": {"grad_bitwise_match": True}}
    """)
    out_path = tmp_path / "BENCH_overhead.json"
    code = bench_run.run(smoke=True, out_path=str(out_path),
                         sections=[("fig5_measured_overhead", fig5),
                                   ("kernel_rooflines", kern)])
    assert code == 0
    doc = json.loads(out_path.read_text())
    assert doc["smoke"] is True
    assert doc["payload"]["journal_overhead"]["journal_tax"] == 1.2
    assert doc["kernels"]["fused_vs_compiled"]["grad_bitwise_match"] is True


def test_serve_payload_written_without_fig5(tmp_path, monkeypatch):
    # the bench-smoke CI job runs `--only fig5,serve`; a serve-only run
    # must still produce the artifact with the "serve" section
    serve = _write_module(tmp_path, monkeypatch, "bench_fake_serve", """
        def main(smoke=False):
            return {"preemptions": 1, "p99_s": 0.1}
    """)
    out_path = tmp_path / "BENCH_overhead.json"
    code = bench_run.run(smoke=True, out_path=str(out_path),
                         sections=[("serve_scheduler", serve)])
    assert code == 0
    doc = json.loads(out_path.read_text())
    assert doc["serve"]["preemptions"] == 1
    assert "payload" not in doc


def test_only_filter_accepts_comma_list(fake_modules, tmp_path, capsys):
    good, broken, _ = fake_modules
    code = bench_run.run(only="good,also-good",
                         sections=[("good", good), ("also-good", good),
                                   ("broken", broken)],
                         out_path=str(tmp_path / "out.json"))
    out = capsys.readouterr().out
    assert code == 0
    assert out.count("-- ok in") == 2
    assert "broken" not in out


def test_real_registry_importable_and_lazy():
    # the shipped registry holds (name, module_path) string pairs — the
    # eager-import regression would turn these back into module objects
    for name, module_path in bench_run.ALL:
        assert isinstance(module_path, str) and module_path.startswith(
            "benchmarks."), (name, module_path)
