"""The per-layer readers that take their numbers from the op metadata and
opcodes of the compiled step: the six parts of the step and
``allreduce_ms``, on a trace recorded on the chip and on traces written out
by hand."""
import json
import os

import pytest

from harness import scopes as sc
from harness import spec
from harness import trace as tr

from conftest import BENCH

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data",
                        "scoped-mamba2-370m.offload-2k-smoke.json")


def _reader(name):
    return spec.Metric(name, "ms/step").reader(BENCH)


def _ctx(t, op_names=None, opcodes=None):
    """What a traced run hands the readers (``harness.train.run``)."""
    return {"trace": {"trace": t, "steps": tr.steps_in(t),
                      "name_ns": sc.name_ns(t)},
            "op_names": op_names or {}, "opcodes": opcodes or {}}


@pytest.fixture(scope="module")
def recorded():
    with open(RECORDED) as f:
        d = json.load(f)
    return tr.Trace.from_json(d), d["op_names"]


@pytest.mark.parametrize("name", list(sc.PARTS))
def test_part_reader_reads_part_ms(recorded, name):
    t, names = recorded
    got = _reader(name).read(_ctx(t, names))
    assert got == sc.part_ms(t, names)[name]
    assert got > 0


def test_ssd_reads_zero_where_no_op_carries_it(recorded):
    t, names = recorded
    no_ssd = {k: v for k, v in names.items() if not sc.in_scope(v, sc.SSD)}
    assert len(no_ssd) < len(names)
    got = _reader("ssd_ms").read(_ctx(t, no_ssd))
    assert got is not None and got == 0.0
    assert _reader("chain_bwd_ms").read(_ctx(t, no_ssd)) > 0


@pytest.mark.parametrize("name", list(sc.PARTS) + ["allreduce_ms"])
def test_reader_reads_nothing_without_a_trace(name):
    assert _reader(name).read({"trace": None, "op_names": {},
                               "opcodes": {}}) is None


# Two steps of 100 us on two chips.  Chip 0: all-reduce-start 10-12,
# all-reduce-done 40-50, a fusion 12-40; chip 1: all-reduce-start 10-11,
# all-reduce-done 40-44 and a synchronous all-reduce 150-156 us.
TWO_CHIPS = tr.Trace(
    device_ops={
        "/device:TPU:0": [("all-reduce-start.1", 10_000, 2_000),
                          ("fusion.3", 12_000, 28_000),
                          ("all-reduce-done.1", 40_000, 10_000)],
        "/device:TPU:1": [("all-reduce-start.1", 10_000, 1_000),
                          ("fusion.3", 11_000, 29_000),
                          ("all-reduce-done.1", 40_000, 4_000),
                          ("all-reduce.2", 150_000, 6_000)]},
    host_spans=[("bench.step", 0, 100_000), ("bench.step", 100_000, 100_000)])
OPCODES = {"all-reduce-start.1": "all-reduce-start",
           "all-reduce-done.1": "all-reduce-done",
           "all-reduce.2": "all-reduce", "fusion.3": "fusion"}


def test_allreduce_ms_by_hand():
    got = _reader("allreduce_ms").read(_ctx(TWO_CHIPS, opcodes=OPCODES))
    # (12 + 11) us over two chips and two steps
    assert got == pytest.approx(23e-3 / 4)


def test_allreduce_ms_reads_nothing_in_a_step_without_one():
    one = {k: v for k, v in OPCODES.items() if not v.startswith("all-")}
    assert _reader("allreduce_ms").read(_ctx(TWO_CHIPS, opcodes=one)) is None


def test_opcodes_from_hlo_by_hand():
    hlo = "\n".join([
        "HloModule jit_step_fn",
        "",
        "%region.1 (a: f32[], b: f32[]) -> f32[] {",
        "  ROOT %add.1 = f32[] add(f32[] %a, f32[] %b)",
        "}",
        "",
        "ENTRY %main.9 (p: bf16[2,4]) -> f32[] {",
        "  %fusion.12 = bf16[2,4]{1,0:T(8,128)(2,1)} fusion(bf16[2,4]{1,0} "
        '%p), kind=kLoop, calls=%fused.1, metadata={op_name="a(b)"}',
        "  %copy-start.1 = (f32[8]{0}, f32[8]{0:S(5)}, u32[]) copy-start(%x)",
        "  %all-reduce.7 = ((f32[8]{0:T(256)}, f32[4]), /*index=1*/bf16[2]) "
        "all-reduce(%q, %r), replica_groups=[1,4]<=[4], to_apply=%region.1",
        "  ROOT %all-reduce-done.2 = f32[8]{0} all-reduce-done(%s)",
        "}"])
    assert tr.opcodes_from_hlo(hlo) == {
        "add.1": "add", "fusion.12": "fusion", "copy-start.1": "copy-start",
        "all-reduce.7": "all-reduce", "all-reduce-done.2": "all-reduce-done"}


def test_traced_run_hands_readers_the_steps_metadata(recorded, monkeypatch):
    """The harness's traced run, on the CPU at the small size, with the
    profiler's trace replaced by the recorded one: every part metric is in
    the result line, none null, and the readers got the step's
    ``op_names``, ``opcodes``, sizes and shape.  (The CPU's step has no
    host memory space, so the Level-2 numbers read nothing here.)"""
    import time

    from harness import train

    t, _ = recorded
    seen = {}

    def traced(self, n):
        return {"trace": t, "steps": tr.steps_in(t), "window_s": 1.0,
                "busy_s": tr.mean_busy_ns(t) / 1e9, "top_ops": [],
                "idle_gaps": [], "name_ns": sc.name_ns(t)}

    def per_layer(cell, ctx):
        seen.update(ctx)
        return real(cell, ctx)

    real = train._per_layer
    monkeypatch.setattr(train.Trainer, "traced", traced)
    monkeypatch.setattr(train, "_per_layer", per_layer)
    cell = spec.load_cell(os.path.dirname(BENCH), "mamba2-370m.offload-2k")
    out = train.run(cell, 2 ** 31 + 5, 0.2, True, t_start=time.time(),
                    smoke=True)
    assert out["correct"] is True
    got = out["metrics"]
    for name in sc.PARTS:
        assert got[name]["value"] is not None, name
    assert "allreduce_ms" not in got
    assert seen["op_names"] and set(seen["opcodes"]) >= set(seen["op_names"])
    assert seen["sizes"]["n_layer"] == 2
    assert seen["shape"]["rows"] == 2 and seen["shape"]["interval"] == 1
