"""Device time per named part of the program (``harness.scopes``), on
traces written out by hand (exact answers) and on one recorded on the chip
at a small size (invariants)."""
import json
import os

import pytest

from harness import scopes as sc
from harness import trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))
DEV = "/device:TPU:0"

SEG = "jit(step_fn)/jvp()/while/body/closed_call/chain.segment/dot_general"
REC = ("jit(step_fn)/transpose(jvp())/while/body/closed_call/checkpoint/"
       "rematted_computation/chain.segment/while/body/ssd/mul")
BWD = ("jit(step_fn)/transpose(jvp())/while/body/closed_call/checkpoint/"
       "chain.segment/while/body/closed_call/add_any")
OPT = "jit(step_fn)/optimizer/mul"
PRE_FWD = "jit(step_fn)/jvp(chain.prelude)/transpose"
PRE_BWD = "jit(step_fn)/transpose(jvp(chain.prelude))/add_any"
OUT = "jit(step_fn)/jvp()/chain.readout/log_softmax"


@pytest.mark.parametrize("op_name,phase", [
    (SEG, "forward"), (REC, "recompute"), (BWD, "backward"),
    # a forward op whose primitive is a transpose is no backward op
    (PRE_FWD, "forward"), (PRE_BWD, "backward"), (OPT, "forward")])
def test_phase_by_hand(op_name, phase):
    assert sc.phase_of(op_name) == phase


def test_scope_is_a_whole_path_component_by_hand():
    assert sc.in_scope(PRE_FWD, sc.PRELUDE)          # jvp(chain.prelude)
    assert sc.in_scope(PRE_BWD, sc.PRELUDE)
    assert sc.in_scope(REC, sc.SSD) and sc.in_scope(REC, sc.SEGMENT)
    assert not sc.in_scope("jit(f)/ssd_like/mul", sc.SSD)
    assert not sc.in_scope("jit(f)/chain.segments/mul", sc.SEGMENT)
    assert sc.label(REC) == "ssd/recompute"
    assert sc.label(BWD) == "chain.segment/backward"
    assert sc.label("jit(step_fn)/while/body/dynamic_slice") == "unscoped"
    assert sc.label(None) == "unscoped"
    assert sc.selects(REC, (sc.SEGMENT,), "recompute")
    assert not sc.selects(REC, (sc.SEGMENT,), "forward")
    assert sc.selects(PRE_BWD, (sc.PRELUDE, sc.READOUT), None)
    assert not sc.selects(None, (sc.SEGMENT,), None)


def test_op_names_from_hlo_text_by_hand():
    hlo = "\n".join([
        "ENTRY %main.1 (p: bf16[2,4]) -> f32[] {",
        "  %fusion.12 = bf16[2,4]{1,0} fusion(bf16[2,4]{1,0} %p), "
        f'kind=kLoop, calls=%fused.1, metadata={{op_name="{SEG}" '
        'source_line=3}',
        "  %copy-start.1 = (f32[8], f32[8]{0:S(5)}) copy-start(%x)",
        '  %w = f32[] constant(0), metadata={op_type="x" op_name="a\\"b"}',
        f'  ROOT %add.3 = f32[] add(%a, %b), metadata={{op_name="{OPT}"}}',
        "}"])
    assert sc.op_names_from_hlo(hlo) == {
        "fusion.12": SEG, "add.3": OPT, "w": 'a\\"b'}


def test_an_op_without_op_name_takes_its_callers_by_hand():
    # a TPU module: the loop's body holds a prefetch the compiler made (no
    # metadata), which takes the loop's op_name; so does a fusion in a
    # computation the body calls.  The entry's own copy has none to take.
    loop = "jit(step_fn)/transpose(jvp(chain.segment))/while"
    hlo = "\n".join([
        "HloModule jit_step_fn, entry_computation_layout={(f32[8]{0})->f32[8]{0}}",
        "",
        "%fused_computation.1 (param_0: f32[8]) -> f32[8] {",
        "  %param_0 = f32[8]{0} parameter(0)",
        "  ROOT %neg.1 = f32[8]{0} negate(%param_0)",
        "}",
        "",
        "%called.2 (p: f32[8]) -> f32[8] {",
        "  %p = f32[8]{0} parameter(0)",
        "  ROOT %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, "
        "calls=%fused_computation.1",
        "}",
        "",
        "%body.3 (q: (f32[8])) -> (f32[8]) {",
        "  %q = (f32[8]{0}) parameter(0)",
        "  %copy-start.4 = (f32[8]{0}, f32[8]{0:S(1)}, u32[]) copy-start(%q)",
        "  %copy-done.4 = f32[8]{0:S(1)} copy-done(%copy-start.4)",
        "  %call.5 = f32[8]{0} call(%copy-done.4), to_apply=%called.2",
        f'  ROOT %add.6 = f32[8]{{0}} add(%call.5, %call.5), '
        f'metadata={{op_name="{BWD}"}}',
        "}",
        "",
        "ENTRY %main.9 (a: f32[8]) -> f32[8] {",
        "  %a = f32[8]{0} parameter(0)",
        "  %copy.8 = f32[8]{0} copy(%a)",
        "  ROOT %while.9 = (f32[8]{0}) while(%copy.8), condition=%cond.1, "
        f'body=%body.3, metadata={{op_name="{loop}"}}',
        "}",
        "",
        "FileNames",
        '1 "step.py"'])
    got = sc.op_names_from_hlo(hlo)
    assert got["add.6"] == BWD
    for name in ("copy-start.4", "copy-done.4", "call.5", "fusion.7",
                 "neg.1", "while.9"):
        assert got[name] == loop, name
    assert "copy.8" not in got and "a" not in got
    assert sc.label(got["copy-done.4"]) == "chain.segment/backward"


# One step, 0-200 us.  A container region.1 0-100 (forward) holds a
# recompute op 10-40 and a backward op 50-90, which holds a forward op
# 60-70; a while loop 100-190 holds the optimizer 120-150 and an op of no
# scope 160-170; the window closes at 180 in the middle of a readout op
# 175-195.
NESTED = tr.Trace(
    device_ops={DEV: [
        ("region.1", 0, 100_000), ("fusion.r", 10_000, 30_000),
        ("fusion.b", 50_000, 40_000), ("fusion.f", 60_000, 10_000),
        ("while.1", 100_000, 90_000), ("fusion.o", 120_000, 30_000),
        ("copy.u", 160_000, 10_000), ("fusion.h", 175_000, 20_000)]},
    host_spans=[("bench.step", 0, 180_000),
                ("bench.readout", 150_000, 30_000)])
NAMES = {"region.1": SEG, "fusion.r": REC, "fusion.b": BWD, "fusion.f": SEG,
         "while.1": "jit(step_fn)/while", "fusion.o": OPT,
         "copy.u": "jit(step_fn)/copy", "fusion.h": OUT}


def test_exclusive_time_goes_to_the_innermost_op_by_hand():
    ops = NESTED.device_ops[DEV]
    got = sc.exclusive_ns(ops, 0, 180_000)
    assert got == {"region.1": 30_000, "fusion.r": 30_000,
                   "fusion.b": 30_000, "fusion.f": 10_000,
                   "fusion.o": 30_000, "copy.u": 10_000, "fusion.h": 5_000}
    assert sum(got.values()) == tr.busy_ns(ops, 0, 180_000)
    # clipped at both ends of a window
    assert sc.exclusive_ns(ops, 20_000, 55_000) == {
        "fusion.r": 20_000, "region.1": 10_000, "fusion.b": 5_000}


def test_exclusive_time_of_ops_that_overlap_without_nesting_by_hand():
    ops = [("a", 0, 10), ("b", 5, 10), ("c", 30, 5)]
    got = sc.exclusive_ns(ops, 0, 100)
    assert got == {"a": 5, "b": 10, "c": 5}
    assert sum(got.values()) == tr.busy_ns(ops, 0, 100) == 20


def test_parts_and_coverage_by_hand():
    got = sc.part_ms(NESTED, NAMES)
    ms = 1e-3                         # 1 us in ms
    # the container keeps only its own 30 us: its nested backward op is
    # not counted again as forward
    assert got == pytest.approx({
        "chain_fwd_ms": 40 * ms, "recompute_ms": 30 * ms,
        "chain_bwd_ms": 30 * ms, "optimizer_ms": 30 * ms,
        "head_ms": 5 * ms, "ssd_ms": 30 * ms,
        "busy_ms": 145 * ms, "unscoped_ms": 10 * ms})
    covered = sum(got[p] for p in sc.COVER)
    assert covered + got["unscoped_ms"] == pytest.approx(got["busy_ms"])
    assert sc.unscoped_ops(NESTED, NAMES) == [
        ["copy.u", pytest.approx(10 * ms), "jit(step_fn)/copy"]]


def test_parts_average_over_devices_and_steps_by_hand():
    two = tr.Trace(
        device_ops={DEV: [("x", 0, 40)], "/device:TPU:1": [("x", 100, 20)]},
        host_spans=[("bench.step", 0, 100), ("bench.step", 100, 100)])
    got = sc.part_ms(two, {"x": OPT})
    # 60 ns over two devices and two steps
    assert got["optimizer_ms"] == pytest.approx(15e-6)
    assert got["busy_ms"] == pytest.approx(15e-6)


def test_a_program_without_scopes_reads_no_part_by_hand():
    got = sc.part_ms(NESTED, {})
    assert all(got[p] == 0 for p in sc.PARTS)
    assert got["unscoped_ms"] == got["busy_ms"] > 0


def test_idle_gaps_in_loops_carry_their_loop_part_by_hand():
    # 100-120, 150-160 and 170-175 idle inside while.1
    gaps = tr.idle_gaps(NESTED)
    assert sc.while_gaps(gaps, NAMES) == [
        ["in while.1", pytest.approx(35e-6), "unscoped"]]
    loop = dict(NAMES, **{"while.1": BWD + "/while"})
    assert sc.while_gaps(gaps, loop)[0][2] == "chain.segment/backward"


RECORDED = os.path.join(HERE, "data",
                        "scoped-mamba2-370m.offload-2k-smoke.json")


def test_recorded_scoped_trace():
    # six steps of mamba2-370m.offload-2k at its small size on a TPU v5e
    # (``bench/phases.py --smoke --steps 6 --record``)
    with open(RECORDED) as f:
        d = json.load(f)
    t = tr.Trace.from_json(d)
    got = sc.part_ms(t, d["op_names"])
    busy = tr.mean_busy_ns(t) / tr.steps_in(t) / 1e6
    assert got["busy_ms"] == pytest.approx(busy)
    covered = sum(got[p] for p in sc.COVER)
    assert covered <= busy * (1 + 1e-9)
    assert covered + got["unscoped_ms"] == pytest.approx(busy)
    assert covered > 0.9 * busy
    for part in sc.PARTS:
        assert got[part] > 0, part
    assert got["ssd_ms"] <= (got["chain_fwd_ms"] + got["recompute_ms"]
                             + got["chain_bwd_ms"]) * (1 + 1e-9)
