"""The trace reduction, on a small trace written out by hand (exact
answers) and on a recorded one (invariants)."""
import glob
import os

import pytest

from harness import trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))

# Two steps of 100 us each on the host; one device.  Ops (us): a while
# loop 0-50 holding fusion.1 0-30 and fusion.2 30-50; copy-start.1 60-70,
# copy-done.1 120-145, all-reduce.1 150-165, fusion.1 170-190.
HAND = tr.Trace(
    device_ops={"/device:TPU:0": [
        ("while.1", 0, 50_000), ("fusion.1", 0, 30_000),
        ("fusion.2", 30_000, 20_000), ("copy-start.1", 60_000, 10_000),
        ("copy-done.1", 120_000, 25_000), ("all-reduce.1", 150_000, 15_000),
        ("fusion.1", 170_000, 20_000)]},
    host_spans=[
        ("bench.step", 0, 100_000), ("bench.fetch", 0, 10_000),
        ("bench.dispatch", 10_000, 40_000), ("bench.readout", 50_000, 50_000),
        ("bench.step", 100_000, 100_000), ("bench.fetch", 100_000, 30_000),
        ("bench.dispatch", 130_000, 20_000),
        ("bench.readout", 150_000, 50_000)])


def test_window_busy_and_idle_by_hand():
    assert tr.window_of(HAND) == (0, 200_000)
    assert tr.steps_in(HAND) == 2
    # union: 0-50, 60-70, 120-145, 150-165, 170-190
    assert tr.mean_busy_ns(HAND) == 120_000


def test_op_time_by_hand():
    ops = HAND.device_ops["/device:TPU:0"]
    copies = {"copy-start.1", "copy-done.1"}.__contains__
    assert tr.op_ns(ops, copies, 0, 200_000) == 35_000
    assert tr.op_ns(ops, copies, 0, 130_000) == 20_000     # clipped


def test_top_ops_are_self_time_and_gaps_are_named_by_hand():
    # the while loop is control flow, no work of its own
    assert tr.top_ops(HAND) == [
        ["fusion.1", 50e-6], ["copy-done.1", 25e-6], ["fusion.2", 20e-6],
        ["all-reduce.1", 15e-6], ["copy-start.1", 10e-6]]
    # 50-60 and 70-120 (host in readout of step 1: 95 lies in it), 145-150
    # (dispatch of step 2), 165-170 and 190-200 (readout of step 2)
    assert tr.idle_gaps(HAND) == [["bench.readout", 75e-6],
                                  ["bench.dispatch", 5e-6]]
    assert tr.idle_gaps(HAND, n=1) == [["bench.readout", 75e-6]]


# One step of 120 us; a while loop 0-100 holds fusion.a 0-30, fusion.c
# 40-45 and a conditional 50-100, which holds fusion.b 60-100.  The loop's
# and the conditional's events span the gaps between the ops they hold;
# fusion.c holds a marker of no length, as TPU traces show custom calls.
LOOP = tr.Trace(
    device_ops={"/device:TPU:0": [
        ("while.2", 0, 100_000), ("fusion.a", 0, 30_000),
        ("fusion.c", 40_000, 5_000), ("custom-call.9", 40_000, 0),
        ("conditional.1", 50_000, 50_000),
        ("fusion.b", 60_000, 40_000)]},
    host_spans=[("bench.step", 0, 120_000), ("bench.dispatch", 0, 5_000),
                ("bench.readout", 5_000, 115_000)])


def test_gaps_inside_a_while_loop_are_idle_by_hand():
    ops = LOOP.device_ops["/device:TPU:0"]
    assert [e[0] for e in tr.work_ops(ops)] == [
        "fusion.a", "fusion.c", "custom-call.9", "fusion.b"]
    assert tr.mean_busy_ns(LOOP) == 75_000
    assert tr.top_ops(LOOP) == [["fusion.b", 40e-6], ["fusion.a", 30e-6],
                                ["fusion.c", 5e-6]]
    # 30-40 between the loop's ops, 45-60 between the conditional's, and
    # 100-120 after the loop, while the host reads the loss back
    assert tr.idle_gaps(LOOP) == [["bench.readout", 20e-6],
                                  ["in conditional.1", 15e-6],
                                  ["in while.2", 10e-6]]


def test_round_trip_through_json(tmp_path):
    p = tmp_path / "t.json"
    tr.dump(HAND, str(p))
    import json

    assert tr.Trace.from_json(json.loads(p.read_text())) == HAND


HLO = """
  %dynamic-update-slice-start = ((f32[8,4]{1,0:T(8,128)S(5)}, f32[1,4]{1,0}), f32[8,4]{1,0:T(8,128)S(5)}, u32[]) dynamic-update-slice-start(%a, %b, %c), metadata={}
  %dynamic-update-slice-done = f32[8,4]{1,0:T(8,128)S(5)} dynamic-update-slice-done(%dynamic-update-slice-start)
  %dynamic-slice-start.1 = ((f32[8,4]{1,0:T(8,128)S(5)}, s32[]), f32[1,4]{1,0}, u32[]) dynamic-slice-start(%d, %e), dynamic_slice_sizes={1,4}
  %dynamic-slice-done.1 = f32[1,4]{1,0} dynamic-slice-done(%dynamic-slice-start.1)
  %copy-start.2 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%f)
  %copy-done.2 = f32[4]{0} copy-done(%copy-start.2)
  %copy-start.3 = (f32[4]{0:S(5)}, f32[4]{0}, u32[]) copy-start(%g)
  %copy-done.3 = f32[4]{0} copy-done(%copy-start.3)
  %dynamic-slice-start.7 = ((f32[8,4]{1,0:T(8,128)S(5)}, s32[]), f32[1,4]{1,0}, u32[]{:S(2)}) async-start(%h, %i), calls=%async_computation
  %dynamic-slice-done.7 = f32[1,4]{1,0} async-done(%dynamic-slice-start.7), metadata={}
  %all-gather-start = (f32[4]{0}, f32[16]{0}) async-start(%j), calls=%ag
  %all-gather-done = f32[16]{0} async-done(%all-gather-start)
"""


def test_host_copy_names_from_hlo():
    assert tr.host_copy_names(HLO) == {
        "dynamic-update-slice-start", "dynamic-update-slice-done",
        "dynamic-slice-start.1", "dynamic-slice-done.1",
        "copy-start.3", "copy-done.3",
        "dynamic-slice-start.7", "dynamic-slice-done.7"}


RECORDED = sorted(glob.glob(os.path.join(HERE, "data", "trace-*.json")))


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_recorded_trace_invariants(path):
    import json

    with open(path) as f:
        t = tr.Trace.from_json(json.load(f))
    lo, hi = tr.window_of(t)
    busy = tr.mean_busy_ns(t)
    assert 0 < busy <= hi - lo
    gaps = tr.idle_gaps(t, n=10 ** 9)
    assert sum(g[1] for g in gaps) * 1e9 == pytest.approx(
        (hi - lo) - tr.busy_ns(t.device_ops[sorted(t.device_ops)[0]], lo,
                               hi), abs=len(gaps) + 1)
    # work ops on one device line do not overlap: their times add up to
    # the busy time, which the while loops' events would only widen
    dev = sorted(t.device_ops)[0]
    work = tr.work_ops(t.device_ops[dev])
    assert len(work) < len(t.device_ops[dev])
    assert tr.op_ns(work, lambda _: True, lo, hi) == \
        tr.busy_ns(t.device_ops[dev], lo, hi)
    top = tr.top_ops(t)
    assert 0 < len(top) <= 10
    assert all(a[1] >= b[1] for a, b in zip(top, top[1:]))


def test_op_name_from_a_tpu_event_name():
    assert tr.op_name("%fusion.613 = bf16[2,16]{1,0} fusion(f32[2] %a), "
                      "kind=kOutput") == "fusion.613"
    assert tr.op_name("copy-start.3") == "copy-start.3"
