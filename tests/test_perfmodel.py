"""Performance model: the paper's §3 inequalities as hypothesis properties."""
import math

import pytest
from _hypothesis_compat import given, settings, st  # optional dep, see shim

from repro.core import perfmodel as pm
from repro.core import revolve as rv


@settings(deadline=None, max_examples=80)
@given(n=st.integers(2, 2000), s=st.integers(2, 64),
       t_a=st.floats(1e-5, 1e-2), t_b_ratio=st.floats(0.5, 4.0),
       t_t_ratio=st.floats(0.01, 50.0))
def test_async_never_slower_than_revolve_at_optimal_interval(
        n, s, t_a, t_b_ratio, t_t_ratio):
    """Paper's headline claim, over a broad hardware/workload space.

    Exact under the paper's §3 formula (T = n·R(I,s)·T_A + n·T_B with
    R(I,s) <= R(n,s)); our ``t_async`` additionally models prefetch stalls
    and the ceil on partial segments, so it gets a per-segment allowance.
    """
    import math
    from repro.core import revolve as rv
    from repro.core import schedule as ms
    t_b = t_a * t_b_ratio
    t_t = t_a * t_t_ratio
    interval = pm.optimal_interval(t_t, t_a)
    t_rev = pm.t_revolve(n, s, t_a, t_b)
    # the paper's formula: exact inequality
    if interval <= n:
        r_paper = ms.multistage_recompute_factor_paper(n, interval, s)
        t_paper = n * r_paper * t_a + n * t_b
        assert r_paper <= rv.recompute_factor(n, s) + 1e-9
        assert t_paper <= t_rev * (1 + 1e-9) + n * t_a * 1e-6
    # the realistic model: bounded by revolve + stall/partial-segment slack
    t_async = pm.t_async(n, interval, s, t_a, t_b, t_t)
    segs = math.ceil(n / max(interval, 1))
    slack = segs * (t_t + interval * t_a + t_b) + n * t_a
    assert t_async <= t_rev * (1 + 1e-9) + slack
    # and never beats the no-memory-limit bound
    assert t_async >= pm.t_inf(n, t_a, t_b) * (1 - 1e-9) - 1e-12


def test_overhead_constant_in_n():
    """T_async/T_inf approaches a constant as n grows (paper §3/Fig 3)."""
    s, t_a, t_b, t_t = 100, 1e-3, 2e-3, 8e-3
    i = pm.optimal_interval(t_t, t_a)
    ratios = [pm.t_async(n, i, s, t_a, t_b, t_t) / pm.t_inf(n, t_a, t_b)
              for n in (10_000, 100_000, 1_000_000)]
    assert max(ratios) - min(ratios) < 0.01
    # Revolve's ratio keeps growing
    rev = [pm.t_revolve(n, s, t_a, t_b) / pm.t_inf(n, t_a, t_b)
           for n in (10_000, 100_000, 1_000_000)]
    assert rev[-1] > rev[0] + 0.1


def test_optimal_interval_law():
    assert pm.optimal_interval(8e-3, 1e-3) == 8
    assert pm.optimal_interval(8.1e-3, 1e-3) == 9
    assert pm.optimal_interval(1e-6, 1e-3) == 1


def test_degenerates_to_revolve_for_short_chains():
    s, t_a, t_b, t_t = 10, 1e-3, 2e-3, 5e-3
    assert pm.t_async(8, 16, s, t_a, t_b, t_t) == \
        pm.t_revolve(8, s, t_a, t_b)


def test_forced_small_interval_stalls():
    """I < ceil(T_T/T_A): stores can't keep up; the model must show it."""
    s, t_a, t_b, t_t = 8, 1e-3, 2e-3, 16e-3
    fast = pm.t_async(256, 16, s, t_a, t_b, t_t)
    stalled = pm.t_async(256, 4, s, t_a, t_b, t_t)
    assert stalled > fast


def test_times_from_roofline():
    hw = pm.TPU_V5E
    st_ = pm.times_from_roofline(
        step_flops=1e12, step_hbm_bytes=1e9, state_bytes=100e6, hw=hw)
    assert st_.t_a == pytest.approx(max(1e12 / hw.peak_flops,
                                        1e9 / hw.hbm_bw))
    assert st_.interval == math.ceil(st_.t_t / st_.t_a)
    assert st_.never_stalls


# ---------------------------------------------------------------------------
# two-tier (capacity-bounded) Level-2 model
# ---------------------------------------------------------------------------


def test_effective_transfer_time_regimes():
    # 8 segments of 100 B: fast while they fit, slow-bound once they don't
    args = dict(n=64, interval=8, state_bytes=100, t_t_fast=1e-3,
                t_t_slow=8e-3)
    assert pm.effective_transfer_time(capacity_bytes=800, **args) == 1e-3
    assert pm.effective_transfer_time(capacity_bytes=799, **args) == 8e-3
    # the write-behind pipeline is bottlenecked by the slower stage
    assert pm.effective_transfer_time(
        n=64, interval=8, state_bytes=100, capacity_bytes=0,
        t_t_fast=9e-3, t_t_slow=8e-3) == 9e-3


def test_choose_tiered_interval():
    # everything fits at the fast optimum: the §3 fast-tier rule applies
    assert pm.choose_tiered_interval(
        n=64, state_bytes=100, capacity_bytes=100 * 64,
        t_a=1e-3, t_t_fast=4e-3, t_t_slow=32e-3) == 4
    # tight budget (4 states): I grows to the cheaper escape — here fitting
    # all boundaries on the fast tier (I=16) beats the slow-tier rate (I=32)
    assert pm.choose_tiered_interval(
        n=64, state_bytes=100, capacity_bytes=100 * 4,
        t_a=1e-3, t_t_fast=4e-3, t_t_slow=32e-3) == 16
    # slow tier keeps up sooner than the boundaries fit: accept the spill
    assert pm.choose_tiered_interval(
        n=64, state_bytes=100, capacity_bytes=100 * 2,
        t_a=1e-3, t_t_fast=4e-3, t_t_slow=8e-3) == 8
    # nothing ever fits (capacity < one state): the slow tier sets I
    assert pm.choose_tiered_interval(
        n=64, state_bytes=100, capacity_bytes=50,
        t_a=1e-3, t_t_fast=4e-3, t_t_slow=8e-3) == 8
    # never below the fast-tier optimum
    assert pm.choose_tiered_interval(
        n=64, state_bytes=100, capacity_bytes=50,
        t_a=1e-3, t_t_fast=8e-3, t_t_slow=1e-3) == 8


def test_t_async_tiered_constant_overhead_when_slow_keeps_up():
    """At I >= ceil(T_T_eff/T_A) the two-tier overhead is constant in n
    even when every boundary spills to the slow tier."""
    kw = dict(interval=8, s=4, t_a=1e-3, t_b=2e-3, t_t_fast=1e-3,
              t_t_slow=8e-3, state_bytes=100, capacity_bytes=100)
    per_step = [pm.t_async_tiered(n, **kw) / n for n in (64, 256, 1024)]
    assert max(per_step) < 1.05 * min(per_step)
    # a forced-small interval pays the slow tier's stall, visibly
    assert pm.t_async_tiered(256, interval=2, s=4, t_a=1e-3, t_b=2e-3,
                             t_t_fast=1e-3, t_t_slow=8e-3, state_bytes=100,
                             capacity_bytes=100) > \
        pm.t_async_tiered(256, **{**kw})


def test_fast_peak_bytes_model():
    assert pm.fast_peak_bytes_model(64, 8, 100, 100 * 64) == 800
    assert pm.fast_peak_bytes_model(64, 8, 100, 100 * 3) == 300
    assert pm.fast_peak_bytes_model(64, 8, 100, 50) == 0
    assert pm.fast_tier_slots(350, 100) == 3
    with pytest.raises(ValueError):
        pm.fast_tier_slots(100, 0)


def test_tier_plan_annotations():
    from repro.core.schedule import segment_plan

    plan = segment_plan(n=64, interval=8, s_l1=4)       # 8 segments
    assert plan.reverse_access_order() == tuple(range(56, -1, -8))
    tp = plan.tier_plan(capacity_bytes=3 * 100, state_bytes=100)
    assert tp.fast_slots == 3 and tp.spilled == 5
    # the 3 largest begins are resident when their reverse turn comes
    assert tp.resident == (False,) * 5 + (True,) * 3
    assert tp.prefetch_distance == 2
    # everything fits: plain double-buffering
    tp_all = plan.tier_plan(capacity_bytes=8 * 100, state_bytes=100)
    assert tp_all.spilled == 0 and tp_all.prefetch_distance == 1
    assert all(tp_all.resident)
    # timed distance: one slow fetch spans ~3 segments of reverse work
    tp_t = plan.tier_plan(capacity_bytes=100, state_bytes=100,
                          t_t_slow=3e-3, t_seg_reverse=1.1e-3)
    assert tp_t.prefetch_distance == 3


def test_hardware_for_keys_tpus_by_device_kind():
    from types import SimpleNamespace

    v5e = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert pm.hardware_for(v5e) is pm.TPU_V5E
    cpu = SimpleNamespace(platform="cpu", device_kind="cpu")
    assert pm.hardware_for(cpu) is pm.KNL
    other = SimpleNamespace(platform="tpu", device_kind="TPU v4")
    with pytest.raises(ValueError, match="TPU v4"):
        pm.hardware_for(other)

