"""Training substrate: optimization, accumulation, checkpointing,
compression, fault tolerance, data pipeline."""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st  # optional dep, see shim

from repro.ckpt import CheckpointManager
from repro.configs import SMOKE_SHAPE, get_config
from repro.data import Prefetcher, SyntheticDataset
from repro.distributed import compression as comp
from repro.distributed.fault_tolerance import (StragglerWatchdog,
                                               elastic_mesh, with_retries)
from repro.models import get_model
from repro.optim import adamw, rmsprop, sgd, clip_by_global_norm
from repro.train import init_train_state, make_train_step

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen1.5-4b", smoke=True)
    api = get_model(cfg)
    opt = adamw(3e-3)
    ds = SyntheticDataset(cfg, SMOKE_SHAPE)
    batch = jax.tree_util.tree_map(jnp.asarray, ds.batch(0))
    return cfg, api, opt, ds, batch


@pytest.mark.slow
def test_overfits_fixed_batch(setup):
    cfg, api, opt, ds, batch = setup
    state = init_train_state(api, opt, KEY)
    step = jax.jit(make_train_step(api, opt))
    losses = []
    for _ in range(8):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 1.0
    assert int(state["step"]) == 8


@pytest.mark.slow
def test_grad_accum_matches_full_batch(setup):
    cfg, api, opt, ds, batch = setup
    s0 = init_train_state(api, opt, jax.random.PRNGKey(7))
    s1, m1 = jax.jit(make_train_step(api, opt))(s0, batch)
    s2, m2 = jax.jit(make_train_step(api, opt, grad_accum=2))(s0, batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3
    assert abs(float(m1["grad_norm"]) - float(m2["grad_norm"])) < \
        0.02 * float(m1["grad_norm"]) + 1e-3


@pytest.mark.parametrize("make_opt", [lambda: rmsprop(1e-3),
                                      lambda: sgd(1e-2, momentum=0.9)])
@pytest.mark.slow
def test_other_optimizers_reduce_loss(setup, make_opt):
    cfg, api, _, ds, batch = setup
    opt = make_opt()
    state = init_train_state(api, opt, KEY)
    step = jax.jit(make_train_step(api, opt))
    l0 = lN = None
    for i in range(6):
        state, m = step(state, batch)
        l0 = float(m["loss"]) if l0 is None else l0
        lN = float(m["loss"])
    assert lN < l0


def test_clip_by_global_norm():
    g = {"a": jnp.full((4,), 10.0), "b": jnp.full((3,), -10.0)}
    clipped, gn = clip_by_global_norm(g, 1.0)
    total = sum(float(jnp.sum(x ** 2))
                for x in jax.tree_util.tree_leaves(clipped))
    assert total == pytest.approx(1.0, rel=1e-5)
    assert float(gn) == pytest.approx(np.sqrt(700.0), rel=1e-6)


def test_checkpoint_roundtrip_and_resume(setup):
    cfg, api, opt, ds, batch = setup
    state = init_train_state(api, opt, KEY)
    step = jax.jit(make_train_step(api, opt))
    state, _ = step(state, batch)
    with tempfile.TemporaryDirectory() as d:
        with CheckpointManager(d, keep_last=2) as cm:
            cm.save(state, 1)
            state2, _ = step(state, batch)
            cm.save(state2, 2)
            cm.wait()
            assert cm.all_steps() == [1, 2]
            restored, s = cm.restore(state)
            assert s == 2
            for a, b in zip(jax.tree_util.tree_leaves(state2),
                            jax.tree_util.tree_leaves(restored)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # garbage collection respects keep_last
        with CheckpointManager(d, keep_last=1) as cm2:
            cm2.save(restored, 3)
            cm2.wait()
            assert cm2.all_steps()[-1] == 3


def test_checkpoint_atomic_publish():
    with tempfile.TemporaryDirectory() as d:
        with CheckpointManager(d) as cm:
            cm.save({"x": jnp.ones((8,))}, 1)
            cm.wait()
            import os
            assert not any(p.endswith(".tmp") for p in os.listdir(d))


# ---------------------------------------------------------------- compression
@settings(deadline=None, max_examples=25)
@given(scale=st.floats(1e-4, 1e3))
def test_quantization_error_bound(scale):
    x = jax.random.normal(jax.random.PRNGKey(3), (64,)) * scale
    q, s = comp.quantize(x)
    err = float(jnp.max(jnp.abs(comp.dequantize(q, s) - x)))
    assert err <= comp.quantization_error_bound(x) * 1.01 + 1e-12
    assert q.dtype == jnp.int8


def test_error_feedback_reduces_bias():
    """Repeated quantisation with EF must track the true running sum."""
    x = jax.random.normal(jax.random.PRNGKey(4), (256,)) * 0.01
    e = jnp.zeros_like(x)
    acc_q = jnp.zeros_like(x)
    for _ in range(50):
        g = x + e
        q, s = comp.quantize(g)
        dq = comp.dequantize(q, s)
        e = g - dq
        acc_q = acc_q + dq
    true = x * 50
    rel = float(jnp.linalg.norm(acc_q - true) / jnp.linalg.norm(true))
    assert rel < 0.01  # EF keeps the accumulated error tiny


def test_compressed_mean_single_axis():
    """compressed_mean over a trivial 1-device mesh axis is exact dequant."""
    from jax.sharding import Mesh
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P
    import numpy as np
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("pod",))
    tree = {"w": jnp.linspace(-1, 1, 32)}

    def f(t):
        m, e = comp.compressed_mean(t, "pod")
        return m, e

    m, e = shard_map(f, mesh=mesh, in_specs=(P(),), out_specs=(P(), P()))(tree)
    np.testing.assert_allclose(np.array(m["w"]), np.array(tree["w"]),
                               atol=comp.quantization_error_bound(tree["w"]))
    np.testing.assert_allclose(np.array(m["w"] + e["w"]),
                               np.array(tree["w"]), atol=1e-6)


# ------------------------------------------------------------ fault tolerance
def test_straggler_watchdog():
    import time
    wd = StragglerWatchdog(warmup=2, threshold=1.5)
    for step in range(4):
        wd.start()
        time.sleep(0.01)
        assert not wd.stop(step)
    wd.start()
    time.sleep(0.1)
    assert wd.stop(4)
    assert wd.slow_steps and wd.slow_steps[0][0] == 4


def test_elastic_mesh_shrinks_data_axis():
    mesh = elastic_mesh(1, model_parallelism=1)
    assert mesh.shape["data"] == 1 and mesh.shape["model"] == 1
    with pytest.raises(RuntimeError):
        elastic_mesh(0)


def test_with_retries():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("preempted")
        return 42

    assert with_retries(flaky, retries=3)() == 42
    assert calls["n"] == 3


def test_with_retries_recover_hook_runs_before_each_attempt():
    """The recovery path (checkpoint restore + journal resume in the
    launcher) must run between a failure and its re-attempt — and a
    typed StorageFault (a RuntimeError subclass) must be retryable."""
    from repro.core.faults import StorageFault

    seen = []
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise StorageFault(f"level-2 fault {calls['n']}")
        return "ok"

    def recover(attempt, err):
        assert isinstance(err, StorageFault)
        seen.append((attempt, calls["n"]))

    assert with_retries(flaky, retries=3, recover=recover)() == "ok"
    # recover ran after failure 1 (before attempt 2) and after failure 2
    assert seen == [(0, 1), (1, 2)]


@pytest.mark.parametrize("err", [
    NotImplementedError("Unimplemented primitive in Pallas TPU lowering"),
    jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: Ran out of memory"),
    jax.errors.JaxRuntimeError("INTERNAL: Mosaic failed to compile TPU "
                               "kernel"),
])
def test_with_retries_raises_permanent_errors_at_once(err):
    """Out-of-memory and compile failures repeat on retry, and a retry
    of a donating step would only report 'Array has been deleted'."""
    calls = {"n": 0}

    def fails():
        calls["n"] += 1
        raise err

    with pytest.raises(type(err)):
        with_retries(fails, retries=3)()
    assert calls["n"] == 1


@pytest.mark.slow
def test_launcher_retries_through_injected_storage_fault(tmp_path):
    """End-to-end launcher recovery: a step that dies to an injected
    Level-2 fetch failure must be retried in-process and the run must
    complete — requires both the journal's standing resume mode and the
    no-donation-under-journaling rule (a donated state would die on
    'Array has been deleted' at the first retry)."""
    from repro.core import faults
    from repro.core.faults import FaultPlan
    from repro.launch.train import main as train_main

    with faults.inject(FaultPlan(fail_get_at=1)):
        state = train_main([
            "--arch", "lstm-paper", "--smoke", "--steps", "2",
            "--strategy", "multistage_async", "--interval", "8",
            "--slots", "4", "--journal-dir", str(tmp_path / "wal")])
    assert int(state["step"]) == 2   # the faulted step was retried, not lost


def test_restore_of_gced_step_raises():
    """Regression: restore(step=) must refuse a step that was never saved
    or has been garbage-collected instead of handing back different
    weights — and the error lists what all_steps() still holds."""
    state = {"w": jnp.arange(4.0)}
    with tempfile.TemporaryDirectory() as d:
        with CheckpointManager(d, keep_last=2) as cm:
            for s in (1, 2, 3, 4):
                cm.save(state, s)
            cm.wait()
            assert cm.all_steps() == [3, 4]      # 1 and 2 were GC'd
            with pytest.raises(ValueError, match=r"step 1 not available"):
                cm.restore(state, step=1)
            with pytest.raises(ValueError, match=r"\[3, 4\]"):
                cm.restore(state, step=99)       # never saved
            _, s = cm.restore(state, step=3)     # an existing step is fine
            assert s == 3


# --------------------------------------------------------------------- data
def test_synthetic_data_deterministic():
    cfg = get_config("yi-6b", smoke=True)
    ds1 = SyntheticDataset(cfg, SMOKE_SHAPE, seed=1)
    ds2 = SyntheticDataset(cfg, SMOKE_SHAPE, seed=1)
    np.testing.assert_array_equal(ds1.batch(5)["tokens"],
                                  ds2.batch(5)["tokens"])
    assert not np.array_equal(ds1.batch(5)["tokens"], ds1.batch(6)["tokens"])
    assert ds1.batch(0)["tokens"].max() < cfg.vocab


def test_prefetcher_order_and_close():
    it = Prefetcher(iter(range(10)), depth=3)
    assert list(it) == list(range(10))
    it2 = Prefetcher(iter(range(1000)), depth=2)
    assert next(it2) == 0
    it2.close()


def test_host_sharded_batches():
    cfg = get_config("yi-6b", smoke=True)
    a = SyntheticDataset(cfg, SMOKE_SHAPE, host_id=0, num_hosts=2).batch(0)
    b = SyntheticDataset(cfg, SMOKE_SHAPE, host_id=1, num_hosts=2).batch(0)
    assert a["tokens"].shape[0] == SMOKE_SHAPE.global_batch // 2
    assert not np.array_equal(a["tokens"], b["tokens"])
