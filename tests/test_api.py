"""The differentiable front-end: ``value_and_grad_offloaded`` must be a
drop-in ``jax.value_and_grad`` — same values, same gradients (fp32
tolerance) — on every chain-structured model family, with executor stats
showing the paper's memory behaviour (peak Level-1 states O(interval+slots),
independent of sequence length)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.api.autotune import AutoTuner, snap_interval, default_slots
from repro.configs import SMOKE_SHAPE, get_config
from repro.configs.shapes import make_batch
from repro.models import get_model

KEY = jax.random.PRNGKey(0)


from _helpers import max_rel_err as _max_err  # noqa: E402


# ---------------------------------------------------------------------------
# checkpointed_bptt on a synthetic chain
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def rnn_chain():
    T, B, D = 37, 4, 8
    params = {"W": jax.random.normal(KEY, (D, D)) * 0.4,
              "U": jax.random.normal(jax.random.fold_in(KEY, 1), (D, D)) * 0.2}
    xs = jax.random.normal(jax.random.fold_in(KEY, 2), (T, B, D)) * 0.1
    c0 = jnp.zeros((B, D))

    def body(p, c, x):
        c = jnp.tanh(c @ p["W"] + x @ p["U"])
        return c, jnp.sum(c ** 2)

    def ref_loss(p):
        _, ls = jax.lax.scan(lambda c, x: body(p, c, x), c0, xs)
        return jnp.sum(ls)

    ref_v, ref_g = jax.value_and_grad(ref_loss)(params)
    return params, c0, xs, body, float(ref_v), ref_g


@pytest.mark.parametrize("strategy,opts", [
    ("conventional", {}),
    ("revolve", dict(slots=6)),
    ("multistage_async", dict(interval=8, slots=6)),
    ("multistage_async", dict(interval=8, slots=6, storage="disk")),
])
def test_checkpointed_bptt_matches_autodiff(rnn_chain, strategy, opts):
    params, c0, xs, body, ref_v, ref_g = rnn_chain
    bptt = api.checkpointed_bptt(body, strategy=strategy, **opts)
    v, g = bptt(params, c0, xs)
    assert abs(float(v) - ref_v) < 1e-5
    assert _max_err(g, ref_g) < 1e-5


def test_checkpointed_bptt_under_jit(rnn_chain):
    params, c0, xs, body, ref_v, ref_g = rnn_chain
    bptt = api.checkpointed_bptt(body, strategy="multistage_async",
                                 interval=8, slots=6)
    v, g = jax.jit(bptt)(params, c0, xs)
    assert abs(float(v) - ref_v) < 1e-5
    assert _max_err(g, ref_g) < 1e-5


def test_peak_l1_constant_in_sequence_length():
    """The paper's headline memory claim through the public API: peak
    Level-1 states stay bounded by slots + O(1) while the chain grows 8x."""
    B, D = 2, 8
    params = {"W": jax.random.normal(KEY, (D, D)) * 0.4}

    def body(p, c, x):
        c = jnp.tanh(c @ p["W"] + x)
        return c, jnp.sum(c ** 2)

    peaks, stores = {}, {}
    for T in (32, 256):
        xs = jax.random.normal(jax.random.fold_in(KEY, T), (T, B, D)) * 0.1
        bptt = api.checkpointed_bptt(body, strategy="multistage_async",
                                     interval=16, slots=4)
        bptt(params, jnp.zeros((B, D)), xs)
        st = api.last_stats()
        peaks[T] = st.peak_l1_states
        stores[T] = st.l2_stores
    # Level-1: bounded by slots + O(1), independent of T
    assert peaks[32] <= 4 + 2
    assert peaks[256] <= 4 + 2
    assert peaks[256] <= peaks[32] + 1
    # Level-2 stores grow with T instead (n / interval boundary states)
    assert stores[32] == 2 and stores[256] == 16


def test_recompute_factor_constant_in_length():
    B, D = 2, 8
    params = {"W": jax.random.normal(KEY, (D, D)) * 0.4}

    def body(p, c, x):
        c = jnp.tanh(c @ p["W"] + x)
        return c, jnp.sum(c ** 2)

    factors = []
    for T in (64, 512):
        xs = jnp.zeros((T, B, D))
        bptt = api.checkpointed_bptt(body, strategy="multistage_async",
                                     interval=16, slots=4)
        bptt(params, jnp.zeros((B, D)), xs)
        factors.append(api.last_stats().recompute_factor)
    assert abs(factors[1] - factors[0]) < 0.05


# ---------------------------------------------------------------------------
# model families: gradients must match jax.value_and_grad
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("arch,tol", [
    ("lstm-paper", 1e-5),      # fp32 time chain (the paper's §5 model)
    ("granite-3-2b", 2e-2),    # bf16 dense transformer, depth chain
    ("mamba2-370m", 2e-2),     # bf16 SSM, depth chain
])
def test_model_chain_matches_value_and_grad(arch, tol):
    cfg = get_config(arch, smoke=True)
    m = get_model(cfg)
    assert m.train_chain is not None
    params = m.init(jax.random.fold_in(KEY, 7))
    batch = make_batch(cfg, SMOKE_SHAPE)
    ref_v, ref_g = jax.value_and_grad(m.train_loss)(params, batch)
    vg = api.value_and_grad_offloaded(m.train_loss, interval=2, slots=2)
    v, g = vg(params, batch)
    assert abs(float(v) - float(ref_v)) <= tol
    assert _max_err(g, ref_g) <= tol
    assert jax.tree_util.tree_structure(g) == \
        jax.tree_util.tree_structure(ref_g)


@pytest.mark.slow
def test_moe_chain_matches_value_and_grad():
    cfg = get_config("phi3.5-moe-42b", smoke=True)
    m = get_model(cfg)
    params = m.init(jax.random.fold_in(KEY, 8))
    batch = make_batch(cfg, SMOKE_SHAPE)
    ref_v, ref_g = jax.value_and_grad(m.train_loss)(params, batch)
    vg = api.value_and_grad_offloaded(m.train_loss, interval=1)
    v, g = vg(params, batch)
    assert abs(float(v) - float(ref_v)) <= 2e-2
    assert _max_err(g, ref_g) <= 2e-2


def test_chain_loss_value_only_path():
    """Calling the offloaded loss without differentiation uses the plain
    scan primal — value equals the reference loss."""
    cfg = get_config("lstm-paper", smoke=True)
    m = get_model(cfg)
    params = m.init(KEY)
    batch = make_batch(cfg, SMOKE_SHAPE)
    loss = api.offloaded_loss(m.train_chain, api.OffloadConfig())
    np.testing.assert_allclose(float(loss(params, batch)),
                               float(m.train_loss(params, batch)), rtol=1e-6)


def test_fallback_without_chain_spec():
    def plain_loss(params, batch):
        return jnp.sum(params["w"] ** 2) * batch

    with pytest.warns(UserWarning, match="no chain decomposition"):
        vg = api.value_and_grad_offloaded(plain_loss)
    v, g = vg({"w": jnp.arange(3.0)}, 2.0)
    np.testing.assert_allclose(np.array(g["w"]), np.array([0., 4., 8.]))
    with pytest.raises(TypeError):
        api.value_and_grad_offloaded(plain_loss, fallback=False)


# ---------------------------------------------------------------------------
# autotuner
# ---------------------------------------------------------------------------


def test_snap_interval():
    assert snap_interval(48, 8) == 8       # exact divisor
    assert snap_interval(48, 7) == 8       # nearby divisor wins, upward
    assert snap_interval(48, 5) == 6       # never below the optimum:
    #                                        I = ceil(T_T/T_A) is the
    #                                        minimum no-stall interval
    assert snap_interval(37, 8) == 8       # prime length: keep the optimum
    assert snap_interval(48, 1000) == 48   # capped at n
    assert snap_interval(48, 0) == 1
    # the no-stall invariant: the snap never shrinks the interval
    for n in (24, 37, 48, 97):
        for t in range(1, n + 1):
            assert t <= snap_interval(n, t) <= min(2 * t, n), (n, t)


def test_default_slots():
    assert default_slots(4, 16) == 4       # interval <= budget: store-all
    assert default_slots(64, 16) == 16


def test_autotuner_measures_and_caches():
    from repro.core.storage import RAMStorage

    tuner = AutoTuner(repeats=1)
    state0 = jnp.zeros((4, 16))

    calls = []

    def forward_step(state, k):
        calls.append(k)
        return state

    backend = RAMStorage()
    r1 = tuner.measure("m", forward_step=forward_step, state0=state0,
                       n=64, backend=backend)
    assert r1.source == "measured"
    assert 1 <= r1.interval <= 64
    assert r1.slots >= 1
    n_calls = len(calls)
    r2 = tuner.measure("m", forward_step=forward_step, state0=state0,
                       n=64, backend=backend)
    assert r2 is r1               # cached: no re-measurement
    assert len(calls) == n_calls
    assert not list(backend.keys())  # probe state cleaned up


def test_autotune_end_to_end_first_call():
    """interval=None: first call measures T_A/T_T and records the choice."""
    B, D = 2, 8
    params = {"W": jax.random.normal(KEY, (D, D)) * 0.4}

    def body(p, c, x):
        c = jnp.tanh(c @ p["W"] + x)
        return c, jnp.sum(c ** 2)

    xs = jnp.zeros((48, B, D))
    tuner = AutoTuner(repeats=1)
    bptt = api.checkpointed_bptt(body, strategy="multistage_async",
                                 tuner=tuner)
    bptt(params, jnp.zeros((B, D)), xs)
    tune = api.last_tune()
    assert tune.source == "measured"
    assert tune.t_a > 0 and tune.t_t > 0
    assert 1 <= tune.interval <= 48
    assert tune.never_stalls or tune.interval == 48


def test_roofline_tuning_path():
    from repro.core.perfmodel import TPU_V5E

    tuner = AutoTuner()
    r = tuner.from_roofline("roof", n=4096, step_flops=1e12,
                            step_hbm_bytes=1e9, state_bytes=64e6, hw=TPU_V5E)
    assert r.source == "roofline"
    # I = ceil(T_T/T_A) with T_A = max(flops, bytes) roofline terms
    t_a = max(1e12 / TPU_V5E.peak_flops, 1e9 / TPU_V5E.hbm_bw)
    t_t = 64e6 / TPU_V5E.d2h_bw
    assert r.interval >= 1
    assert r.interval * t_a >= t_t * 0.5  # never badly transfer-bound


# ---------------------------------------------------------------------------
# train-step integration
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_train_step_with_strategy():
    from repro.optim import rmsprop
    from repro.train import init_train_state, make_train_step

    cfg = get_config("lstm-paper", smoke=True)
    m = get_model(cfg)
    opt = rmsprop(5e-3)
    state = init_train_state(m, opt, KEY)
    step = make_train_step(m, opt, strategy="multistage_async",
                           offload_opts=dict(interval=8, slots=4))
    batch = make_batch(cfg, SMOKE_SHAPE)
    losses = []
    for _ in range(6):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]
    assert api.last_stats().peak_l1_states <= 8


def test_train_step_strategy_rejects_unchained_family():
    from repro.optim import sgd
    from repro.train import make_train_step

    cfg = get_config("whisper-tiny", smoke=True)
    m = get_model(cfg)
    assert m.train_chain is None
    with pytest.raises(ValueError, match="no chain decomposition"):
        make_train_step(m, sgd(1e-3), strategy="multistage_async")


def test_unknown_strategy_rejected():
    with pytest.raises(ValueError, match="unknown strategy"):
        api.OffloadConfig(strategy="nope")


@pytest.mark.parametrize("engine", ["compiled", "interpreted"])
def test_executor_engines_refused_on_tpu(engine, monkeypatch):
    """On a TPU io_callback would run the executor engines' segments on
    the host CPU: they raise naming engine='scan', which is the default
    there — no silent switch of an engine the caller asked for."""
    spec = api.ChainSpec(lambda p, b: (b["c0"], b["xs"]),
                         lambda p, c, x, b: c + p["w"] * jnp.tanh(x + c),
                         lambda p, c, b: jnp.sum(c), name="tpu-refusal")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert api.default_engine() == "scan"
    with pytest.raises(NotImplementedError, match="engine='scan'"):
        api.value_and_grad_offloaded(spec, engine=engine)
    vg = api.value_and_grad_offloaded(spec)
    assert vg.offload_config.engine == "scan"
    monkeypatch.undo()
    assert api.default_engine() == "compiled"
