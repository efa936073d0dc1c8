"""Compile the main path for one described TPU v5e chip, at real widths.

Nothing here runs on a chip: each test lowers and compiles a kernel or a
jitted step against a v5e topology *described* to the TPU compiler, which
refuses what the chip would refuse (block shapes off the tiling, kernels
Mosaic cannot lower, programs that do not fit).  The topology is described
inside a module-scoped fixture, never at import, so every test worker
collects the same tests and only the worker given this file loads the TPU
library.  The persistent compile cache is off around these compiles: an
entry written for a described chip cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield topo
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def test_described_chip_is_v5e(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"
    from repro.core.perfmodel import TPU_V5E, hardware_for

    assert hardware_for(topo.devices[0]) is TPU_V5E


# ------------------------------------------------------------------ kernels


def test_flash_attention_compiles(one_chip):
    from repro.kernels import ops

    q = jax.ShapeDtypeStruct((1, 4096, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    c = jax.jit(lambda q, k, v: ops.flash_attention(
        q, k, v, interpret=False)).lower(q, q, q).compile()
    assert "tpu_custom_call" in c.as_text()


def test_lstm_cell_compiles(one_chip):
    from repro.kernels import ops

    B, Dx, Dh = 512, 64, 256
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in ((B, Dx), (B, Dh), (B, Dh), (Dx + Dh, 4 * Dh),
                      (4 * Dh,))]
    c = jax.jit(lambda *a: ops.lstm_cell(*a, interpret=False)).lower(
        *args).compile()
    assert "tpu_custom_call" in c.as_text()


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    """mamba2-370m: 32 heads of 64 channels, d_state 128, chunk 128."""
    from repro.configs import get_config
    from repro.kernels import ops

    cfg = get_config("mamba2-370m")
    s = cfg.ssm
    H = s.expand * cfg.d_model // s.headdim
    B, T, P, N, G = 1, 2048, s.headdim, s.d_state, s.ngroups
    f32 = jnp.float32

    def S(shape):
        return jax.ShapeDtypeStruct(shape, f32, sharding=one_chip)

    c = jax.jit(lambda x, dt, A, b, cc: ops.ssd_scan(
        x, dt, A, b, cc, chunk=s.chunk, interpret=False)).lower(
        S((B, T, H, P)), S((B, T, H)), S((H,)), S((B, T, G, N)),
        S((B, T, G, N))).compile()
    assert "tpu_custom_call" in c.as_text()


def test_pallas_segment_runner_refused_on_tpu(one_chip, monkeypatch):
    """The fused segment kernels do not lower for the chip (Mosaic has no
    rule for their per-chunk scan), so runner='pallas' raises on a TPU
    with that reason instead of falling back."""
    from repro.api import frontend
    from repro.api.chain import diff_mask
    from repro.configs import get_config
    from repro.kernels import segment_pallas as sp
    from repro.models.lstm import init_lstm, train_chain

    cfg = get_config("lstm-paper")
    B, T = 64, 64
    params = jax.eval_shape(lambda: init_lstm(
        jax.random.PRNGKey(0), cfg.vocab, cfg.d_model, cfg.d_ff))
    spec = train_chain(cfg)
    tokens = jax.ShapeDtypeStruct((B, T + 1), jnp.int32)
    carry0, xs = jax.eval_shape(lambda p, t: spec.prelude(p, {"tokens": t}),
                                params, tokens)
    treedef, mask = diff_mask(xs)
    with pytest.raises(NotImplementedError):
        jax.jit(lambda p, c, x: sp.fused_advance_segment(
            spec.body, treedef, mask, p, c, x, {}, chunk=16,
            interpret=False)).lower(
            _sds(params, one_chip), _sds(carry0, one_chip),
            _sds(xs, one_chip)).compile()

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg_off = frontend.OffloadConfig(runner="pallas")
    with pytest.raises(NotImplementedError, match="Mosaic"):
        frontend._select_runner(cfg_off)


# ------------------------------------------------------------ main path


def test_lstm_paper_scan_gradient_places_boundaries_on_host(one_chip):
    """The paper's chain at its published widths (B=64, T=4096): the scan
    engine's gradient compiles, with segment boundaries in host memory."""
    from repro import api
    from repro.configs import get_config
    from repro.models.lstm import init_lstm, train_chain

    cfg = get_config("lstm-paper")
    B, T = 64, 4096
    params = jax.eval_shape(lambda: init_lstm(
        jax.random.PRNGKey(0), cfg.vocab, cfg.d_model, cfg.d_ff))
    batch = {"tokens": jax.ShapeDtypeStruct((B, T + 1), jnp.int32)}
    vg = api.value_and_grad_offloaded(
        train_chain(cfg), strategy="multistage_async", engine="scan",
        interval=64)
    c = jax.jit(vg).lower(_sds(params, one_chip),
                          _sds(batch, one_chip)).compile()
    hlo = c.as_text()
    assert "S(5)" in hlo                      # host memory space
    assert hlo.count("copy-start") > 0        # async device<->host copies


def test_mamba2_two_layer_train_step_compiles(one_chip):
    """Two layers of mamba2-370m at published widths (d_model 1024,
    d_state 128, headdim 64, vocab 50280), B=2, T=2048, through the
    scan engine's jitted train step."""
    from repro.configs import get_config
    from repro.models import get_model
    from repro.optim import adamw
    from repro.train import init_train_state, make_train_step

    cfg = get_config("mamba2-370m").replace(n_layers=2)
    model = get_model(cfg)
    opt = adamw(3e-4)
    state = jax.eval_shape(
        lambda: init_train_state(model, opt, jax.random.PRNGKey(0)))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 2049), jnp.int32)}
    step = make_train_step(model, opt, strategy="multistage_async",
                           engine="scan", offload_opts={"interval": 1})
    c = jax.jit(step, donate_argnums=(0,)).lower(
        _sds(state, one_chip), _sds(batch, one_chip)).compile()
    assert "S(5)" in c.as_text()              # boundaries on the host
    mem = c.memory_analysis()
    assert 0 < mem.temp_size_in_bytes
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9
