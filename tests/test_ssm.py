"""SSM: chunked SSD vs sequential oracle; block train path vs decode path."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st  # optional dep, see shim

from repro.models.layers import DTypes
from repro.models.ssm import (init_mamba2, mamba2_block, mamba2_decode_step,
                              ssd_chunked, ssd_sequential)

KEY = jax.random.PRNGKey(0)
DT = DTypes(compute=jnp.float32)


def _ssd_inputs(b, t, h, g, p, n, seed=0):
    k = jax.random.fold_in(KEY, seed)
    x = jax.random.normal(jax.random.fold_in(k, 1), (b, t, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(k, 2),
                                           (b, t, h)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(k, 3), (h,)) * 0.3)
    B = jax.random.normal(jax.random.fold_in(k, 4), (b, t, g, n)) * 0.5
    C = jax.random.normal(jax.random.fold_in(k, 5), (b, t, g, n)) * 0.5
    return x, dt, A, B, C


@settings(deadline=None, max_examples=12)
@given(t=st.sampled_from([32, 64, 128]), chunk=st.sampled_from([8, 16, 32]),
       h=st.sampled_from([2, 4]), g=st.sampled_from([1, 2]))
def test_chunked_equals_sequential(t, chunk, h, g):
    if h % g:
        g = 1
    x, dt, A, B, C = _ssd_inputs(2, t, h, g, 8, 4)
    y_ref, h_ref = ssd_sequential(x, dt, A, B, C)
    y, hf = ssd_chunked(x, dt, A, B, C, chunk=chunk)
    np.testing.assert_allclose(np.array(y), np.array(y_ref), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.array(hf), np.array(h_ref), rtol=1e-4,
                               atol=1e-4)


def test_initial_state_threading():
    """Chunked processing with a carried state == one long scan — the
    uniform-state property the paper's checkpoints rely on."""
    x, dt, A, B, C = _ssd_inputs(1, 64, 2, 1, 8, 4)
    y_all, h_all = ssd_sequential(x, dt, A, B, C)
    # process in two halves, threading the state
    y1, h1 = ssd_chunked(x[:, :32], dt[:, :32], A, B[:, :32], C[:, :32],
                         chunk=16)
    y2, h2 = ssd_chunked(x[:, 32:], dt[:, 32:], A, B[:, 32:], C[:, 32:],
                         chunk=16, h0=h1)
    np.testing.assert_allclose(np.array(jnp.concatenate([y1, y2], axis=1)),
                               np.array(y_all), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.array(h2), np.array(h_all), rtol=1e-4,
                               atol=1e-4)


def test_block_train_equals_decode():
    d_model, b, t = 32, 2, 12
    p = init_mamba2(jax.random.fold_in(KEY, 9), d_model, d_state=8,
                    headdim=8, ngroups=1)
    x = jax.random.normal(jax.random.fold_in(KEY, 10), (b, t, d_model)) * 0.5
    y_train = mamba2_block(p, x, d_state=8, headdim=8, chunk=4, dt=DT)
    conv = jnp.zeros((b, 3, 2 * d_model + 16))
    ssm = jnp.zeros((b, (2 * d_model) // 8, 8, 8))
    ys = []
    for i in range(t):
        y, conv, ssm = mamba2_decode_step(p, x[:, i:i + 1], conv, ssm,
                                          d_state=8, headdim=8, dt=DT)
        ys.append(y)
    np.testing.assert_allclose(np.array(jnp.concatenate(ys, axis=1)),
                               np.array(y_train), rtol=2e-3, atol=2e-3)


def test_block_state_return_consistency():
    """prefill-style (return_state) then decode == one long train pass."""
    d_model, b = 32, 1
    p = init_mamba2(jax.random.fold_in(KEY, 11), d_model, d_state=8,
                    headdim=8)
    x = jax.random.normal(jax.random.fold_in(KEY, 12), (b, 16, d_model)) * 0.5
    y_full = mamba2_block(p, x, d_state=8, headdim=8, chunk=8, dt=DT)
    y_pre, (conv, ssm) = mamba2_block(p, x[:, :12], d_state=8, headdim=8,
                                      chunk=4, dt=DT, return_state=True)
    ys = [y_pre]
    for i in range(12, 16):
        y, conv, ssm = mamba2_decode_step(p, x[:, i:i + 1], conv,
                                          ssm.astype(jnp.float32),
                                          d_state=8, headdim=8, dt=DT)
        ys.append(y)
    np.testing.assert_allclose(np.array(jnp.concatenate(ys, axis=1)),
                               np.array(y_full), rtol=2e-3, atol=2e-3)


def test_grads_finite():
    x, dt, A, B, C = _ssd_inputs(1, 32, 2, 1, 8, 4)

    def loss(x):
        y, _ = ssd_chunked(x, dt, A, B, C, chunk=8)
        return jnp.sum(y ** 2)

    g = jax.grad(loss)(x)
    assert bool(jnp.all(jnp.isfinite(g)))


@pytest.mark.parametrize("h,g,p,n,t,chunk", [
    (4, 1, 8, 4, 64, 16),    # P > N
    (8, 1, 4, 8, 64, 16),    # P < N
    (8, 2, 8, 4, 64, 16),
    (4, 4, 8, 8, 64, 16),    # G = H
    (4, 2, 4, 8, 40, 16),    # T % chunk != 0: one chunk of all T
])
def test_chunked_grads_equal_sequential(h, g, p, n, t, chunk):
    """Gradients of x, dt, A, B, C and h0 through ``ssd_chunked`` equal the
    oracle's: B and C are contracted per group, so their gradients are the
    sums over each group's heads."""
    x, dt, A, B, C = _ssd_inputs(2, t, h, g, p, n, seed=h * 100 + g * 10 + p)
    h0 = jax.random.normal(jax.random.fold_in(KEY, 77), (2, h, p, n)) * 0.3
    wy = jax.random.normal(jax.random.fold_in(KEY, 78), (2, t, h, p))
    wh = jax.random.normal(jax.random.fold_in(KEY, 79), (2, h, p, n))

    def loss(fn):
        def f(x, dt, A, B, C, h0):
            y, hf = fn(x, dt, A, B, C, h0)
            return jnp.sum(y * wy) + jnp.sum(hf * wh)
        return f

    args = (x, dt, A, B, C, h0)
    ref = jax.jit(jax.grad(loss(lambda *a: ssd_sequential(*a[:5], h0=a[5])),
                           argnums=range(6)))(*args)
    got = jax.jit(jax.grad(loss(lambda *a: ssd_chunked(*a[:5], chunk=chunk,
                                                       h0=a[5])),
                           argnums=range(6)))(*args)
    for name, r, c in zip(("x", "dt", "A", "B", "C", "h0"), ref, got):
        assert c.shape == r.shape, name
        np.testing.assert_allclose(np.array(c), np.array(r), rtol=1e-4,
                                   atol=1e-4, err_msg=name)


def _jaxpr_vars(jaxpr):
    """Every variable bound in ``jaxpr`` and in its sub-jaxprs, with the
    equation that binds it."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield eqn, v
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _jaxpr_vars(sub)


def test_chunked_keeps_scores_and_bc_per_group():
    """With one group, whether P < N or P > N, neither the per-head C.B
    scores nor a head-broadcast copy of B or C is formed, forward or
    backward."""
    b, t, chunk, g, h = 2, 64, 16, 1, 8
    nc = t // chunk
    for p, n in ((4, 8), (6, 3)):
        x, dt, A, B, C = _ssd_inputs(b, t, h, g, p, n)
        f = functools.partial(ssd_chunked, chunk=chunk)

        fwd = jax.make_jaxpr(f)(x, dt, A, B, C).jaxpr
        scores = [v.aval.shape for e, v in _jaxpr_vars(fwd)
                  if e.primitive.name == "dot_general"]
        assert scores, "no contraction traced"
        assert (b, nc, h, chunk, chunk) not in scores, (p, n)

        def head_bc(shape):
            # (b, nc, chunk, h, n) in any order, or split as (g, h // g)
            return sorted(d for d in shape if d != 1) == sorted(
                (b, nc, chunk, h, n))

        def vjp(x, dt, A, B, C):
            out, back = jax.vjp(f, x, dt, A, B, C)
            return back(out)

        for jaxpr in (fwd, jax.make_jaxpr(vjp)(x, dt, A, B, C).jaxpr):
            shapes = [v.aval.shape for _, v in _jaxpr_vars(jaxpr)]
            assert not [s for s in shapes if head_bc(s)], (p, n)
