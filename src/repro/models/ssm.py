"""Mamba-2 (SSD — state-space duality) blocks, pure JAX.

The SSD recurrence per head (state N, head dim P)::

    h_t = a_t * h_{t-1} + dt_t * B_t  (outer) x_t         h: (P, N)
    y_t = h_t @ C_t + D * x_t                             a_t = exp(dt_t * A)

``ssd_chunked`` evaluates it with the chunked algorithm of the Mamba-2 paper:
intra-chunk terms as batched matmuls (MXU-friendly), inter-chunk state passed
through a short ``lax.scan``.  This is the sub-quadratic sequence mixer that
makes the ``long_500k`` shape feasible, and the chain whose per-chunk states
are exactly the paper's uniform checkpoints: ``multistage_scan`` over the
chunk axis offloads every I-th chunk state to host memory.

``ssd_sequential`` is the O(T) oracle used by tests.  The Pallas kernel in
``repro.kernels.ssd_scan`` runs the same chunked algorithm one head at a
time, on B and C repeated per head (``ssd_chunked`` keeps them per group);
no model calls it.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import offload as ofl
from repro.models.layers import DTypes, DEFAULT_DTYPES, dense, init_dense

Params = Any


# ---------------------------------------------------------------------------
# SSD core
# ---------------------------------------------------------------------------


def ssd_sequential(x, dt, A, B, C, h0=None):
    """Oracle recurrence.  x: (b,t,h,p); dt: (b,t,h); A: (h,);
    B, C: (b,t,g,n) with heads mapped to groups h -> h % g... heads per group
    = H // G contiguous blocks.  Returns (y (b,t,h,p), h_final (b,h,p,n))."""
    b, t, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    rep = H // G
    Bh = jnp.repeat(B, rep, axis=2)  # (b,t,H,n)
    Ch = jnp.repeat(C, rep, axis=2)
    a = jnp.exp(dt * A[None, None, :])  # (b,t,H)
    if h0 is None:
        h0 = jnp.zeros((b, H, P, N), jnp.float32)

    def step(h, args):
        xt, at, dtt, Bt, Ct = args
        upd = jnp.einsum("bhp,bhn->bhpn", xt * dtt[..., None], Bt)
        h = h * at[..., None, None] + upd
        y = jnp.einsum("bhpn,bhn->bhp", h, Ct)
        return h, y

    xs = (x.transpose(1, 0, 2, 3).astype(jnp.float32),
          a.transpose(1, 0, 2), dt.transpose(1, 0, 2),
          Bh.transpose(1, 0, 2, 3).astype(jnp.float32),
          Ch.transpose(1, 0, 2, 3).astype(jnp.float32))
    hf, ys = lax.scan(step, h0, xs)
    return ys.transpose(1, 0, 2, 3), hf


def ssd_chunked(x, dt, A, B, C, *, chunk: int = 64, h0=None):
    """Chunked SSD (Mamba-2 alg.).  Same contract as ``ssd_sequential``.

    B and C stay at group granularity: heads are split as (group, head in
    group), ``h = g * (H // G) + r``, and every contraction with B or C runs
    per group.  The C.B scores are formed once per group and meet the
    per-head decay only in the elementwise product that masks them.  The
    per-head decays of the chunk states (``exp(ca_last - ca)``) and of the
    inter-chunk output (``exp(ca)``) go on the (..., H, P) side: on x before
    the state contraction, on the output after it.  So no (..., H, N) copy
    of B or C exists, and autodiff saves and differentiates per-group B and
    C only.
    """
    b, T, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    R = H // G
    if T % chunk != 0:
        chunk = T
    nc = T // chunk

    xf = x.astype(jnp.float32).reshape(b, nc, chunk, G, R, P)
    dtf = dt.astype(jnp.float32).reshape(b, nc, chunk, G, R)
    Bf = B.astype(jnp.float32).reshape(b, nc, chunk, G, N)
    Cf = C.astype(jnp.float32).reshape(b, nc, chunk, G, N)
    la = dtf * A.reshape(G, R)                 # log a  (b,c,l,g,r)
    ca = jnp.cumsum(la, axis=2)                # cumulative within chunk
    xbar = xf * dtf[..., None]                 # dt-weighted input

    # ---- intra-chunk (dual / attention-like form) --------------------------
    cb = jnp.einsum("bclgn,bcsgn->bcgls", Cf, Bf)          # per group
    ca_t = jnp.moveaxis(ca, 2, -1)                          # (b,c,g,r,l)
    seg = ca_t[..., :, None] - ca_t[..., None, :]           # (b,c,g,r,l,s)
    li = jnp.arange(chunk)
    causal = li[:, None] >= li[None, :]
    # mask BEFORE exp: exp of masked (positive) entries overflows and the
    # where-VJP would produce 0 * inf = NaN gradients otherwise.
    seg = jnp.where(causal, seg, -jnp.inf)
    M = cb[:, :, :, None] * jnp.exp(seg)                    # (b,c,g,r,l,s)
    y_intra = jnp.einsum("bcgrls,bcsgrp->bclgrp", M, xbar)

    # ---- chunk states -------------------------------------------------------
    last = ca[:, :, -1:]                                    # (b,c,1,g,r)
    dec_to_end = jnp.exp(last - ca)[..., None]              # (b,c,l,g,r,1)
    S_c = jnp.einsum("bclgn,bclgrp->bcgrpn", Bf, xbar * dec_to_end)
    S_c = S_c.reshape(b, nc, H, P, N)

    # ---- inter-chunk scan ----------------------------------------------------
    chunk_decay = jnp.exp(last[:, :, 0]).reshape(b, nc, H)  # (b,c,h)
    if h0 is None:
        h0 = jnp.zeros((b, H, P, N), jnp.float32)

    def pass_state(h, args):
        s_c, dec = args
        h_next = h * dec[..., None, None] + s_c
        return h_next, h  # emit the state *entering* the chunk

    (hf, h_before) = lax.scan(
        pass_state, h0,
        (S_c.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)))
    h_before = h_before.transpose(1, 0, 2, 3, 4).reshape(b, nc, G, R, P, N)

    dec_in = jnp.exp(ca)[..., None]                         # (b,c,l,g,r,1)
    y_inter = jnp.einsum("bclgn,bcgrpn->bclgrp", Cf, h_before) * dec_in
    y = (y_intra + y_inter).reshape(b, T, H, P)
    return y.astype(x.dtype), hf


# ---------------------------------------------------------------------------
# Mamba-2 block
# ---------------------------------------------------------------------------


def init_mamba2(key, d_model: int, *, d_state: int = 128, headdim: int = 64,
                expand: int = 2, ngroups: int = 1, conv_k: int = 4,
                dtype=jnp.float32) -> Params:
    d_inner = expand * d_model
    nheads = d_inner // headdim
    conv_dim = d_inner + 2 * ngroups * d_state
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d_proj = 2 * d_inner + 2 * ngroups * d_state + nheads
    return {
        "in_proj": init_dense(k1, d_model, d_proj, dtype=dtype),
        "conv_w": jax.random.normal(k2, (conv_k, conv_dim), dtype) * 0.2,
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nheads).astype(dtype)),
        "D": jnp.ones((nheads,), dtype),
        "dt_bias": jnp.zeros((nheads,), dtype),
        "norm_scale": jnp.zeros((d_inner,), dtype),
        "out_proj": init_dense(k4, d_inner, d_model, dtype=dtype),
    }


def _split_proj(z, d_inner, ngroups, d_state, nheads):
    zs = [d_inner, d_inner, ngroups * d_state, ngroups * d_state, nheads]
    idx = [0]
    for s in zs:
        idx.append(idx[-1] + s)
    return tuple(z[..., idx[i]:idx[i + 1]] for i in range(5))


def _gated_norm(p, y, z, eps=1e-6):
    yf = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(yf * yf, axis=-1, keepdims=True)
    return (yf * lax.rsqrt(var + eps) *
            (1.0 + p["norm_scale"].astype(jnp.float32)))


def mamba2_block(p: Params, x: jnp.ndarray, *, d_state: int = 128,
                 headdim: int = 64, expand: int = 2, ngroups: int = 1,
                 conv_k: int = 4, chunk: int = 64,
                 dt: DTypes = DEFAULT_DTYPES, state=None,
                 return_state: bool = False):
    """Full-sequence (training/prefill) Mamba-2 mixer.  x: (B, T, d).

    ``state`` / ``return_state``: optional (conv_state (B, K-1, conv_dim),
    ssm_state (B, H, P, N)) for chunked long-sequence processing — this is
    the uniform carry that ``multistage_scan`` offloads when BPTT-ing over
    sequence segments (the paper's RNN case, on an SSM).
    """
    Bsz, T, d_model = x.shape
    d_inner = expand * d_model
    nheads = d_inner // headdim
    zxbcdt = dense(p["in_proj"], x, dt)
    z, xi, Bc, Cc, dt_raw = _split_proj(zxbcdt, d_inner, ngroups, d_state, nheads)

    # causal depthwise conv over (x, B, C); prev conv window via `state`
    xbc = jnp.concatenate([xi, Bc, Cc], axis=-1)
    conv_state_in = (state[0] if state is not None else
                     jnp.zeros((Bsz, conv_k - 1, xbc.shape[-1]), xbc.dtype))
    pad = jnp.concatenate([conv_state_in.astype(xbc.dtype), xbc], axis=1)
    conv = sum(
        pad[:, i:i + T, :] * dt.c(p["conv_w"][i])[None, None, :]
        for i in range(conv_k)
    ) + dt.c(p["conv_b"])
    conv = jax.nn.silu(conv)
    new_conv_state = pad[:, T:, :]
    xi = conv[..., :d_inner]
    Bc = conv[..., d_inner:d_inner + ngroups * d_state]
    Cc = conv[..., d_inner + ngroups * d_state:]

    dts = jax.nn.softplus(dt_raw.astype(jnp.float32) +
                          p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    xh = xi.reshape(Bsz, T, nheads, headdim)
    Bg = Bc.reshape(Bsz, T, ngroups, d_state)
    Cg = Cc.reshape(Bsz, T, ngroups, d_state)
    h0 = state[1].astype(jnp.float32) if state is not None else None
    with jax.named_scope(ofl.SCOPE_SSD):
        y, hf = ssd_chunked(xh, dts, A, Bg, Cg, chunk=chunk, h0=h0)
    y = y + p["D"].astype(y.dtype)[None, None, :, None] * xh
    y = y.reshape(Bsz, T, d_inner)
    y = _gated_norm(p, y, z).astype(dt.compute)
    out = dense(p["out_proj"], y, dt)
    if return_state:
        return out, (new_conv_state.astype(jnp.float32), hf)
    return out


# ---------------------------------------------------------------------------
# decode path (single-token recurrence)
# ---------------------------------------------------------------------------


def init_ssm_cache(batch: int, d_model: int, *, d_state: int = 128,
                   headdim: int = 64, expand: int = 2, ngroups: int = 1,
                   conv_k: int = 4, n_layers: int = 1,
                   dtype=jnp.float32) -> Params:
    d_inner = expand * d_model
    nheads = d_inner // headdim
    conv_dim = d_inner + 2 * ngroups * d_state
    return {
        "conv": jnp.zeros((n_layers, batch, conv_k - 1, conv_dim), dtype),
        "ssm": jnp.zeros((n_layers, batch, nheads, headdim, d_state), dtype),
    }


def mamba2_decode_step(p: Params, x: jnp.ndarray, conv_state, ssm_state, *,
                       d_state: int = 128, headdim: int = 64, expand: int = 2,
                       ngroups: int = 1, conv_k: int = 4,
                       dt: DTypes = DEFAULT_DTYPES):
    """One token.  x: (B, 1, d); conv_state: (B, conv_k-1, conv_dim);
    ssm_state: (B, H, P, N).  Returns (y, conv_state, ssm_state)."""
    Bsz, _, d_model = x.shape
    d_inner = expand * d_model
    nheads = d_inner // headdim
    zxbcdt = dense(p["in_proj"], x, dt)[:, 0]
    z, xi, Bc, Cc, dt_raw = _split_proj(zxbcdt, d_inner, ngroups, d_state, nheads)

    xbc = jnp.concatenate([xi, Bc, Cc], axis=-1)  # (B, conv_dim)
    window = jnp.concatenate([conv_state, xbc[:, None, :]], axis=1)
    conv = jnp.einsum("bkc,kc->bc", window.astype(jnp.float32),
                      p["conv_w"].astype(jnp.float32)) + \
        p["conv_b"].astype(jnp.float32)
    conv = jax.nn.silu(conv)
    new_conv_state = window[:, 1:, :]
    xi = conv[..., :d_inner]
    Bc = conv[..., d_inner:d_inner + ngroups * d_state]
    Cc = conv[..., d_inner + ngroups * d_state:]

    dts = jax.nn.softplus(dt_raw.astype(jnp.float32) +
                          p["dt_bias"].astype(jnp.float32))  # (B, H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    a = jnp.exp(dts * A[None, :])  # (B, H)
    rep = nheads // ngroups
    xh = xi.reshape(Bsz, nheads, headdim)
    Bh = jnp.repeat(Bc.reshape(Bsz, ngroups, d_state), rep, axis=1)
    Ch = jnp.repeat(Cc.reshape(Bsz, ngroups, d_state), rep, axis=1)
    upd = jnp.einsum("bhp,bhn->bhpn", xh * dts[..., None], Bh)
    new_ssm = ssm_state * a[..., None, None] + upd
    y = jnp.einsum("bhpn,bhn->bhp", new_ssm, Ch)
    y = y + p["D"].astype(jnp.float32)[None, :, None] * xh
    y = y.reshape(Bsz, d_inner)
    y = _gated_norm(p, y, z).astype(dt.compute)
    y = dense(p["out_proj"], y[:, None, :], dt)
    return y, new_conv_state.astype(conv_state.dtype), new_ssm.astype(ssm_state.dtype)
