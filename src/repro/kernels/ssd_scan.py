"""Pallas TPU kernel for the Mamba-2 chunked SSD scan.

One grid step processes one (batch*head, chunk) cell: the L x L intra-chunk
dual form runs as two small MXU matmuls, and the (P x N) running state lives
in VMEM scratch carried across the chunk axis (innermost grid dimension) —
the TPU analogue of the GPU kernel's register-resident state.

Inputs are head-flattened (wrapper in ``ops.py``):
    x  (BH, T, P)   dt-weighted inputs are formed in-kernel
    la (BH, T)      per-step log decay (dt * A, negative)
    b, c (BH, T, N)
    dt (BH, T)
Outputs: y (BH, T, P) and the final state h (BH, P, N).

Mosaic layout constraints shape the kernel: the per-step rows ``la``/``dt``
enter as ``(1, chunk)`` lane rows (a block's last two dims must tile by
(8, 128) or span the array, so ``chunk`` is a multiple of 128 on a TPU
unless it is all of ``T``); the in-chunk prefix sum is a matmul with an
upper-triangular ones matrix (Mosaic has no ``cumsum``); and a scalar is
broadcast to a matrix in two steps, sublanes then lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, la_ref, b_ref, c_ref, dt_ref, y_ref, hout_ref, h_ref, *,
            chunk: int, n_chunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)          # (L, P)
    la = la_ref[0].astype(jnp.float32)        # (1, L)
    b = b_ref[0].astype(jnp.float32)          # (L, N)
    c = c_ref[0].astype(jnp.float32)          # (L, N)
    dt = dt_ref[0].astype(jnp.float32)        # (1, L)
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # inclusive prefix sum along the row: ca[i] = sum_{j<=i} la[j]
    ca_row = jax.lax.dot(la, (li <= lj).astype(jnp.float32),
                         preferred_element_type=jnp.float32)   # (1, L)
    ca = ca_row.T                             # (L, 1)
    xbar = x * dt.T

    # intra-chunk: y_i += sum_{j<=i} exp(ca_i - ca_j) (c_i . b_j) xbar_j
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L, L)
    seg = jnp.where(li >= lj, ca - ca_row, -jnp.inf)
    m = cb * jnp.exp(seg)
    y = jax.lax.dot(m, xbar, preferred_element_type=jnp.float32)

    # inter-chunk: y_i += exp(ca_i) * (c_i @ h^T);  h: (P, N)
    h = h_ref[...]
    y += jnp.exp(ca) * jax.lax.dot_general(
        c, h, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    # state update: h' = exp(ca_L) h + sum_j exp(ca_L - ca_j) xbar_j (x) b_j
    last = ca_row[:, chunk - 1:chunk]         # (1, 1)
    w = jnp.exp(last - ca)                    # (L, 1)
    decay = jnp.exp(jnp.broadcast_to(last, (h.shape[0], 1)))   # (P, 1)
    h_new = h * decay + jax.lax.dot_general(
        xbar * w, b, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)   # (P, N)
    h_ref[...] = h_new
    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        hout_ref[0] = h_new.astype(hout_ref.dtype)


def ssd_scan(x: jnp.ndarray, la: jnp.ndarray, b: jnp.ndarray,
             c: jnp.ndarray, dt: jnp.ndarray, *, chunk: int = 128,
             interpret: bool = False):
    """Chunked SSD scan.  Shapes as in the module docstring."""
    BH, T, P = x.shape
    N = b.shape[-1]
    chunk = min(chunk, T)
    assert T % chunk == 0
    nc = T // chunk
    kernel = functools.partial(_kernel, chunk=chunk, n_chunks=nc)
    row = pl.BlockSpec((1, 1, chunk), lambda bh, ci: (bh, 0, ci))
    return pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda bh, ci: (bh, ci, 0)),
            row,
            pl.BlockSpec((1, chunk, N), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda bh, ci: (bh, ci, 0)),
            row,
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, P, N), lambda bh, ci: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, P), x.dtype),
            jax.ShapeDtypeStruct((BH, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(x, la.reshape(BH, 1, T), b, c, dt.reshape(BH, 1, T))
