"""Plain fp32 reference of lstm-paper's training loss and gradients.

The source paper's §5 test case (arXiv:1806.01117): a character-level
vanilla LSTM (embedding, one LSTM cell, a linear head), run over the whole
sequence; the loss is the sum over time steps of the batch-mean
next-character cross entropy.  The system's conventions that the
reference shares, because the weights are random: the gates are packed
``[input, forget, output, cell]`` in one matrix over ``[x, h]``, the forget
gate has a +1 bias, and the weights are drawn as the system's initialiser
draws them from the seed (the recipe is written out below).

fp32 with matmuls at "highest" precision.  Plain autodiff over 65536 steps
keeps about 27 GB of residuals, so the scan runs over blocks of
``BLOCK`` steps under ``jax.checkpoint``: the backward keeps one carry per
block and recomputes one block at a time.

``control=True`` computes the matmuls as "high" precision does on a TPU,
the precision below the configuration's: each operand split into a
bfloat16 high part and a bfloat16 low part, and the three products other
than low x low summed in fp32.  It is written out so that it is the same
on every platform.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 256


def init_params(key, c):
    """The seed's weights, drawn as the system draws them."""
    E, H, V = c["embed_dim"], c["hidden_size"], c["vocab_size"]
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "emb": jax.random.normal(k1, (V, E)) * 0.1,
        "w": jax.random.normal(k2, (E + H, 4 * H)) * (E + H) ** -0.5,
        "b": jnp.zeros((4 * H,)),
        "w_out": jax.random.normal(k3, (H, V)) * H ** -0.5,
        "b_out": jnp.zeros((V,)),
    }


def _split(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _dot_3pass(a, b):
    (ah, al), (bh, bl) = _split(a), _split(b)
    dot = functools.partial(jnp.matmul, precision="highest")
    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


def _loss(params, tokens, control):
    """Sum over steps of the batch-mean cross entropy; tokens (B, T+1)."""
    B, T1 = tokens.shape
    T = T1 - 1
    H = params["w"].shape[1] // 4
    dot = _dot_3pass if control else functools.partial(
        jnp.matmul, precision="highest")

    def step(carry, x):
        h, c, acc = carry
        tok, tgt = x
        z = dot(jnp.concatenate([params["emb"][tok], h], -1),
                params["w"]) + params["b"]
        i, f, o, g = jnp.split(z, 4, axis=-1)
        c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        logits = dot(h, params["w_out"]) + params["b_out"]
        nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, tgt[:, None], -1)[:, 0]
        return (h, c, acc + jnp.mean(nll)), None

    @jax.checkpoint
    def block(carry, xb):
        return jax.lax.scan(step, carry, xb)[0], None

    blk = BLOCK if T % BLOCK == 0 else T
    xs = (tokens[:, :-1].T.reshape(T // blk, blk, B),
          tokens[:, 1:].T.reshape(T // blk, blk, B))
    zero = jnp.zeros((B, H), jnp.float32)
    (_, _, acc), _ = jax.lax.scan(block, (zero, zero, jnp.float32(0.0)), xs)
    return acc


@functools.lru_cache(maxsize=None)
def _program(control):
    return jax.jit(jax.value_and_grad(
        functools.partial(_loss, control=control)))


def loss_and_grad(params, tokens, c, *, control=False):
    """Loss over ``tokens`` (rows, T + 1) and its gradient."""
    return _program(control)(params, jnp.asarray(np.asarray(tokens)))
