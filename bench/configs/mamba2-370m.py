"""Plain fp32 reference of mamba2-370m's training loss and gradients.

Follows the Mamba-2 paper (Dao & Gu, arXiv:2405.21060): each layer is
``x + out_proj(gated_rmsnorm(SSD(conv(in_proj(rmsnorm(x))))))``, the SSD
computed by the paper's minimal chunked algorithm ("ssd_minimal", with its
stable segment sums) at chunk 256, the reference code's default.  The
head is tied to the embedding; the loss is the mean next-token cross
entropy.  Departures from the published code, all of them the system's own
conventions that the reference has to share because the weights are
random: RMSNorm weights are stored as an offset from 1 with eps 1e-6, and
the weights are drawn as the system's initialiser draws them from the
seed (the recipe is written out below, nothing is imported).

Everything is fp32 with matmuls at "highest" precision.  It runs layer by
layer so that it fits one chip: the forward keeps each layer's input, and
the backward recomputes one layer at a time under ``jax.vjp``.

``control=True`` computes every dense matmul (the in/out projections and
the head), the ones the system computes in bfloat16, in float8: each
operand of the forward product and of both backward products rounded to
e4m3 with one scale per tensor, the products summed in fp32.  That is the
precision below the configuration's.

``control="bfloat16"`` is no control but a witness: it stores what the
system stores in bfloat16 (the residual stream, the norms' outputs, the
dense matmuls' operands and outputs, the convolution's and the SSD's
outputs) rounded to bfloat16, forward and backward, and computes the rest
in fp32.  It shows how far bfloat16 storage alone moves each number from
the fp32 reference.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

SSD_CHUNK = 256
EPS = 1e-6


def _dims(c):
    s = c["ssm_cfg"]
    d = c["d_model"]
    d_in = s["expand"] * d
    heads = d_in // s["headdim"]
    gn = s["ngroups"] * s["d_state"]
    return d, d_in, heads, gn, d_in + 2 * gn, 2 * d_in + 2 * gn + heads


def init_params(key, c):
    """The seed's weights, drawn as the system draws them."""
    d, d_in, heads, gn, conv_dim, d_proj = _dims(c)
    k_conv = c["ssm_cfg"]["d_conv"]
    ke, kl, _ = jax.random.split(key, 3)
    layer_key = jax.random.split(kl, 1)[0]

    def layer(k):
        k1, k2, _, k4 = jax.random.split(jax.random.split(k, 4)[0], 4)
        return {
            "ln1": {"scale": jnp.zeros((d,))},
            "mamba": {
                "in_proj": {"w": jax.random.normal(k1, (d, d_proj))
                            * d ** -0.5},
                "conv_w": jax.random.normal(k2, (k_conv, conv_dim)) * 0.2,
                "conv_b": jnp.zeros((conv_dim,)),
                "A_log": jnp.log(jnp.linspace(1.0, 16.0, heads)),
                "D": jnp.ones((heads,)),
                "dt_bias": jnp.zeros((heads,)),
                "norm_scale": jnp.zeros((d_in,)),
                "out_proj": {"w": jax.random.normal(k4, (d_in, d))
                             * d_in ** -0.5},
            },
        }

    @jax.jit
    def make(ke, layer_key):
        layers = jax.vmap(layer)(jax.random.split(layer_key, c["n_layer"]))
        return {"embed": {"emb": jax.random.normal(
                    ke, (c["vocab_size"], d)) * d ** -0.5},
                "final_norm": {"scale": jnp.zeros((d,))},
                "layers": {"pos0": layers}}

    return make(ke, layer_key)


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm_exact(a, b):
    return jnp.matmul(a, b, precision="highest")


@jax.custom_vjp
def _mm_fp8(a, b):
    return _mm_exact(_fp8(a), _fp8(b))


def _mm_fp8_fwd(a, b):
    return _mm_fp8(a, b), (a, b)


def _mm_fp8_bwd(res, g):
    a, b = res
    g8 = _fp8(g)
    ga = _mm_exact(g8, jnp.swapaxes(_fp8(b), -1, -2))
    gb = jnp.einsum("...i,...j->ij", _fp8(a), g8, precision="highest")
    return ga, gb


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


@jax.custom_vjp
def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


_bf16.defvjp(lambda x: (_bf16(x), None), lambda _, g: (_bf16(g),))


def _store(control):
    """What the witness rounds a stored activation with; else nothing."""
    return _bf16 if control == "bfloat16" else (lambda x: x)


def _mm(a, b, control):
    if control == "bfloat16":
        return _bf16(_mm_exact(_bf16(a), _bf16(b)))
    return _mm_fp8(a, b) if control else _mm_exact(a, b)


def _rmsnorm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) \
        * (1.0 + scale)


def _segsum(x):
    """Stable segment sums: out[..., i, j] = sum(x[..., j+1:i+1]), -inf
    above the diagonal."""
    T = x.shape[-1]
    x = jnp.repeat(x[..., None], T, axis=-1)
    x = jnp.where(jnp.tril(jnp.ones((T, T), bool), -1), x, 0.0)
    x = jnp.cumsum(x, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool), 0), x, -jnp.inf)


def _ssd(X, A, B, C, chunk):
    """The paper's ssd_minimal_discrete.  X: (b,l,h,p), A: (b,l,h),
    B, C: (b,l,h,n); returns Y: (b,l,h,p)."""
    b, L, h, p = X.shape
    c = L // chunk
    X = X.reshape(b, c, chunk, h, p)
    B = B.reshape(b, c, chunk, h, -1)
    C = C.reshape(b, c, chunk, h, -1)
    A = A.reshape(b, c, chunk, h).transpose(0, 3, 1, 2)     # b h c l
    A_cs = jnp.cumsum(A, axis=-1)
    ein = functools.partial(jnp.einsum, precision="highest")
    Lm = jnp.exp(_segsum(A))
    Y_diag = ein("bclhn,bcshn,bhcls,bcshp->bclhp", C, B, Lm, X)
    decay_states = jnp.exp(A_cs[..., -1:] - A_cs)
    states = ein("bclhn,bhcl,bclhp->bchpn", B, decay_states, X)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    decay_chunk = jnp.exp(_segsum(jnp.pad(A_cs[..., -1], ((0, 0), (0, 0),
                                                           (1, 0)))))
    states = ein("bhzc,bchpn->bzhpn", decay_chunk, states)[:, :-1]
    Y_off = ein("bclhn,bchpn,bhcl->bclhp", C, states, jnp.exp(A_cs))
    return (Y_diag + Y_off).reshape(b, L, h, p)


def _layer(lp, x, c, control):
    s = c["ssm_cfg"]
    d, d_in, heads, gn, conv_dim, _ = _dims(c)
    b, T, _ = x.shape
    mp = lp["mamba"]
    r = _store(control)
    zxbcdt = _mm(r(_rmsnorm(x, lp["ln1"]["scale"])), mp["in_proj"]["w"],
                 control)
    z = zxbcdt[..., :d_in]
    xbc = zxbcdt[..., d_in:d_in + conv_dim]
    dt = zxbcdt[..., d_in + conv_dim:]
    K = s["d_conv"]
    pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + T] * mp["conv_w"][i] for i in range(K))
    conv = r(jax.nn.silu(r(conv + mp["conv_b"])))
    xs = conv[..., :d_in].reshape(b, T, heads, s["headdim"])
    G, N = s["ngroups"], s["d_state"]
    Bm = conv[..., d_in:d_in + gn].reshape(b, T, G, N)
    Cm = conv[..., d_in + gn:].reshape(b, T, G, N)
    Bm = jnp.repeat(Bm, heads // G, axis=2)
    Cm = jnp.repeat(Cm, heads // G, axis=2)
    dt = jax.nn.softplus(dt + mp["dt_bias"])
    A = -jnp.exp(mp["A_log"])
    y = _ssd(xs * dt[..., None], A * dt, Bm, Cm, min(SSD_CHUNK, T))
    y = r(r(y) + mp["D"][:, None] * xs).reshape(b, T, d_in)
    y = _rmsnorm(y * jax.nn.silu(z), mp["norm_scale"])
    return r(x + _mm(y, mp["out_proj"]["w"], control))


def _head(params, x, labels, c, control):
    h = _store(control)(_rmsnorm(x, params["final_norm"]["scale"]))
    logits = _mm(h, params["embed"]["emb"].T, control)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


@functools.lru_cache(maxsize=None)
def _programs(c_json, control):
    c = json.loads(c_json)

    def pick(layers, i):
        return jax.tree_util.tree_map(lambda a: a[i], layers)

    @jax.jit
    def fwd(layers, i, x):
        return _layer(pick(layers, i), x, c, control)

    @functools.partial(jax.jit, donate_argnums=(4,))
    def bwd(layers, i, x, gy, g_layers):
        _, vjp = jax.vjp(lambda lp, x_: _layer(lp, x_, c, control),
                         pick(layers, i), x)
        g_lp, gx = vjp(gy)
        g_layers = jax.tree_util.tree_map(
            lambda acc, g: acc.at[i].add(g), g_layers, g_lp)
        return gx, g_layers

    @jax.jit
    def head(params, x, labels):
        return jax.value_and_grad(
            lambda p, x_: _head(p, x_, labels, c, control),
            argnums=(0, 1))(params, x)

    @jax.jit
    def embed(emb, tokens):
        return _store(control)(emb[tokens])

    @jax.jit
    def embed_grad(g_emb, tokens, gx):
        return g_emb.at[tokens].add(gx)

    return fwd, bwd, head, embed, embed_grad


def loss_and_grad(params, tokens, c, *, control=False, block_rows=2):
    """Mean next-token loss over ``tokens`` (rows, T + 1) and its gradient,
    in blocks of ``block_rows`` rows, layer by layer."""
    fwd, bwd, head, embed, embed_grad = _programs(
        json.dumps(c, sort_keys=True), control)
    tokens = np.asarray(tokens)
    rows = tokens.shape[0]
    layers = params["layers"]["pos0"]
    top = {k: v for k, v in params.items() if k != "layers"}
    total_loss, grads = 0.0, None
    for r in range(0, rows, block_rows):
        blk = tokens[r:r + block_rows]
        inp, labels = jnp.asarray(blk[:, :-1]), jnp.asarray(blk[:, 1:])
        xs = [embed(params["embed"]["emb"], inp)]
        for i in range(c["n_layer"]):
            xs.append(fwd(layers, i, xs[-1]))
        loss, (g_top, gx) = head(top, xs[-1], labels)
        g_layers = jax.tree_util.tree_map(jnp.zeros_like, layers)
        for i in reversed(range(c["n_layer"])):
            gx, g_layers = bwd(layers, i, xs[i], gx, g_layers)
        del xs
        g = {"embed": {"emb": embed_grad(g_top["embed"]["emb"], inp, gx)},
             "final_norm": g_top["final_norm"],
             "layers": {"pos0": g_layers}}
        weight = blk.shape[0] / rows
        total_loss += weight * float(loss)
        grads = (jax.tree_util.tree_map(lambda x: weight * x, g)
                 if grads is None else jax.tree_util.tree_map(
                     lambda a, x: a + weight * x, grads, g))
    return jnp.float32(total_loss), grads
