"""Model FLOPs per trained token of mamba2-370m (forward and backward,
recompute not counted): three times the forward's.

Forward, per token: each layer's dense matmuls (in_proj, out_proj) and its
depthwise convolution, two FLOPs per weight; the SSD's mixing in its
chunked form at the configuration's chunk Q, causal half of the
intra-chunk part (scores C.B over the groups, their application to x over
the heads), and its state terms (B^T x into the chunk state, C h out of
it); and the tied head, two FLOPs per weight of the embedding.  Norms,
gates and elementwise work are not counted.
"""


def ssd_flops_per_token(c):
    """Forward SSD mixing of one layer, per token."""
    s = c["ssm_cfg"]
    d_in = s["expand"] * c["d_model"]
    heads = d_in // s["headdim"]
    Q, N, G, P = c["chunk_size"], s["d_state"], s["ngroups"], s["headdim"]
    intra = Q * N * G + Q * heads * P          # 2 FLOPs x causal half
    states = 2 * N * heads * P * 2             # B^T x in, C h out
    return intra + states


def layer_weights(c):
    s = c["ssm_cfg"]
    d = c["d_model"]
    d_in = s["expand"] * d
    heads = d_in // s["headdim"]
    gn = s["ngroups"] * s["d_state"]
    d_proj = 2 * d_in + 2 * gn + heads
    return d * d_proj + d_in * d + s["d_conv"] * (d_in + 2 * gn)


def flops_per_token(c):
    forward = c["n_layer"] * (2 * layer_weights(c) + ssd_flops_per_token(c)) \
        + 2 * c["vocab_size"] * c["d_model"]
    return 3 * forward
