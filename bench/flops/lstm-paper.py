"""Model FLOPs per trained token of lstm-paper (forward and backward,
recompute not counted): three times the forward's.  Forward, per token:
the gate matmul over [x, h] and the head, two FLOPs per weight.  The
embedding gather, gates and elementwise work are not counted."""


def flops_per_token(c):
    E, H, V = c["embed_dim"], c["hidden_size"], c["vocab_size"]
    return 3 * (2 * (E + H) * 4 * H + 2 * H * V)
