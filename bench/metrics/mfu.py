"""mfu: model FLOPs per token (bench/flops/<config>.py) times the window's
tokens per second, over the chips' bf16 peak (bench/harness/peaks.json),
in percent.  Recomputed work does not count."""


def read(ctx):
    if ctx["peaks"] is None:
        return None
    return 100.0 * ctx["tokens_per_s"] * ctx["flops_per_token"] / (
        ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
