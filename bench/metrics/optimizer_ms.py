"""optimizer_ms: device time per step, in ms, of the optimizer: ops of the
``optimizer`` scope (the AdamW update, the gradient's clipping and its
norm).

Each instant of busy time goes to the innermost op running then, and that op
to a part by the ``op_name`` metadata of the compiled step
(``harness.scopes.part_ms``), averaged over the chips used.  A scope that
names no op of the step reads 0.0."""
from harness import scopes


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    return scopes.part_ms(t["trace"], ctx["op_names"],
                          t["name_ns"])["optimizer_ms"]
