"""allreduce_ms: device time per step, in ms, of the compiled step's
all-reduce ops (``all-reduce``, or its ``all-reduce-start`` and
``all-reduce-done``), as they appear on each chip's op line of the trace,
averaged over the chips used.  The ops' own durations are the exchange time
that no compute hides, as ``l2_copy_ms`` reads the Level-2 copies.  A step
with no all-reduce reads nothing."""
from harness import trace as tr

KINDS = ("all-reduce", "all-reduce-start", "all-reduce-done")


def read(ctx):
    t = ctx["trace"]
    if t is None or not t["steps"]:
        return None
    names = {n for n, op in ctx["opcodes"].items() if op in KINDS}
    if not names:
        return None
    lo, hi = tr.window_of(t["trace"])
    per_dev = [tr.op_ns(ops, names.__contains__, lo, hi)
               for ops in t["trace"].device_ops.values()]
    return sum(per_dev) / len(per_dev) / t["steps"] / 1e6
