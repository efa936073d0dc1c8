"""l2_copy_ms: device time per step, in ms, of the copies that carry
Level-2 state between device and host memory: the compiled step's async
copy, dynamic-slice and dynamic-update-slice start/done ops on memory space
S(5), as they appear on the device's op line of the trace, averaged over
the chips used.  A transfer runs between its start and its done; the ops'
own durations are the time the device's op stream spends issuing it and
waiting at the done for it to land, i.e. the copy time that no compute
hides."""
from harness import trace as tr


def read(ctx):
    t, names = ctx["trace"], ctx["host_copy_names"]
    if t is None or not names or not t["steps"]:
        return None
    lo, hi = tr.window_of(t["trace"])
    per_dev = []
    for ops in t["trace"].device_ops.values():
        if any(n in names for n, _, _ in ops):
            per_dev.append(tr.op_ns(ops, names.__contains__, lo, hi))
    if not per_dev:
        return None
    return sum(per_dev) / len(per_dev) / t["steps"] / 1e6
