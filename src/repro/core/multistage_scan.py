"""``multistage_scan`` — the paper's technique as a composable JAX transform
(the *trace-native* engine that runs on pods: ``engine="scan"`` behind
``repro.api``).

A chain computation ``carry_{k+1} = body(carry_k, x_k)`` of length ``n`` is
split into the segments of a :class:`~repro.core.schedule.SegmentPlan` — the
same planning IR the compiled and interpreted executor engines drive.  Each
segment is wrapped in ``jax.checkpoint`` with a policy that **offloads the
segment-boundary carry to pinned host memory** and recomputes everything
inside the segment during the backward pass.  On TPU, XLA lowers the
offloads to asynchronous ``copy-start`` / ``copy-done`` DMA pairs overlapped
with compute — precisely the paper's asynchronous Level-2 store (forward)
and prefetch (backward), but scheduled by the compiler instead of Python
threads.

Because everything stays inside the trace (no ``io_callback``, no host-side
run registry), the transform composes with ``jax.jit``, ``jax.vmap`` and
mesh sharding (``NamedSharding`` / ``shard_map``) like any other JAX
function.

Memory behaviour (matches the paper's model):

* Level-2 (host) footprint: ``num_segments x state_bytes`` — grows with
  ``n`` but lives in cheap, large memory.
* Level-1 (HBM) footprint: one segment of activations at a time, i.e.
  O(I) — **constant in n**.
* Recompute overhead: one extra forward per segment interior — constant in
  ``n`` (the compiled counterpart of ``R(I, s)``; plan segments that
  overflow the Level-1 budget are recomputed at the plan's inner chunk
  granularity, the trace-native projection of Revolve-within-the-interval).

Plans need no divisibility: an ``n % I != 0`` chain simply ends in a shorter
tail segment (one extra trace, nothing else).  The legacy
``nested_intervals=(I2, ...)`` knob still recursively segments each segment
explicitly; when a :class:`SegmentPlan` is supplied the inner intervals come
from the plan's Revolve sub-plans instead (via ``SegmentPlan.inner_chunk``).
"""
from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import offload as ofl
from repro.core.schedule import SegmentPlan, segment_plan

Body = Callable[[Any, Any], Tuple[Any, Any]]

tree_map = jax.tree_util.tree_map


def choose_interval(n: int, target: int) -> int:
    """Best Level-2 store interval <= ``target`` for an ``n``-step chain.

    Prefers the largest divisor of ``n`` in ``[ceil(target/2), target]``
    (even segments mean one compiled segment variant instead of two), but
    never degrades below half the requested interval: when no divisor is in
    range — prime or odd ``n`` — the target itself is returned and the plan
    simply ends in a shorter tail segment.  (The old divisor-snapping
    fallback silently returned ``I=1`` for prime ``n``: per-step Level-2
    stores, the worst-case recompute/transfer regime.  Uneven tails are
    first-class in the :class:`SegmentPlan` IR, so the divisibility
    constraint is gone.)
    """
    target = max(1, min(target, n))
    floor = max(1, -(-target // 2))
    for i in range(target, floor - 1, -1):
        if n % i == 0:
            return i
    return target


def _split_xs(xs: Any, num_segments: int, interval: int) -> Any:
    def rs(x):
        return x.reshape((num_segments, interval) + x.shape[1:])

    return tree_map(rs, xs)


def _merge_ys(ys: Any, n: int) -> Any:
    def rs(y):
        return y.reshape((n,) + y.shape[2:])

    return tree_map(rs, ys)


def multistage_scan(
    body: Body,
    carry: Any,
    xs: Any = None,
    *,
    length: Optional[int] = None,
    interval: Optional[int] = None,
    plan: Optional[SegmentPlan] = None,
    s_l1: Optional[int] = None,
    offload: bool = True,
    nested_intervals: Sequence[int] = (),
    unroll: int = 1,
    boundary_name: str = ofl.BOUNDARY,
) -> Tuple[Any, Any]:
    """Drop-in replacement for ``lax.scan(body, carry, xs)`` implementing
    asynchronous multistage checkpointing, driven by a
    :class:`~repro.core.schedule.SegmentPlan`.

    Args:
      body: ``(carry, x) -> (carry, y)`` — one chain step (an RNN/SSM time
        step, or one transformer layer when scanning over depth).
      carry: initial carry (the chain state; this is what gets offloaded).
      xs: stacked per-step inputs with leading axis ``n`` (or None).
      length: chain length when ``xs is None``.
      interval: the checkpointing interval ``I``.  Any value in ``[1, n]``
        works — a non-dividing interval yields a shorter tail segment.
      plan: an explicit :class:`SegmentPlan` to execute (overrides
        ``interval``/``s_l1``; segment boundaries, uneven tails and inner
        recompute granularity all come from the plan).
      s_l1: Level-1 snapshot budget.  When given (and ``plan`` is not), the
        plan is built via ``segment_plan(n, interval, s_l1)`` and segments
        that overflow the budget are recomputed at the plan's inner chunk
        granularity.
      offload: if True, boundary carries go to pinned host memory (Level 2);
        if False they are saved in HBM (plain segmented remat — the
        single-stage baseline).
      nested_intervals: optional explicit inner intervals for Revolve-like
        nested recomputation inside each segment (legacy knob; ignored when
        the inner structure comes from ``plan``/``s_l1``).
      unroll: unroll factor for the innermost scan.

    Returns: ``(final_carry, ys)`` identical (up to float assoc.) to
      ``lax.scan``.
    """
    if xs is None:
        n = length
    else:
        n = int(jax.tree_util.tree_leaves(xs)[0].shape[0])
    if n is None:
        raise ValueError("need xs or length")

    if plan is not None:
        if plan.n != n:
            raise ValueError(
                f"plan is for an n={plan.n} chain, got xs of length {n}")
        groups = _plan_groups(plan)
    else:
        if interval is None:
            raise ValueError("need interval= or plan=")
        interval = max(1, min(interval, n))
        if s_l1 is not None:
            groups = _plan_groups(segment_plan(n, interval, s_l1))
        else:
            # Legacy explicit path: uniform segments (+ uneven tail), with
            # the caller's nested_intervals applied inside every segment.
            nested = tuple(nested_intervals)
            num_full, tail = divmod(n, interval)
            groups = [(num_full, interval, nested)]
            if tail:
                groups.append((1, tail, nested))

    # every op of the chain, the loops over segments included
    with jax.named_scope(ofl.SCOPE_SEGMENT):
        return _run_groups(body, carry, xs, groups, offload=offload,
                           unroll=unroll, boundary_name=boundary_name)


def _plan_groups(plan: SegmentPlan) -> List[Tuple[int, int, Tuple[int, ...]]]:
    """Collapse a plan into runs of equal-length segments: ``(count, length,
    nested_intervals)`` triples in forward order.  ``segment_plan`` emits
    uniform intervals plus at most one shorter tail, so the trace contains
    one ``lax.scan``-over-segments region per distinct length — O(I) trace
    size regardless of ``n``.  The inner recompute interval is the plan's
    projection of its Revolve sub-plan (``SegmentPlan.inner_chunk``)."""
    groups: List[Tuple[int, int, Tuple[int, ...]]] = []
    for seg in plan.segments:
        chunk = plan.inner_chunk(seg)
        nested = (chunk,) if chunk is not None else ()
        if groups and groups[-1][1] == seg.length and \
                groups[-1][2] == nested:
            count, ln, nst = groups[-1]
            groups[-1] = (count + 1, ln, nst)
        else:
            groups.append((1, seg.length, nested))
    return groups


def _run_groups(body: Body, carry: Any, xs: Any, groups, *, offload: bool,
                unroll: int, boundary_name: str) -> Tuple[Any, Any]:
    """Execute ``(count, length, nested)`` segment groups in order: each
    group with ``count > 1`` is one ``lax.scan`` over its reshaped inputs;
    a singleton group (the uneven tail, or a single-segment chain) is one
    direct segment call."""
    ys_parts: List[Any] = []
    offset = 0
    for count, seg_len, nested in groups:
        seg_fn = _make_segment(body, seg_len, offload, nested, unroll,
                               boundary_name)
        end = offset + count * seg_len
        xs_grp = None if xs is None else \
            tree_map(lambda a: a[offset:end], xs)
        if count == 1:
            carry, ys = seg_fn(carry, xs_grp)
        else:
            xs_seg = None if xs_grp is None else \
                _split_xs(xs_grp, count, seg_len)
            carry, ys = lax.scan(seg_fn, carry, xs_seg, length=count)
            ys = None if ys is None else _merge_ys(ys, count * seg_len)
        ys_parts.append(ys)
        offset = end
    if len(ys_parts) == 1:
        return carry, ys_parts[0]
    if any(y is None for y in ys_parts):
        return carry, None
    return carry, tree_map(lambda *ps: jnp.concatenate(ps, axis=0),
                           *ys_parts)


def _make_segment(
    body: Body,
    seg_len: int,
    offload: bool,
    nested_intervals: Sequence[int],
    unroll: int,
    boundary_name: str,
) -> Callable[[Any, Any], Tuple[Any, Any]]:
    """One segment: remat region whose boundary carry is offloaded/saved."""

    policy = ofl.segment_policy(offload, boundary_name)

    def segment(carry, xs_seg):
        # Tag the *input* carry: this is the every-I-th state the paper
        # stores to Level 2.  All consumers read the tagged value, so remat
        # saves (offloads) exactly this tensor and recomputes the rest.
        carry = ofl.tag(carry, boundary_name)
        if nested_intervals:
            inner_i, *rest = nested_intervals
            carry, ys = multistage_scan(
                body, carry, xs_seg,
                length=None if xs_seg is not None else seg_len,
                interval=min(inner_i, seg_len),
                offload=False,
                nested_intervals=rest,
                unroll=unroll,
                boundary_name=ofl.INNER_BOUNDARY,
            )
        else:
            carry, ys = lax.scan(body, carry, xs_seg, length=seg_len,
                                 unroll=unroll)
        return carry, ys

    return jax.checkpoint(segment, policy=policy, prevent_cse=False)


# ---------------------------------------------------------------------------
# BPTT convenience wrapper
# ---------------------------------------------------------------------------


def bptt_grad(
    step_loss: Callable[[Any, Any, Any], Tuple[Any, Any]],
    params: Any,
    carry0: Any,
    xs: Any,
    *,
    interval: int,
    s_l1: Optional[int] = None,
    offload: bool = True,
    nested_intervals: Sequence[int] = (),
) -> Tuple[Any, Any]:
    """Gradient of a summed per-step loss over a long sequence, computed with
    multistage checkpointing.

    ``step_loss(params, carry, x) -> (new_carry, loss_k)``.

    Returns ``(total_loss, grads)`` — the multi-level counterpart of
    ``jax.grad`` over ``lax.scan``.
    """

    def total_loss(p):
        def body(carry, x):
            new_carry, l = step_loss(p, carry, x)
            return new_carry, l

        _, losses = multistage_scan(
            body, carry0, xs, interval=interval, s_l1=s_l1, offload=offload,
            nested_intervals=nested_intervals,
        )
        return jnp.sum(losses)

    return jax.value_and_grad(total_loss)(params)
