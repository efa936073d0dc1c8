"""Fused Pallas segment kernels — the ``runner="pallas"`` execution path.

The paper's constant-overhead bound rides on the ratio ``T_T / T_A`` between
the Level-2 transfer time of a boundary state and the compute time of one
interval.  The three existing engines pay the store as a *separate* host
event; here the store is fused **into** the segment kernel, so the boundary
copy streams out over DMA while the next chunk computes — on hardware the
effective ``T_T`` the autotuner sees shrinks toward the residual that cannot
be hidden behind compute.

Two kernels, both generic over the ``ChainSpec`` body contract
``body(params, carry, x, batch) -> carry``:

* :func:`fused_advance_segment` — the segment advance as one kernel: the
  chain carry stays in registers while the kernel's chunk loop runs one
  ``lax.scan`` per chunk; each chunk-entry carry is snapshotted into one of
  **two** VMEM slots and ``pltpu.make_async_copy``'d to an ``ANY``-space
  (host-reachable) boundary buffer while the chunk's steps compute.  The
  classic double buffer: chunk ``k``'s copy is only waited on at chunk
  ``k+2``, when its slot is next reused.  ``boundary[0]`` is the
  segment-entry state the executor journals to Level 2.
* :func:`fused_reverse_segment` — Echo-style fused recompute (PAPERS.md
  1805.08899): instead of materialising the segment's interior states to
  Level 1, the kernel first recomputes the chunk-entry boundaries from the
  Level-2 segment boundary, *streaming them out through the same double
  buffer* to an ``ANY``-space spill; the backward chunk loop then walks the
  chunks in reverse — prefetching each entry boundary back in through a
  second double buffer and running one ``jax.vjp`` of the chunk's scan
  (recompute + transpose fused, nothing materialised outside the kernel).

**Bitwise parity.**  The fused reverse reproduces the compiled runner's
gradients bit for bit (asserted in ``tests/test_kernels.py``).  This is a
sharp constraint: XLA does *not* produce bitwise-identical results for an
unrolled step loop vs. ``lax.scan``, nor for a hand-rolled per-step vjp vs.
the scan transpose.  What is stable — empirically, and by construction,
because scans compile their loop bodies as standalone computations — is the
scan itself: a chain of per-chunk ``lax.scan``/``jax.vjp``-of-scan calls
with the same step closure matches the single-scan forms bit for bit.  The
kernels therefore express **all** compute as per-chunk scans with closures
mirroring ``CompiledChainOps``, fold the parameter cotangent across full
chunks from zero in descending order, and add a short tail chunk's
contribution once at the end — the exact association of the compiled
runner's chunk-checkpointed transpose.  Uneven tails are a shorter static
chunk, never a masked pad (``x + 0.0`` is not even bitwise-neutral).

The kernels run in interpret mode only.  Mosaic refuses their per-chunk
``lax.scan`` over chunk inputs, so on a TPU :func:`runner_supported` says
no and the front-end raises.  CPU has no Pallas lowering for the DMA path:
there the front-end falls back to the compiled engine with a one-line
warning, while tests/benchmarks opt into ``interpret=True``
(Python-evaluated kernels, same numerics) via ``REPRO_PALLAS_INTERPRET=1``.
"""
from __future__ import annotations

import functools
import os
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "fused_advance_segment",
    "fused_reverse_segment",
    "runner_supported",
    "default_interpret",
]

tree_flatten = jax.tree_util.tree_flatten
tree_unflatten = jax.tree_util.tree_unflatten
tree_map = jax.tree_util.tree_map

_FORCE_INTERPRET_ENV = "REPRO_PALLAS_INTERPRET"


def _force_interpret() -> bool:
    return os.environ.get(_FORCE_INTERPRET_ENV, "").lower() in ("1", "true", "yes")


def runner_supported() -> Tuple[bool, str]:
    """Whether the fused pallas runner can execute on this jax backend.

    Returns ``(ok, reason)``.  On a TPU the answer is no: Mosaic has no
    lowering for a ``lax.scan`` over chunk inputs inside a kernel
    (``_scan_lowering_rule`` raises ``NotImplementedError`` for extensive
    inputs), which both kernels' chunk loops are — ``reason`` says so and
    the front-end raises it.  Elsewhere the kernels run in interpret mode
    when :data:`_FORCE_INTERPRET_ENV` is set, and ``reason`` is otherwise
    the one-line fallback message the front-end warns with.
    """
    backend = jax.default_backend()
    if backend == "tpu":
        return False, (
            "runner='pallas' does not compile for a TPU: Mosaic cannot "
            "lower the fused kernels' lax.scan over chunk inputs "
            "(NotImplementedError in _scan_lowering_rule for extensive "
            "inputs); use runner='compiled'")
    if _force_interpret():
        return True, ""
    return False, (
        f"runner='pallas' has no DMA lowering on the '{backend}' backend; "
        f"falling back to the compiled segment runner "
        f"(set {_FORCE_INTERPRET_ENV}=1 to force interpret-mode kernels)")


def default_interpret() -> bool:
    """Interpret-mode resolution: compiled on TPU, interpreted elsewhere."""
    return jax.default_backend() != "tpu"


def _canon(shape) -> Tuple[int, ...]:
    """Pad a leaf shape to >= 2 dims (Pallas TPU refs want 2D+ blocks)."""
    shape = tuple(int(d) for d in shape)
    if len(shape) == 0:
        return (1, 1)
    if len(shape) == 1:
        return (1,) + shape
    return shape


def _full_spec(canon_shape):
    nd = len(canon_shape)
    return pl.BlockSpec(canon_shape, lambda _nd=nd: (0,) * _nd)


@functools.lru_cache(maxsize=32)
def _fused_ops(body, xs_treedef, xs_mask, interpret):
    """Build (and cache) the jitted fused advance/reverse for one chain body.

    Keyed like ``CompiledChainOps``: (body, xs structure, per-leaf inexact
    mask) — plus the interpret flag.  Shapes key ``jax.jit``'s own cache.
    """
    xs_mask = tuple(xs_mask)

    def _combine(xd_leaves, xnd_leaves):
        xd_it, xnd_it = iter(xd_leaves), iter(xnd_leaves)
        leaves = [next(xd_it) if m else next(xnd_it) for m in xs_mask]
        return tree_unflatten(xs_treedef, leaves)

    # -- forward: fused advance + double-buffered boundary store -------------

    @functools.partial(jax.jit, static_argnames=("chunk",))
    def advance(params, carry, xs_seg, batch, *, chunk):
        x_leaves, x_tree = tree_flatten(xs_seg)
        assert x_tree == xs_treedef, "xs structure does not match the chain"
        c_leaves, c_tree = tree_flatten(carry)
        p_leaves, p_tree = tree_flatten(params)
        b_leaves, b_tree = tree_flatten(batch)

        T = int(x_leaves[0].shape[0])
        chunk = min(int(chunk), T)
        # Chunk layout for the forward: [0, chunk, 2*chunk, ..., T], except a
        # length-1 tail merges into the previous chunk — XLA inlines a
        # trip-count-1 scan, and an inlined step is not bitwise-identical to
        # the same step inside a rolled scan (the compiled advance is one
        # long scan, so every fused chunk must stay a rolled scan too).
        bounds = list(range(0, T, chunk)) + [T]
        if len(bounds) > 2 and bounds[-1] - bounds[-2] == 1:
            del bounds[-2]
        nc = len(bounds) - 1

        c_shapes = [tuple(l.shape) for l in c_leaves]
        c_canon = [_canon(s) for s in c_shapes]
        p_shapes = [tuple(l.shape) for l in p_leaves]
        b_shapes = [tuple(l.shape) for l in b_leaves]
        x_step = [tuple(l.shape[1:]) for l in x_leaves]
        x_canon = [_canon(s) for s in x_step]

        xs_in = [l.reshape((T,) + cs) for l, cs in zip(x_leaves, x_canon)]
        p_in = [l.reshape(_canon(s)) for l, s in zip(p_leaves, p_shapes)]
        b_in = [l.reshape(_canon(s)) for l, s in zip(b_leaves, b_shapes)]
        c_in = [l.reshape(cs) for l, cs in zip(c_leaves, c_canon)]
        nX, nP, nB, nC = len(xs_in), len(p_in), len(b_in), len(c_in)

        def kernel(*refs):
            xs_refs = refs[:nX]
            p_refs = refs[nX:nX + nP]
            b_refs = refs[nX + nP:nX + nP + nB]
            c0_refs = refs[nX + nP + nB:nX + nP + nB + nC]
            k = nX + nP + nB + nC
            cout_refs = refs[k:k + nC]
            bnd_refs = refs[k + nC:k + 2 * nC]
            s = k + 2 * nC
            slot_scr = refs[s:s + nC]
            sems = refs[s + nC:s + 2 * nC]

            params_v = tree_unflatten(
                p_tree, [r[...].reshape(sh) for r, sh in zip(p_refs, p_shapes)])
            batch_v = tree_unflatten(
                b_tree, [r[...].reshape(sh) for r, sh in zip(b_refs, b_shapes)])

            def step(c_, x):
                return body(params_v, c_, x, batch_v), None

            carry_v = tree_unflatten(
                c_tree,
                [r[...].reshape(sh) for r, sh in zip(c0_refs, c_shapes)])
            for kk in range(nc):
                slot = kk % 2
                # double buffer: slot kk%2 was last used by chunk kk-2 —
                # wait for that copy to drain before overwriting the slot.
                if kk >= 2:
                    for scr, bnd, sem in zip(slot_scr, bnd_refs, sems):
                        pltpu.make_async_copy(
                            scr.at[slot], bnd.at[kk - 2], sem.at[slot]).wait()
                # snapshot the chunk-ENTRY carry and stream it out while
                # the chunk's steps compute below.
                leaves = tree_flatten(carry_v)[0]
                for scr, v, cs in zip(slot_scr, leaves, c_canon):
                    scr[slot] = v.reshape(cs)
                for scr, bnd, sem in zip(slot_scr, bnd_refs, sems):
                    pltpu.make_async_copy(
                        scr.at[slot], bnd.at[kk], sem.at[slot]).start()
                lo, hi = bounds[kk], bounds[kk + 1]
                xk = tree_unflatten(
                    xs_treedef,
                    [r[lo:hi].reshape((hi - lo,) + sh)
                     for r, sh in zip(xs_refs, x_step)])
                carry_v, _ = lax.scan(step, carry_v, xk)
            # drain the last two in-flight copies
            for scr, bnd, sem in zip(slot_scr, bnd_refs, sems):
                pltpu.make_async_copy(
                    scr.at[(nc - 1) % 2], bnd.at[nc - 1],
                    sem.at[(nc - 1) % 2]).wait()
            if nc >= 2:
                for scr, bnd, sem in zip(slot_scr, bnd_refs, sems):
                    pltpu.make_async_copy(
                        scr.at[(nc - 2) % 2], bnd.at[nc - 2],
                        sem.at[(nc - 2) % 2]).wait()
            out_leaves = tree_flatten(carry_v)[0]
            for dst, v, cs in zip(cout_refs, out_leaves, c_canon):
                dst[...] = v.reshape(cs)

        in_specs = (
            [_full_spec((T,) + cs) for cs in x_canon]
            + [_full_spec(_canon(sh)) for sh in p_shapes]
            + [_full_spec(_canon(sh)) for sh in b_shapes]
            + [_full_spec(cs) for cs in c_canon]
        )
        out_specs = (
            [_full_spec(cs) for cs in c_canon]
            + [pl.BlockSpec(memory_space=pltpu.ANY) for _ in c_canon]
        )
        out_shape = (
            [jax.ShapeDtypeStruct(cs, l.dtype)
             for l, cs in zip(c_leaves, c_canon)]
            + [jax.ShapeDtypeStruct((nc,) + cs, l.dtype)
               for l, cs in zip(c_leaves, c_canon)]
        )
        scratch_shapes = (
            [pltpu.VMEM((2,) + cs, l.dtype)
             for l, cs in zip(c_leaves, c_canon)]
            + [pltpu.SemaphoreType.DMA((2,)) for _ in c_canon]
        )
        outs = pl.pallas_call(
            kernel, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, scratch_shapes=scratch_shapes,
            interpret=interpret,
        )(*xs_in, *p_in, *b_in, *c_in)

        carry_out = tree_unflatten(
            c_tree, [o.reshape(sh) for o, sh in zip(outs[:nC], c_shapes)])
        boundaries = tree_unflatten(
            c_tree,
            [o.reshape((nc,) + sh) for o, sh in zip(outs[nC:], c_shapes)])
        return carry_out, boundaries

    # -- reverse: Echo-style fused recompute + streamed boundaries -----------

    @functools.partial(jax.jit, static_argnames=("chunk",))
    def reverse(params, carry_b, xs_seg, batch, dcarry, *, chunk):
        x_leaves, x_tree = tree_flatten(xs_seg)
        assert x_tree == xs_treedef, "xs structure does not match the chain"
        c_leaves, c_tree = tree_flatten(carry_b)
        p_leaves, p_tree = tree_flatten(params)
        b_leaves, b_tree = tree_flatten(batch)
        dc_leaves = tree_flatten(dcarry)[0]

        T = int(x_leaves[0].shape[0])
        chunk = min(int(chunk), T)
        nc = -(-T // chunk)
        rem = T - (nc - 1) * chunk  # tail chunk length (== chunk if even)

        c_shapes = [tuple(l.shape) for l in c_leaves]
        c_canon = [_canon(s) for s in c_shapes]
        p_shapes = [tuple(l.shape) for l in p_leaves]
        p_canon = [_canon(s) for s in p_shapes]
        b_shapes = [tuple(l.shape) for l in b_leaves]
        x_step = [tuple(l.shape[1:]) for l in x_leaves]
        x_canon = [_canon(s) for s in x_step]
        diff_idx = [i for i, m in enumerate(xs_mask) if m]
        d_step = [x_step[i] for i in diff_idx]
        d_canon = [x_canon[i] for i in diff_idx]

        xs_in = [l.reshape((T,) + cs) for l, cs in zip(x_leaves, x_canon)]
        p_in = [l.reshape(cs) for l, cs in zip(p_leaves, p_canon)]
        b_in = [l.reshape(_canon(sh)) for l, sh in zip(b_leaves, b_shapes)]
        cb_in = [l.reshape(cs) for l, cs in zip(c_leaves, c_canon)]
        dc_in = [l.reshape(cs) for l, cs in zip(dc_leaves, c_canon)]
        nX, nP, nB, nC = len(xs_in), len(p_in), len(b_in), len(cb_in)
        nD = len(diff_idx)

        def kernel(*refs):
            xs_refs = refs[:nX]
            p_refs = refs[nX:nX + nP]
            b_refs = refs[nX + nP:nX + nP + nB]
            cb_refs = refs[nX + nP + nB:nX + nP + nB + nC]
            dc_refs = refs[nX + nP + nB + nC:nX + nP + nB + 2 * nC]
            k = nX + nP + nB + 2 * nC
            dcout_refs = refs[k:k + nC]
            gout_refs = refs[k + nC:k + nC + nP]
            dxd_refs = refs[k + nC + nP:k + nC + nP + nD]
            bnd_refs = refs[k + nC + nP + nD:k + 2 * nC + nP + nD]
            s = k + 2 * nC + nP + nD
            out_slot = refs[s:s + nC]
            in_slot = refs[s + nC:s + 2 * nC]
            sem_out = refs[s + 2 * nC:s + 3 * nC]
            sem_in = refs[s + 3 * nC:s + 4 * nC]

            params_v = tree_unflatten(
                p_tree, [r[...].reshape(sh) for r, sh in zip(p_refs, p_shapes)])
            batch_v = tree_unflatten(
                b_tree, [r[...].reshape(sh) for r, sh in zip(b_refs, b_shapes)])

            def read_xk(lo, hi):
                return [r[lo:hi].reshape((hi - lo,) + sh)
                        for r, sh in zip(xs_refs, x_step)]

            def fwd_step(c_, x):
                return body(params_v, c_, x, batch_v), None

            # Phase A: recompute every chunk-entry boundary from the Level-2
            # segment boundary, streaming each one out through the double
            # buffer while the next chunk computes — the forward kernel's
            # store pattern, reused for the spill.
            carry_v = tree_unflatten(
                c_tree,
                [r[...].reshape(sh) for r, sh in zip(cb_refs, c_shapes)])
            for kk in range(nc):
                slot = kk % 2
                if kk >= 2:
                    for scr, bnd, sem in zip(out_slot, bnd_refs, sem_out):
                        pltpu.make_async_copy(
                            scr.at[slot], bnd.at[kk - 2], sem.at[slot]).wait()
                leaves = tree_flatten(carry_v)[0]
                for scr, v, cs in zip(out_slot, leaves, c_canon):
                    scr[slot] = v.reshape(cs)
                for scr, bnd, sem in zip(out_slot, bnd_refs, sem_out):
                    pltpu.make_async_copy(
                        scr.at[slot], bnd.at[kk], sem.at[slot]).start()
                if kk < nc - 1:
                    # the last chunk's interior is never a boundary — phase A
                    # stops (nc-1)*chunk steps in; its vjp recomputes it.
                    xk = tree_unflatten(
                        xs_treedef, read_xk(kk * chunk, (kk + 1) * chunk))
                    carry_v, _ = lax.scan(fwd_step, carry_v, xk)
            for scr, bnd, sem in zip(out_slot, bnd_refs, sem_out):
                pltpu.make_async_copy(
                    scr.at[(nc - 1) % 2], bnd.at[nc - 1],
                    sem.at[(nc - 1) % 2]).wait()
            if nc >= 2:
                for scr, bnd, sem in zip(out_slot, bnd_refs, sem_out):
                    pltpu.make_async_copy(
                        scr.at[(nc - 2) % 2], bnd.at[nc - 2],
                        sem.at[(nc - 2) % 2]).wait()

            # Backward chunk loop: prefetch each chunk's entry boundary back
            # in through the second double buffer, then fuse recompute +
            # transpose as one vjp of the chunk's scan.
            for scr, bnd, sem in zip(in_slot, bnd_refs, sem_in):
                pltpu.make_async_copy(
                    bnd.at[nc - 1], scr.at[(nc - 1) % 2],
                    sem.at[(nc - 1) % 2]).start()
            if nc >= 2:
                for scr, bnd, sem in zip(in_slot, bnd_refs, sem_in):
                    pltpu.make_async_copy(
                        bnd.at[nc - 2], scr.at[(nc - 2) % 2],
                        sem.at[(nc - 2) % 2]).start()

            dc_v = tree_unflatten(
                c_tree,
                [r[...].reshape(sh) for r, sh in zip(dc_refs, c_shapes)])
            gacc_v = tree_map(jnp.zeros_like, params_v)
            dp_tail = None
            for kk in range(nc - 1, -1, -1):
                slot = kk % 2
                for scr, bnd, sem in zip(in_slot, bnd_refs, sem_in):
                    pltpu.make_async_copy(
                        bnd.at[kk], scr.at[slot], sem.at[slot]).wait()
                entry = tree_unflatten(
                    c_tree,
                    [r[slot].reshape(sh) for r, sh in zip(in_slot, c_shapes)])
                if kk >= 2:
                    # slot consumed — prefetch the boundary it serves next
                    # while this chunk's vjp recomputes and transposes.
                    for scr, bnd, sem in zip(in_slot, bnd_refs, sem_in):
                        pltpu.make_async_copy(
                            bnd.at[kk - 2], scr.at[slot], sem.at[slot]).start()
                lo, hi = kk * chunk, min((kk + 1) * chunk, T)
                x_all = read_xk(lo, hi)
                xd_k = [x_all[i] for i in diff_idx]
                xnd_k = [x_all[i] for i, m in enumerate(xs_mask) if not m]

                def segf(p, c, xd_, _xnd=tuple(xnd_k), _n=hi - lo):
                    def step(c_, x):
                        xd_t, xnd_t = x
                        return (body(p, c_, _combine(xd_t, xnd_t), batch_v),
                                None)

                    c2, _ = lax.scan(step, c, (tuple(xd_), _xnd), length=_n)
                    return c2

                _, vjp = jax.vjp(segf, params_v, entry, list(xd_k))
                dp, dc_v, dxd_k = vjp(dc_v)
                if kk == nc - 1 and rem != chunk:
                    # short tail: keep its contribution out of the running
                    # fold and add it once at the end — the association of
                    # the compiled runner's transpose (bitwise parity).
                    dp_tail = dp
                else:
                    gacc_v = tree_map(jnp.add, gacc_v, dp)
                for dst, v, cs in zip(dxd_refs, dxd_k, d_canon):
                    dst[lo:hi] = v.reshape((hi - lo,) + cs)
            if dp_tail is not None:
                gacc_v = tree_map(jnp.add, gacc_v, dp_tail)

            for dst, v, cs in zip(dcout_refs, tree_flatten(dc_v)[0], c_canon):
                dst[...] = v.reshape(cs)
            for dst, v, cs in zip(gout_refs, tree_flatten(gacc_v)[0], p_canon):
                dst[...] = v.reshape(cs)

        in_specs = (
            [_full_spec((T,) + cs) for cs in x_canon]
            + [_full_spec(cs) for cs in p_canon]
            + [_full_spec(_canon(sh)) for sh in b_shapes]
            + [_full_spec(cs) for cs in c_canon]
            + [_full_spec(cs) for cs in c_canon]
        )
        out_specs = (
            [_full_spec(cs) for cs in c_canon]
            + [_full_spec(cs) for cs in p_canon]
            + [_full_spec((T,) + cs) for cs in d_canon]
            + [pl.BlockSpec(memory_space=pltpu.ANY) for _ in c_canon]
        )
        out_shape = (
            [jax.ShapeDtypeStruct(cs, l.dtype)
             for l, cs in zip(c_leaves, c_canon)]
            + [jax.ShapeDtypeStruct(cs, l.dtype)
               for l, cs in zip(p_leaves, p_canon)]
            + [jax.ShapeDtypeStruct((T,) + cs, x_leaves[i].dtype)
               for i, cs in zip(diff_idx, d_canon)]
            + [jax.ShapeDtypeStruct((nc,) + cs, l.dtype)
               for l, cs in zip(c_leaves, c_canon)]
        )
        scratch_shapes = (
            [pltpu.VMEM((2,) + cs, l.dtype)
             for l, cs in zip(c_leaves, c_canon)]
            + [pltpu.VMEM((2,) + cs, l.dtype)
               for l, cs in zip(c_leaves, c_canon)]
            + [pltpu.SemaphoreType.DMA((2,)) for _ in c_canon]
            + [pltpu.SemaphoreType.DMA((2,)) for _ in c_canon]
        )
        outs = pl.pallas_call(
            kernel, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, scratch_shapes=scratch_shapes,
            interpret=interpret,
        )(*xs_in, *p_in, *b_in, *cb_in, *dc_in)

        dc_out = tree_unflatten(
            c_tree, [o.reshape(sh) for o, sh in zip(outs[:nC], c_shapes)])
        dp_out = tree_unflatten(
            p_tree,
            [o.reshape(sh) for o, sh in zip(outs[nC:nC + nP], p_shapes)])
        dxd = [
            o.reshape((T,) + st)
            for o, st in zip(outs[nC + nP:nC + nP + nD], d_step)
        ]
        return dc_out, dp_out, dxd

    class _Fused:
        pass

    ops = _Fused()
    ops.advance = advance
    ops.reverse = reverse
    return ops


def fused_advance_segment(body, xs_treedef, xs_mask, params, carry, xs_seg,
                          batch, *, chunk: int, interpret: bool):
    """Advance the carry over one segment with the fused forward kernel.

    Returns ``(carry_out, boundaries)`` where ``boundaries`` mirrors the
    carry pytree with a leading ``num_chunks`` axis of chunk-entry states;
    ``boundaries[...][0]`` is the segment-entry state (what the executor
    stores to Level 2), already copied out of the compute buffers by DMA.
    """
    ops = _fused_ops(body, xs_treedef, tuple(xs_mask), bool(interpret))
    return ops.advance(params, carry, xs_seg, batch, chunk=int(chunk))


def fused_reverse_segment(body, xs_treedef, xs_mask, params, carry_b, xs_seg,
                          batch, dcarry, *, chunk: int, interpret: bool):
    """Reverse one segment with Echo-style fused recompute.

    Returns ``(dcarry_at_begin, dparams_for_segment, dxs_diff_leaves)``;
    the caller folds ``dparams_for_segment`` into its gradient accumulator
    (``gacc + dp``, matching ``CompiledChainOps.reverse_segment``).
    """
    ops = _fused_ops(body, xs_treedef, tuple(xs_mask), bool(interpret))
    return ops.reverse(params, carry_b, xs_seg, batch, dcarry,
                       chunk=int(chunk))
