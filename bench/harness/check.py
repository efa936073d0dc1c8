"""What decides ``correct``: the timed step's first three steps against a
plain reference that follows them.

The program's readings are taken in set-up, from the one compiled step
that the window then drives: each step's loss, the first gradient as the
optimizer got it (AdamW's first moment after one step is ``(1 - b1) g``),
and each parameter leaf's change after three steps.  The reference starts
from the same seed, takes the same batches, and applies its own AdamW.

The numbers, each compared against its limit from
``bench/cells/<workload>.json`` where that file gives one:

* ``loss_gap``   the largest ``|loss - loss_ref| / |loss_ref|`` of the
  three steps;
* ``grad_gap``   over leaves, the largest gap between the norm of the
  program's first gradient and the reference's, over the larger of that
  leaf's reference norm and the median leaf's;
* ``update_gap`` the same for the norm of each leaf's change after three
  steps, over the leaves whose reference gradient is at least a thousandth
  of the median leaf's (the others move under Adam by round-off alone);
* ``grad_err``   over leaves, the largest norm of the difference between the
  program's first gradient and the reference's, over the larger of that
  leaf's reference norm and the median leaf's.  A gap of norms moves only
  at second order under rounding noise of mean zero; this one moves at
  first order, so it is the number a lower precision fails.
"""
from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

CHECK_STEPS = 3
MOVED_LEAF_SHARE = 1e-3     # leaves under this share of the median gradient
                            # are left out of update_gap


@dataclasses.dataclass
class Readings:
    losses: List[float]
    grad_norms: Dict[str, float]       # first gradient, as the optimizer got it
    change_norms: Dict[str, float]     # |params after three steps - init|
    first_grad: Dict[str, np.ndarray]  # that gradient, on the host


def leaf_path(path) -> str:
    """``layers/pos0/mamba/in_proj/w`` for a pytree key path."""
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def leaf_norm_fn():
    """A jitted ``tree -> {path: fp32 norm}``."""
    import jax
    import jax.numpy as jnp

    def norms(tree):
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        return {leaf_path(p): jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32)))) for p, x in flat}

    return jax.jit(norms)


def to_host(d: Dict[str, Any]) -> Dict[str, float]:
    return {k: float(v) for k, v in d.items()}


def host_leaves(tree, scale: float = 1.0) -> Dict[str, np.ndarray]:
    """``{path: fp32 array on the host}``, each leaf times ``scale``."""
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))
    return {leaf_path(p): np.asarray(x, np.float32) * np.float32(scale)
            for p, x in flat}


# ------------------------------------------------------------- reference


def adamw_reference(opt: Dict[str, Any]):
    """AdamW with global-norm clipping, written from its definition
    (Loshchilov & Hutter; clipping as in Pascanu et al.), constant rate."""
    import jax
    import jax.numpy as jnp

    lr, b1, b2 = opt["lr"], opt["b1"], opt["b2"]
    eps, wd, clip = opt["eps"], opt["weight_decay"], opt["max_grad_norm"]
    tmap = jax.tree_util.tree_map

    @jax.jit
    def clip_grads(g):
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                          for x in jax.tree_util.tree_leaves(g)))
        scale = jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-9))
        return tmap(lambda x: x * scale, g)

    @jax.jit
    def update(p, m, v, g, t):
        m = tmap(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = tmap(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
        c1 = 1 - b1 ** t
        c2 = 1 - b2 ** t
        p = tmap(lambda p_, m_, v_: p_ - lr * (
            (m_ / c1) / (jnp.sqrt(v_ / c2) + eps) + wd * p_), p, m, v)
        return p, m, v

    return clip_grads, update


def split_rows(loss_and_grad, devices: Sequence[Any]):
    """``loss_and_grad`` over a batch's rows split evenly across
    ``devices``, each part on its own device from a thread of its own; the
    loss and the gradient are the mean over all rows, on the first
    device."""
    import jax
    import jax.numpy as jnp

    if len(devices) == 1:
        return loss_and_grad

    def split(params, tokens, sizes, *, control=False):
        parts = np.array_split(np.asarray(tokens), len(devices))

        def part(dev, rows):
            with jax.default_device(dev):
                loss, g = loss_and_grad(jax.device_put(params, dev), rows,
                                        sizes, control=control)
                return float(loss), g

        with ThreadPoolExecutor(len(devices)) as pool:
            outs = list(pool.map(part, devices, parts))
        w = [len(p) / len(tokens) for p in parts]
        loss = sum(wi * l for wi, (l, _) in zip(w, outs))
        grads = jax.tree_util.tree_map(
            lambda *gs: sum(wi * jax.device_put(g, devices[0])
                            for wi, g in zip(w, gs)),
            *[g for _, g in outs])
        return jnp.float32(loss), grads

    return split


def reference_readings(ref, sizes: Dict[str, Any], key, batches: List[Any],
                       opt: Dict[str, Any], *, control: bool = False,
                       devices: Sequence[Any],
                       rows: Optional[int] = None) -> Readings:
    """Three reference steps from ``ref.init_params(key, sizes)``.

    ``control=True`` computes in the precision below the configuration's
    (``ref.loss_and_grad(..., control=True)``).  ``rows`` keeps only the
    first rows of each batch (a planted fault: half the batch left out).
    With more than one of ``devices`` each step's rows are split across
    them (``split_rows``)."""
    import jax
    import jax.numpy as jnp

    clip_grads, update = adamw_reference(opt)
    loss_and_grad = split_rows(ref.loss_and_grad, devices)
    norms = leaf_norm_fn()
    params = ref.init_params(key, sizes)
    p0 = jax.tree_util.tree_map(jnp.copy, params)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, first = [], None
    for k, b in enumerate(batches[:CHECK_STEPS]):
        tokens = b["tokens"] if rows is None else b["tokens"][:rows]
        loss, g = loss_and_grad(params, tokens, sizes, control=control)
        losses.append(float(loss))
        g = clip_grads(g)
        if first is None:
            first = (to_host(norms(g)), host_leaves(g))
        params, m, v = update(params, m, v, g, jnp.float32(k + 1))
        del g
    change = to_host(norms(jax.tree_util.tree_map(jnp.subtract, params, p0)))
    return Readings(losses, first[0], change, first[1])


# ------------------------------------------------------------- comparison


def _norm(x: np.ndarray) -> float:
    x = x.ravel()
    return float(np.sqrt(np.dot(x, x)))


def _worst_gap(got: Dict[str, float], want: Dict[str, float],
               leaves: List[str], scale: Optional[Dict[str, float]] = None):
    """The largest ``|got - want|`` over ``leaves``, each over the larger
    of that leaf's ``scale`` (by default ``want``) and the median leaf's."""
    if set(got) != set(want):
        raise ValueError(f"leaves differ: program {sorted(got)}, "
                         f"reference {sorted(want)}")
    scale = want if scale is None else scale
    median = float(np.median([scale[k] for k in scale]))
    worst, where = 0.0, None
    for k in leaves:
        gap = abs(got[k] - want[k]) / max(scale[k], median, 1e-30)
        if not np.isfinite(got[k]):
            gap = float("inf")
        if where is None or gap > worst:
            worst, where = gap, k
    return worst, where


def compare(prog: Readings, ref: Readings) -> Dict[str, Dict[str, Any]]:
    """The numbers compared, each with the leaf or step it came from."""
    loss_gaps = [abs(a - b) / abs(b) if np.isfinite(a) else float("inf")
                 for a, b in zip(prog.losses, ref.losses)]
    step = int(np.argmax(loss_gaps))
    grad_gap, g_leaf = _worst_gap(prog.grad_norms, ref.grad_norms,
                                  sorted(ref.grad_norms))
    median_g = float(np.median(list(ref.grad_norms.values())))
    moved = sorted(k for k, n in ref.grad_norms.items()
                   if n >= MOVED_LEAF_SHARE * median_g)
    update_gap, u_leaf = _worst_gap(prog.change_norms, ref.change_norms,
                                    moved)
    grad_err, e_leaf = _worst_gap(
        {k: _norm(prog.first_grad[k] - ref.first_grad[k])
         for k in ref.first_grad}, {k: 0.0 for k in ref.first_grad},
        sorted(ref.first_grad), scale=ref.grad_norms)
    return {
        "loss_gap": {"value": loss_gaps[step], "at": f"step {step + 1}"},
        "grad_gap": {"value": grad_gap, "at": g_leaf},
        "update_gap": {"value": update_gap, "at": u_leaf,
                       "left_out": sorted(set(ref.grad_norms) - set(moved))},
        "grad_err": {"value": grad_err, "at": e_leaf},
    }


def judge(numbers: Dict[str, Dict[str, Any]], limits: Dict[str, float]
          ) -> bool:
    return all(numbers[k]["value"] <= limits[k] for k in limits)


def unchanged_state_readings(ref: Readings) -> Readings:
    """What a step that returns its state unchanged reports: the first
    loss, no gradient in the optimizer and no change."""
    return Readings([ref.losses[0]] * len(ref.losses),
                    {k: 0.0 for k in ref.grad_norms},
                    {k: 0.0 for k in ref.change_norms},
                    {k: np.zeros_like(v) for k, v in ref.first_grad.items()})

