"""Train / serve step builders.

``make_train_step`` assembles the jitted training step from a ModelAPI +
optimizer, with:

* microbatch gradient accumulation (``lax.scan`` over microbatches — keeps
  the activation working set at 1/k while the paper's offload policy keeps
  the per-microbatch boundaries in host memory);
* optional int8+error-feedback cross-pod gradient reduction
  (``cross_pod="int8_ef"``) via a shard_map-manual pod axis;
* donated state buffers (in-place update on device).

``make_serve_steps`` builds the prefill and decode steps (decode donates the
cache — the KV update is in-place).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import offload as ofl
from repro.distributed import compression as comp
from repro.models.model_factory import ModelAPI
from repro.optim.optimizers import Optimizer

Params = Any
TrainState = Dict[str, Any]  # {"params", "opt", "step", ("ef")}


def init_train_state(api: ModelAPI, optimizer: Optimizer, key,
                     error_feedback: bool = False) -> TrainState:
    params = api.init(key)
    state = {"params": params, "opt": optimizer.init(params),
             "step": jnp.zeros((), jnp.int32)}
    if error_feedback:
        state["ef"] = comp.init_error_feedback(params)
    return state


def _split_microbatches(batch: Dict[str, jnp.ndarray], k: int):
    def rs(x):
        assert x.shape[0] % k == 0, (x.shape, k)
        return x.reshape((k, x.shape[0] // k) + x.shape[1:])

    return jax.tree_util.tree_map(rs, batch)


def make_train_step(api: ModelAPI, optimizer: Optimizer, *,
                    grad_accum: int = 1, cross_pod: str = "auto",
                    mesh: Optional[Mesh] = None,
                    donate: bool = True,
                    strategy: Optional[str] = None,
                    engine: Optional[str] = None,
                    offload_opts: Optional[Dict[str, Any]] = None) -> Callable:
    """Returns ``step_fn(state, batch) -> (state, metrics)`` (un-jitted; the
    launcher jits with in/out shardings).

    ``cross_pod``: "auto" — let GSPMD insert the f32 all-reduce;
    "int8_ef" — shard_map-manual pod axis with compressed reduction
    (requires ``mesh`` with a "pod" axis and ``error_feedback`` state).

    ``strategy``: None — plain ``jax.value_and_grad`` (activation memory set
    by the model's ``remat_policy``); "multistage_async" / "revolve" /
    "conventional" — route the backward pass through
    ``repro.api.value_and_grad_offloaded`` over the model's chain
    decomposition (``api.train_chain``), keeping peak Level-1 activations
    O(interval + slots) regardless of depth/sequence length.

    ``engine`` picks the execution engine behind an offloaded strategy (it
    is merged into ``offload_opts``; unset, ``api.default_engine()``
    decides): the segment-compiled executor (``"compiled"``, the default
    off a TPU — one XLA call per interval, O(n/I) host dispatches per
    train step), the step-granular interpreter
    (``"interpreted"``), or the trace-native plan-driven scan
    (``"scan"`` — the whole step stays one XLA computation, so it is the
    one to use when the step is jitted with sharded in/out specs on a
    device mesh, and the only one that composes with ``grad_accum``).
    All three execute the same ``SegmentPlan``.  Remaining ``offload_opts``
    are forwarded (interval=, slots=, storage=, l2_capacity_bytes=, ...);
    ``storage="compressed"`` int8-quantises Level-2 boundary states on the
    executor engines, and ``storage="tiered"`` + ``l2_capacity_bytes=``
    bounds the Level-2 host-RAM footprint (cold boundaries spill to disk
    in plan-aware order).
    """

    def loss_fn(params, batch):
        return api.train_loss(params, batch)

    value_and_grad = jax.value_and_grad(loss_fn)
    if strategy is not None:
        if api.train_chain is None:
            raise ValueError(
                f"model family {api.cfg.family!r} has no chain decomposition;"
                " cannot use an offloaded strategy")
        from repro.api import default_engine, value_and_grad_offloaded

        opts = dict(offload_opts or {})
        if engine is not None:
            opts["engine"] = engine
        opts.setdefault("engine", default_engine())
        if grad_accum != 1 and opts["engine"] != "scan":
            raise ValueError(
                "grad_accum with an offloaded strategy needs the "
                "trace-native engine='scan' (the executor engines escape "
                "the trace via io_callback and cannot run under the "
                "microbatch lax.scan)")
        value_and_grad = value_and_grad_offloaded(
            api.train_chain, strategy=strategy, **opts)

    def grads_of(params, batch):
        if grad_accum == 1:
            return value_and_grad(params, batch)
        micro = _split_microbatches(batch, grad_accum)

        def body(carry, mb):
            loss_acc, g_acc = carry
            loss, g = value_and_grad(params, mb)
            return (loss_acc + loss,
                    jax.tree_util.tree_map(jnp.add, g_acc, g)), None

        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss, grads), _ = jax.lax.scan(body, (jnp.float32(0.0), zeros),
                                        micro)
        scale = 1.0 / grad_accum
        return loss * scale, jax.tree_util.tree_map(
            lambda g: g * scale, grads)

    def apply_update(state, loss, grads):
        with jax.named_scope(ofl.SCOPE_OPTIMIZER):
            new_params, new_opt = optimizer.update(
                grads, state["opt"], state["params"], state["step"])
            out = dict(state, params=new_params, opt=new_opt,
                       step=state["step"] + 1)
            metrics = {"loss": loss.astype(jnp.float32),
                       "grad_norm": jnp.sqrt(sum(
                           jnp.sum(jnp.square(g.astype(jnp.float32)))
                           for g in jax.tree_util.tree_leaves(grads)))}
            return out, metrics

    if cross_pod == "int8_ef":
        if mesh is None or "pod" not in mesh.axis_names:
            raise ValueError("int8_ef needs a mesh with a 'pod' axis")

        def per_pod(state, batch):
            loss, grads = grads_of(state["params"], batch)
            grads, new_ef = comp.compressed_mean(grads, "pod",
                                                 state.get("ef"))
            loss = jax.lax.pmean(loss, "pod")
            new_state, metrics = apply_update(state, loss, grads)
            if "ef" in state:
                new_state["ef"] = new_ef
            return new_state, metrics

        def step_fn(state, batch):
            # partial-manual shard_map: only the pod axis is manual; the
            # data/model axes stay under GSPMD inside the body.
            specs_state = jax.tree_util.tree_map(lambda _: P(), state)
            specs_batch = jax.tree_util.tree_map(
                lambda x: P("pod", *(None,) * (x.ndim - 1)), batch)
            return jax.shard_map(
                per_pod, mesh=mesh,
                in_specs=(specs_state, specs_batch),
                out_specs=(specs_state,
                           jax.tree_util.tree_map(lambda _: P(),
                                                  {"loss": 0, "grad_norm": 0})),
                axis_names={"pod"},
                check_vma=False,
            )(state, batch)

        return step_fn

    def step_fn(state, batch):
        loss, grads = grads_of(state["params"], batch)
        return apply_update(state, loss, grads)

    return step_fn


def make_serve_steps(api: ModelAPI, *, jit: bool = True,
                     donate_cache: bool = True):
    """(prefill_fn, decode_fn) for the serving path.

    ``batch["pos"]`` may be an int32 scalar *or* a ``(B,)`` vector of
    per-request positions — the vector form is what continuous batching
    needs once slots hold different-length sequences.

    ``donate_cache=True`` donates the cache argument to the decode jit (the
    KV update is in-place, halving cache HBM).  It MUST be off whenever a
    retry/preemption boundary is active: a faulted step would leave the
    donated input cache deleted ("Array has been deleted") with no valid
    cache to retry from.  The returned ``decode_fn`` carries a
    ``donates_cache`` attribute so schedulers can assert the wiring.
    """

    def prefill_fn(params, batch):
        return api.prefill(params, batch)

    def decode_fn(params, cache, batch):
        return api.decode(params, cache, batch)

    if jit:
        prefill_fn = jax.jit(prefill_fn)
        decode_fn = jax.jit(
            decode_fn, donate_argnums=(1,) if donate_cache else ())
    decode_fn.donates_cache = jit and donate_cache
    return prefill_fn, decode_fn
