"""Differentiable front-end: the paper's multistage checkpointing as a
drop-in ``jax.value_and_grad``.

    from repro import api

    vg = api.value_and_grad_offloaded(model.train_loss)   # or a ChainSpec
    loss, grads = vg(params, batch)                       # O(I + s) Level-1

Gradients run on the plan -> compile -> execute engine: the chain is split
into per-interval segments (``repro.core.schedule.SegmentPlan``), each
compiled once into a jitted advance / checkpointed-vjp reverse pair
(``repro.core.compiled_ops``), and driven with asynchronous Level-2
store/prefetch by the executor — O(n/I) host dispatches per pass.  Pass
``engine="interpreted"`` for the step-granular interpreter, or
``engine="scan"`` for the trace-native path (one XLA call, composes with
``jax.jit`` / ``jax.vmap`` / mesh sharding) — all engines execute the
same ``SegmentPlan`` (``api.last_plan()``).  On a TPU only the scan engine
runs, and it is the default there (``api.default_engine()``).

See ``repro.api.frontend`` for the transform, ``repro.api.chain`` for the
chain decomposition it differentiates, and ``repro.api.autotune`` for the
§3 schedule selection (``I = ceil(T_T/T_A)``) from measured or roofline
times.
"""
from repro.api.autotune import AutoTuner, GLOBAL_TUNER, TuneResult
from repro.api.chain import ChainSpec, chain_length
from repro.api.frontend import (ENGINES, STORAGE_KINDS, STRATEGIES,
                                OffloadConfig, checkpointed_bptt,
                                default_engine, last_plan, last_stats, last_tune,
                                offloaded_loss, resume_offloaded,
                                value_and_grad_offloaded)
from repro.core.faults import StorageFault  # typed Level-2 failure root
from repro.core.perfmodel import Plan2D, choose_2d_plan
from repro.core.schedule import InnerPlan

__all__ = [
    "AutoTuner", "GLOBAL_TUNER", "TuneResult",
    "ChainSpec", "chain_length",
    "ENGINES", "STORAGE_KINDS", "STRATEGIES",
    "InnerPlan", "Plan2D", "choose_2d_plan",
    "OffloadConfig", "StorageFault", "checkpointed_bptt", "default_engine",
    "last_plan",
    "last_stats", "last_tune",
    "offloaded_loss", "resume_offloaded", "value_and_grad_offloaded",
]
