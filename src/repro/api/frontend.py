"""Drop-in autodiff front-end for asynchronous multistage checkpointing.

``value_and_grad_offloaded(loss)`` is the paper's technique packaged the way
``jax.value_and_grad`` is: you hand it a loss, you get back a function
returning ``(loss, grads)``.  The difference is *how* the backward pass runs:

* the forward chain executes as compiled per-interval segments (one jitted
  ``lax.scan`` call each) while the ``AsyncTransferEngine`` streams every
  ``I``-th carry to Level-2 storage (host RAM, disk, int8-compressed, or a
  capacity-bounded RAM-over-disk tier) on a background thread;
* the backward pass replays segments from Level 2 with double-buffered
  prefetch, each reversed by one compiled checkpointed-vjp call — peak
  Level-1 memory is ``O(I + s)``, independent of chain length, at a constant
  recompute factor and O(n/I) host dispatches (pass ``engine="interpreted"``
  for the step-granular paper-faithful interpreter).

Mechanically this is a ``jax.custom_vjp`` whose fwd/bwd rules escape the
tracer via ``jax.experimental.io_callback``: the traced residual is just the
chain inputs plus an integer handle; the Level-2 state lives host-side in a
run registry between the two callbacks.  That makes the transform compose
with ``jax.value_and_grad`` / ``jax.jit`` like any other JAX function, while
the actual store/prefetch machinery stays the paper-faithful threaded
executor (``repro.core.executor``).

``engine="scan"`` swaps that machinery for the trace-native path: the chain
is rewritten as a plan-driven ``multistage_scan`` (``jax.checkpoint``
segments whose boundary carries the compiler offloads to pinned host
memory), so nothing escapes the trace and the transform additionally
composes with ``jax.vmap`` and mesh sharding.  All three engines execute
the same ``SegmentPlan`` (``api.last_plan()``).

The schedule ``(I, s)`` is chosen by ``repro.api.autotune`` from measured
``T_A``/``T_T`` on the first call (``I = ceil(T_T/T_A)``, §3) and cached per
(model, seq-len, hardware); pass ``interval=`` to pin it manually.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import shutil
import threading
import warnings
import weakref
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import io_callback

from repro.api import autotune as at
from repro.api.chain import (ChainSpec, chain_length, combine, diff_mask,
                             index_xs, partition, zero_cotangent, _dtype_of,
                             _is_inexact)
from repro.core import offload as ofl
from repro.core import schedule as ms
from repro.core.compiled_ops import (CompiledChainOps, CompiledSegmentRunner,
                                     PallasSegmentRunner,
                                     ParamStreamSegmentRunner,
                                     inner_chunked_body)
from repro.core.executor import (CheckpointExecutor, ExecutionStats,
                                 ParamStream)
from repro.core.multistage_scan import multistage_scan
from repro.core.storage import (AsyncTransferEngine, JournaledStorage,
                                make_backend)

STRATEGIES = ("multistage_async", "revolve", "conventional")
ENGINES = ("compiled", "interpreted", "scan")
RUNNERS = ("compiled", "pallas")
STORAGE_KINDS = ("ram", "disk", "compressed", "tiered")


@dataclasses.dataclass(frozen=True)
class OffloadConfig:
    """Static (hashable) knobs of one offloaded-gradient transform."""

    strategy: str = "multistage_async"
    interval: Optional[int] = None    # None -> autotune (I = ceil(T_T/T_A))
    slots: Optional[int] = None       # Level-1 Revolve slots; None -> budget
    storage: str = "ram"              # "ram" | "disk" | "compressed" | "tiered"
    storage_dir: Optional[str] = None
    l2_capacity_bytes: Optional[int] = None  # fast-tier budget ("tiered")
    journal_dir: Optional[str] = None  # crash-consistency WAL directory
    resume: bool = False              # resume a crashed run from the journal
    journal_repair: bool = False      # truncate a CRC-damaged journal on open
    autotune: bool = True
    tuner_id: int = 0                 # key into the tuner registry
    backend_id: int = 0               # key into the shared-backend registry
    #                                   (0 = build a private backend from
    #                                   ``storage``; nonzero = the caller
    #                                   passed backend= — a live Level-2
    #                                   store shared across transforms, e.g.
    #                                   a NamespacedStorage view of one
    #                                   capacity-bounded TieredStorage)
    engine: str = "compiled"          # "compiled" (per-segment XLA calls) |
    #                                   "interpreted" (per-step Python ops) |
    #                                   "scan" (trace-native, one XLA call)
    runner: str = "compiled"          # segment runner for engine="compiled":
    #                                   "compiled" (jitted scan per segment) |
    #                                   "pallas" (fused kernel, DMA overlap)
    mesh: Optional[Any] = None        # jax Mesh -> sharded Level-2 streams
    state_spec: Optional[Any] = None  # PartitionSpec of the boundary carry
    #                                   (None -> derive: batch axes over the
    #                                   mesh's data axes when divisible)
    step_memory_budget: Optional[int] = None  # per-step reverse-peak budget
    #                                   (bytes): when one step's activations
    #                                   exceed it, the planner goes 2D —
    #                                   inner layer/head chunks chosen by
    #                                   perfmodel.choose_2d_plan
    plan_2d: Optional[Tuple[int, int]] = None  # pin the inner axis instead:
    #                                   (layer_chunks, head_chunks)
    offload_params: Optional[str] = None  # stream these parameters through
    #                                   Level-2 alongside boundary states:
    #                                   "moe_experts" streams per-(layer,
    #                                   expert) FFN blobs with plan-aware
    #                                   prefetch one segment ahead

    def __post_init__(self):
        if self.strategy not in STRATEGIES:
            raise ValueError(
                f"unknown strategy {self.strategy!r}; known: {STRATEGIES}")
        if self.engine not in ENGINES:
            raise ValueError(
                f"unknown engine {self.engine!r}; known: {ENGINES}")
        if self.runner not in RUNNERS:
            raise ValueError(
                f"unknown runner {self.runner!r}; known: {RUNNERS}")
        if self.runner == "pallas" and self.engine != "compiled":
            raise ValueError(
                "runner='pallas' fuses the compiled engine's per-segment "
                f"scan into a Pallas kernel; engine={self.engine!r} does "
                "not use segment runners")
        if self.storage == "tiered" and self.l2_capacity_bytes is None:
            raise ValueError(
                "storage='tiered' needs l2_capacity_bytes= (the fast-tier "
                "budget the Level-2 store must stay under)")
        if self.l2_capacity_bytes is not None and self.storage != "tiered":
            raise ValueError(
                "l2_capacity_bytes only applies to storage='tiered' "
                f"(got storage={self.storage!r}); the unbounded backends "
                "have no budget to enforce")
        if self.backend_id and self.mesh is not None:
            raise ValueError(
                "backend= hands the transform one already-built Level-2 "
                "store; sharded per-device streams (mesh=) must be built "
                "from a storage kind instead")
        if self.resume and self.journal_dir is None:
            raise ValueError(
                "resume=True needs journal_dir= (there is nothing to "
                "recover without a write-ahead journal)")
        if self.journal_dir is not None and \
                self.strategy != "multistage_async":
            raise ValueError(
                "journal_dir= journals the Level-2 boundary stores of the "
                "multistage_async strategy; strategy="
                f"{self.strategy!r} keeps no Level-2 state to journal")
        if self.state_spec is not None and self.mesh is None:
            raise ValueError(
                "state_spec= partitions the boundary carry over a mesh; "
                "pass mesh= as well")
        if self.mesh is not None:
            if self.strategy != "multistage_async":
                raise ValueError(
                    "mesh= shards the multistage_async Level-2 streams; "
                    f"strategy={self.strategy!r} keeps no Level-2 state")
            if self.engine == "scan":
                raise ValueError(
                    "engine='scan' is trace-native: shard it by jitting "
                    "with NamedSharding'd inputs instead of mesh= (the "
                    "executor engines own the sharded Level-2 streams)")
            if self.runner == "pallas":
                raise ValueError(
                    "runner='pallas' drives a single device's DMA engine; "
                    "sharded Level-2 streams (mesh=) need runner='compiled'")
        if self.step_memory_budget is not None:
            if self.plan_2d is not None:
                raise ValueError(
                    "pass either step_memory_budget= (the planner chooses "
                    "the inner axis) or plan_2d= (pin it), not both")
            if self.step_memory_budget <= 0:
                raise ValueError(
                    "step_memory_budget must be a positive byte count, got "
                    f"{self.step_memory_budget}")
        if self.plan_2d is not None:
            if len(self.plan_2d) != 2 or any(
                    int(c) < 1 for c in self.plan_2d):
                raise ValueError(
                    "plan_2d must be (layer_chunks, head_chunks) with both "
                    f">= 1, got {self.plan_2d!r}")
        if self.step_memory_budget is not None or self.plan_2d is not None:
            if self.strategy != "multistage_async":
                raise ValueError(
                    "2D plans (step_memory_budget=/plan_2d=) chunk the "
                    "multistage_async reverse sweep's per-step work; "
                    f"strategy={self.strategy!r} has no such sweep")
            if self.engine != "compiled":
                raise ValueError(
                    "2D plans execute in the compiled engine's segment "
                    f"runner; engine={self.engine!r} cannot run the inner "
                    "axis")
            if self.runner == "pallas":
                raise ValueError(
                    "runner='pallas' fuses the plain step body into its "
                    "kernel; the inner remat regions of a 2D plan need "
                    "runner='compiled'")
        if self.engine == "scan":
            if self.strategy != "multistage_async":
                raise ValueError(
                    "engine='scan' implements the multistage_async strategy "
                    f"only, got strategy={self.strategy!r}")
            if self.storage != "ram":
                raise ValueError(
                    "engine='scan' keeps Level-2 state in XLA host memory "
                    "(pinned_host); the pluggable storage backends "
                    f"({STORAGE_KINDS[1:]}) apply to the executor engines "
                    "only")
            if self.journal_dir is not None:
                raise ValueError(
                    "engine='scan' runs entirely inside XLA — its Level-2 "
                    "state cannot be journaled; use the executor engines "
                    "('compiled'/'interpreted') for crash consistency")
        if self.offload_params is not None:
            if self.offload_params != "moe_experts":
                raise ValueError(
                    f"unknown offload_params {self.offload_params!r}; "
                    "known: ('moe_experts',)")
            if self.strategy != "multistage_async":
                raise ValueError(
                    "offload_params= streams parameters through the "
                    "multistage_async Level-2 store; strategy="
                    f"{self.strategy!r} keeps no Level-2 state")
            if self.engine != "compiled" or self.runner != "compiled":
                raise ValueError(
                    "offload_params= assembles streamed parameter slices in "
                    "the compiled segment runner; it needs engine='compiled' "
                    f"with runner='compiled' (got engine={self.engine!r}, "
                    f"runner={self.runner!r})")
            if self.mesh is not None:
                raise ValueError(
                    "offload_params= drives a single Level-2 parameter lane; "
                    "sharded streams (mesh=) are not supported yet")
            if self.journal_dir is not None:
                raise ValueError(
                    "offload_params= keeps transient parameter blobs in "
                    "Level-2; journaling (journal_dir=/resume=) tracks "
                    "boundary states only and cannot replay them")
            if self.storage == "compressed":
                raise ValueError(
                    "offload_params= reads blobs back via non-promoting "
                    "peek, which storage='compressed' would return encoded; "
                    "use 'ram', 'disk' or 'tiered'")
            if self.step_memory_budget is not None or \
                    self.plan_2d is not None:
                raise ValueError(
                    "offload_params= is not supported together with 2D "
                    "plans (step_memory_budget=/plan_2d=)")


@dataclasses.dataclass(frozen=True)
class _Static:
    """Everything the custom_vjp rules need that must stay out of the trace."""

    spec: ChainSpec
    cfg: OffloadConfig
    xs_treedef: Any
    xs_mask: Tuple[bool, ...]
    inner: Optional[ms.InnerPlan] = None  # 2D plans: the resolved inner axis


# ---------------------------------------------------------------------------
# tuner + run registries (host side)
# ---------------------------------------------------------------------------

# Weak registry: a custom tuner lives exactly as long as its owner holds it
# (dropping the transform frees the tuner; lookups then fall back to the
# global tuner).  GLOBAL_TUNER itself is kept alive by its module.
_TUNERS: "weakref.WeakValueDictionary[int, at.AutoTuner]" = \
    weakref.WeakValueDictionary({0: at.GLOBAL_TUNER})
_TUNER_IDS = itertools.count(1)


def _register_tuner(tuner: Optional[at.AutoTuner]) -> int:
    if tuner is None or tuner is at.GLOBAL_TUNER:
        return 0
    tid = next(_TUNER_IDS)
    _TUNERS[tid] = tuner
    return tid


# Same weak-registry pattern for caller-supplied Level-2 backends: the
# OffloadConfig must stay a hashable frozen dataclass, so the live backend
# object is parked here and the config carries only its id.  The transform
# keeps a strong reference (``vg.backend``), so the entry lives exactly as
# long as some caller can still invoke the transform.
_SHARED_BACKENDS: "weakref.WeakValueDictionary[int, Any]" = \
    weakref.WeakValueDictionary()
_SHARED_BACKEND_IDS = itertools.count(1)


def _register_shared_backend(backend: Optional[Any]) -> int:
    if backend is None:
        return 0
    bid = next(_SHARED_BACKEND_IDS)
    _SHARED_BACKENDS[bid] = backend
    return bid


@dataclasses.dataclass
class _RunRecord:
    strategy: str
    tune: at.TuneResult
    run: Any = None                   # MultistageRun for multistage_async
    tmpdir: Optional[str] = None      # auto-created disk Level-2 directory

    def dispose(self) -> None:
        # Best-effort: a stale run's pending transfer error (engine.close
        # re-raises) must never crash the healthy call that evicted it.
        if self.run is not None:
            try:
                self.run.close()
            except Exception:
                pass
        if self.tmpdir is not None:
            shutil.rmtree(self.tmpdir, ignore_errors=True)
            self.tmpdir = None


_RUNS: Dict[int, _RunRecord] = {}
_RUNS_LOCK = threading.Lock()
_HANDLES = itertools.count(1)
# Backstop against pullbacks that are taken but never invoked (each holds an
# engine + Level-2 states).  Generous: a legitimate program holds one live
# run per offloaded chain between its forward and backward passes.
_MAX_LIVE_RUNS = 64

_LAST: Dict[str, Any] = {"stats": None, "tune": None, "plan": None}


def last_stats() -> Optional[ExecutionStats]:
    """ExecutionStats of the most recent offloaded backward pass (executor
    instrumentation: peak Level-1 states/bytes, advances, stall times).
    The scan engine has no executor stats (its schedule runs inside XLA):
    it clears this to ``None`` at *trace* time — a cached jit call leaves
    whatever an intervening executor-engine pass recorded."""
    return _LAST["stats"]


def last_tune() -> Optional[at.TuneResult]:
    """The schedule the autotuner chose for the most recent forward pass."""
    return _LAST["tune"]


def last_plan() -> Optional[ms.SegmentPlan]:
    """The :class:`~repro.core.schedule.SegmentPlan` behind the most recent
    multistage pass — the single IR every engine executes.  The executor
    engines record it per run; the scan engine records it at *trace* time
    (a cached jit call leaves it untouched).  ``None`` after a
    revolve/conventional pass."""
    return _LAST["plan"]


def _push_run(handle: int, rec: _RunRecord) -> None:
    evicted = []
    with _RUNS_LOCK:
        _RUNS[handle] = rec
        while len(_RUNS) > _MAX_LIVE_RUNS:
            evicted.append(_RUNS.pop(min(_RUNS)))
    for old in evicted:
        old.dispose()


def _pop_run(handle: int) -> _RunRecord:
    with _RUNS_LOCK:
        try:
            return _RUNS.pop(handle)
        except KeyError:
            raise RuntimeError(
                f"offloaded-chain run {handle} is no longer live (more than "
                f"{_MAX_LIVE_RUNS} pullbacks held open, or backward called "
                "twice); re-run the forward pass") from None


def _make_backend(cfg: OffloadConfig):
    """Build the Level-2 backend from the pluggable registry
    (``repro.core.storage.make_backend`` — unknown kinds raise there, so
    backends added via ``register_backend`` work here unmodified).  Returns
    (backend, tmpdir) — tmpdir is set when we created a temp Level-2
    directory that must be removed when the run is disposed."""
    if cfg.backend_id:
        backend = _SHARED_BACKENDS.get(cfg.backend_id)
        if backend is None:
            raise ValueError(
                "the backend= object this transform was built over is no "
                "longer alive; hold a reference to the transform (or the "
                "backend) for as long as it is called")
        if cfg.journal_dir is not None:
            # Journal composes OUTSIDE the shared store: the WAL records the
            # run's raw (un-namespaced) keys, so a resume replays into
            # whatever namespace the new backend view carries.
            backend = JournaledStorage(backend, cfg.journal_dir,
                                       repair=cfg.journal_repair)
        return backend, None
    tmpdir = None
    kwargs = {}
    if cfg.storage == "disk" or cfg.storage == "tiered" or (
            cfg.storage == "compressed" and cfg.storage_dir is not None):
        # tiered always gets a directory: its slow tier is the disk (the
        # paper's DRAM->SSD platform) unless the caller pinned one
        directory = cfg.storage_dir
        if directory is None:
            import tempfile

            directory = tempfile.mkdtemp(prefix="repro_l2_")
            tmpdir = directory
        kwargs["directory"] = directory
    if cfg.storage == "tiered":
        kwargs["capacity_bytes"] = cfg.l2_capacity_bytes
    if cfg.journal_dir is not None:
        kwargs["journal"] = cfg.journal_dir
        kwargs["journal_repair"] = cfg.journal_repair
    if cfg.mesh is not None:
        # one Level-2 stream per mesh device: each device's shard of every
        # boundary goes to its own inner backend on its own writer thread
        devices = list(cfg.mesh.devices.flat)
        kwargs["shards"] = len(devices)
        kwargs["devices"] = devices
    try:
        return make_backend(cfg.storage, **kwargs), tmpdir
    except BaseException:
        # construction can raise after the tempdir exists (e.g. a
        # ChecksumError from a corrupt journal): don't orphan it
        if tmpdir is not None:
            shutil.rmtree(tmpdir, ignore_errors=True)
        raise


# ---------------------------------------------------------------------------
# per-spec jitted chain operators
# ---------------------------------------------------------------------------


class _Ops:
    """Jitted operators for one (spec, xs-structure): per-step forward /
    backward for the interpreted engine, plus the per-segment compiled ops
    (``CompiledChainOps``) the segment-compiled engine dispatches.  The LRU
    over this class *is* the compile cache — a second transform over the same
    spec reuses every compiled segment."""

    def __init__(self, spec: ChainSpec, xs_treedef, xs_mask,
                 inner: Optional[ms.InnerPlan] = None):
        self.spec = spec
        rbody = None
        if inner is not None:
            # 2D plan: the reverse sweep differentiates through the
            # inner-chunked body (primal-identical — remat regions only
            # change what the backward keeps live), the forward advance
            # keeps the plain body for maximal fusion.
            rbody = inner_chunked_body(spec.layer_body, inner)
        self.cops = CompiledChainOps(spec.body, xs_treedef, xs_mask,
                                     reverse_body=rbody)

        @jax.jit
        def fwd(params, state, x, batch):
            return spec.body(params, state, x, batch)

        @jax.jit
        def scan_fwd(params, carry0, xs, batch):
            def step(c, x):
                return spec.body(params, c, x, batch), None

            carry, _ = lax.scan(step, carry0, xs)
            return carry

        @jax.jit
        def bwd(params, state, x_diff, x_nondiff, batch, dcarry, gacc):
            def f(p, c, xd):
                x = combine(xd, x_nondiff, xs_treedef, xs_mask)
                return spec.body(p, c, x, batch)

            _, vjp = jax.vjp(f, params, state, x_diff)
            dp, dc, dxd = vjp(dcarry)
            gacc = jax.tree_util.tree_map(jnp.add, gacc, dp)
            return dc, gacc, dxd

        @jax.jit
        def zero_grads(params):
            return jax.tree_util.tree_map(
                lambda p: jnp.zeros(jnp.shape(p), _dtype_of(p)), params)

        self.fwd = fwd
        self.scan_fwd = scan_fwd
        self.bwd = bwd
        self.zero_grads = zero_grads


@functools.lru_cache(maxsize=128)
def _get_ops(spec: ChainSpec, xs_treedef, xs_mask,
             inner: Optional[ms.InnerPlan] = None) -> _Ops:
    return _Ops(spec, xs_treedef, xs_mask, inner)


# ---------------------------------------------------------------------------
# 2D plans: trace-time inner-axis resolution
# ---------------------------------------------------------------------------

# The inner axis must be known when the loss is *traced* (the chunked
# readout and the inner-chunked reverse body are part of the traced
# computation), and it is a pure function of shapes — memory feasibility
# does not depend on the measured (T_A, T_T) the way the outer interval
# does.  Cached per (spec, budget, input shapes) so repeated gradient
# calls re-trace nothing.
_INNER_CACHE: Dict[Tuple, Optional[ms.InnerPlan]] = {}


def _shape_signature(*trees) -> Tuple:
    return tuple(
        (str(np.shape(leaf)), str(_dtype_of(leaf)))
        for tree in trees for leaf in jax.tree_util.tree_leaves(tree))


def _resolve_inner(spec: ChainSpec, cfg: OffloadConfig, params, carry0, xs,
                   batch) -> Optional[ms.InnerPlan]:
    """The inner (per-step) axis of the plan, or ``None`` for 1D.

    ``cfg.plan_2d`` pins it; ``cfg.step_memory_budget`` derives it from the
    chain's real per-layer byte profile (``analysis.jaxpr_cost``) through
    the Gruslys-style DP (``perfmodel.choose_2d_plan``).  Raises when the
    budget is infeasible, naming the smallest budget that would work."""
    if cfg.plan_2d is None and cfg.step_memory_budget is None:
        return None
    if not spec.supports_2d:
        raise ValueError(
            f"chain {spec.name!r} has no per-step layer decomposition — 2D "
            "plans (step_memory_budget=/plan_2d=) need "
            "ChainSpec.layer_body/n_layers (and readout_chunked for head "
            "chunking)")
    if cfg.plan_2d is not None:
        lc, hc = cfg.plan_2d
        return ms.InnerPlan(n_layers=spec.n_layers, layer_chunks=int(lc),
                            head_chunks=int(hc))
    key = (spec, cfg.step_memory_budget,
           _shape_signature(params, carry0, xs, batch))
    if key not in _INNER_CACHE:
        from repro.analysis.jaxpr_cost import chain_step_byte_profile
        from repro.core import perfmodel as pm

        state_bytes, layer_bytes, head_bytes = chain_step_byte_profile(
            spec, params, carry0, index_xs(xs, 0), batch)
        plan2d = pm.choose_2d_plan(
            chain_length(xs), t_a=1.0, t_t=0.0,
            s_l1=cfg.slots if cfg.slots is not None else 16,
            state_bytes=state_bytes, layer_bytes=layer_bytes,
            budget_bytes=cfg.step_memory_budget, head_bytes=head_bytes,
            interval=cfg.interval if cfg.interval is not None else 1)
        if not plan2d.feasible:
            need = int(np.ceil(plan2d.min_budget_bytes))
            raise ValueError(
                f"step_memory_budget={cfg.step_memory_budget} is infeasible "
                f"for chain {spec.name!r}: even layer_chunks="
                f"{spec.n_layers} peaks above it; the smallest feasible "
                f"budget is {need} bytes")
        _INNER_CACHE[key] = plan2d.inner
    return _INNER_CACHE[key]


# ---------------------------------------------------------------------------
# host-side callbacks (run outside the trace)
# ---------------------------------------------------------------------------


def _select_runner(cfg: OffloadConfig) -> str:
    """Resolve ``cfg.runner`` against the hardware actually present.

    ``runner="pallas"`` runs its kernels in interpret mode when forced via
    ``REPRO_PALLAS_INTERPRET=1``; on a TPU it raises, because Mosaic cannot
    lower the fused kernels (see :func:`segment_pallas.runner_supported`);
    anywhere else it falls back to the plain compiled runner with a
    one-line warning so CPU CI and laptops keep working untouched.
    """
    if cfg.runner != "pallas":
        return cfg.runner
    from repro.kernels import segment_pallas as sp

    ok, reason = sp.runner_supported()
    if ok:
        return "pallas"
    if jax.default_backend() == "tpu":
        raise NotImplementedError(reason)
    warnings.warn(reason, stacklevel=3)  # one line: why + the fallback
    return "compiled"


_EXPERT_LEAF_NAMES = ("w_gate", "w_up", "w_down")


def _expert_leaf_ids(xs) -> Tuple[int, ...]:
    """Flat indices of the per-(layer, expert) MoE parameter leaves in the
    stacked chain inputs: leaves under a ``'moe'`` subtree named
    ``w_gate``/``w_up``/``w_down`` (shape ``(n_layers, n_experts, ...)``).
    ``tree_flatten_with_path`` enumerates leaves in ``tree_flatten`` order,
    so these indices address the plain flattened list too."""
    ids = []
    flat, _ = jax.tree_util.tree_flatten_with_path(xs)
    for i, (path, leaf) in enumerate(flat):
        names = [getattr(p, "key", None) for p in path]
        if "moe" in names and names and names[-1] in _EXPERT_LEAF_NAMES \
                and np.ndim(leaf) >= 2:
            ids.append(i)
    return tuple(ids)


def _resolve_schedule(static: _Static, ops: _Ops, params, carry0, xs, batch,
                      n: int, backend, runner: str = "compiled",
                      param_stream_bytes: int = 0) -> at.TuneResult:
    cfg = static.cfg
    tuner = _TUNERS.get(cfg.tuner_id, at.GLOBAL_TUNER)
    if cfg.interval is not None:
        return tuner.manual(static.spec.name, n=n, interval=cfg.interval,
                            slots=cfg.slots)
    if cfg.strategy != "multistage_async" or not cfg.autotune or \
            backend is None:
        interval = max(1, min(n, 32))
        return tuner.manual(static.spec.name, n=n, interval=interval,
                            slots=cfg.slots)

    # T_A depends on the execution engine (amortised compiled segments vs
    # per-step dispatch), so the engine — and for the compiled engine the
    # segment runner — is part of the tuner cache identity.
    tune_name = f"{static.spec.name}:{cfg.engine}"
    if runner == "pallas":
        tune_name += ":pallas"
    if param_stream_bytes:
        # param streaming adds per-segment Level-2 read traffic (T_P) to
        # the interval trade-off — keep its schedule out of the plain cache
        tune_name += ":pstream"
    if cfg.engine == "compiled":
        # T_A is the *amortised* per-step time of a compiled segment, not a
        # per-step dispatch: probe one advance_segment over a short prefix.
        # Snap the probe length onto a divisor of n so it coincides with a
        # snap_interval candidate — when the tuner then picks it, the probe
        # compile is the run's compile, not a throwaway.
        from repro.core.multistage_scan import choose_interval

        cap = max(1, min(n, 32))
        cand = choose_interval(n, cap)
        # don't let a prime-ish n shrink the probe to a few steps — the
        # amortised measurement needs a real segment
        probe_len = cand if cand >= min(cap, 8) else cap
        xs_probe = jax.tree_util.tree_map(lambda leaf: leaf[:probe_len], xs)

        store_state0 = None
        if runner == "pallas":
            # probe the *fused* path: T_A includes the in-kernel boundary
            # copy, and T_T is measured from a host-resident state because
            # the kernel has already DMA'd the boundary off the device —
            # the store only pays the un-hidden (serialisation) residual.
            from repro.kernels import segment_pallas as sp

            interp = sp.default_interpret()

            def forward_segment(state):
                out, _ = sp.fused_advance_segment(
                    ops.cops.body, ops.cops.xs_treedef, ops.cops.xs_mask,
                    params, state, xs_probe, batch,
                    chunk=probe_len, interpret=interp)
                return out

            store_state0 = jax.tree_util.tree_map(np.asarray, carry0)
        else:
            def forward_segment(state):
                if ops.cops.donates_carry:
                    # advance_segment donates its carry on accelerators;
                    # the probe reuses state0 across repeats, so feed it a
                    # copy.
                    state = jax.tree_util.tree_map(
                        lambda x: jnp.array(x, copy=True), state)
                return ops.cops.advance_segment(params, state, xs_probe,
                                                batch)

        tune = tuner.measure(tune_name,
                             forward_segment=forward_segment,
                             segment_len=probe_len,
                             state0=carry0, n=n, backend=backend,
                             store_state0=store_state0, mesh=cfg.mesh,
                             param_stream_bytes=param_stream_bytes)
    else:
        def forward_step(state, k):
            return ops.fwd(params, state, index_xs(xs, k), batch)

        tune = tuner.measure(tune_name, forward_step=forward_step,
                             state0=carry0, n=n, backend=backend,
                             mesh=cfg.mesh)
    if cfg.slots is not None:
        tune = dataclasses.replace(tune, slots=cfg.slots)
    return tune


def _mesh_place(cfg: OffloadConfig, backend, params, carry0, xs, batch,
                dcarry=None):
    """Commit the chain inputs to ``cfg.mesh`` (the io_callback hands the
    host callbacks plain numpy — any sharding the caller had is gone):
    boundary carries under the derived state sharding
    (``distributed.sharding.state_shardings``), ``xs`` split along its
    batch axis, params/batch replicated.  Records the carry shardings on
    a sharded backend first, so its per-device streams know how to split
    host-side payloads (journal replay, autotune probes) the same way.

    With the inputs placed *before* schedule resolution, the autotune
    probes run SPMD on the mesh — ``T_A`` is the real per-device rate and
    the fan-out store probe measures the true per-stream ``T_T``."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.distributed import sharding as shd

    mesh = cfg.mesh
    state_sh = shd.state_shardings(mesh, carry0, cfg.state_spec)
    if backend is not None:
        set_sh = getattr(backend, "set_state_sharding", None)
        if set_sh is not None:
            set_sh(state_sh)
    rep = NamedSharding(mesh, P())
    carry0 = jax.device_put(carry0, state_sh)
    xs = jax.device_put(xs, shd.chain_input_shardings(mesh, xs))
    params = jax.device_put(
        params, jax.tree_util.tree_map(lambda _: rep, params))
    batch = jax.device_put(
        batch, jax.tree_util.tree_map(lambda _: rep, batch))
    if dcarry is None:
        return params, carry0, xs, batch
    dcarry = jax.device_put(
        dcarry, shd.state_shardings(mesh, dcarry, cfg.state_spec))
    return params, carry0, xs, batch, dcarry


def _input_fingerprint(*trees) -> str:
    """Sampled identity of the gradient call's inputs
    (params/carry0/xs/batch): per-leaf shape+dtype+nbytes plus a CRC of
    bounded prefix/middle/suffix slices.  Written into the journal's
    BEGIN record and checked before a resume — resuming a crashed sweep
    under *different* inputs (e.g. a restart from an older model
    checkpoint with a stale journal) would silently mix two parameter
    sets into one gradient, so a mismatch falls back to a fresh,
    journaled run.

    The check is probabilistic by design: hashing every byte of a
    multi-GB pytree per gradient call is not affordable, so O(KB) per
    leaf is sampled from three spread-out slices.  Any realistic input
    change (a different batch, an optimizer step — and in the launcher
    the per-step batch differs always) lands in a sampled region with
    overwhelming probability; inputs crafted to collide outside the
    samples are out of scope (documented in the README)."""
    import zlib

    crc = 0
    for tree in trees:
        for leaf in jax.tree_util.tree_leaves(tree):
            a = np.asarray(leaf)
            crc = zlib.crc32(
                f"{a.shape}{a.dtype}{a.nbytes}".encode(), crc)
            # bound the copied bytes: slice flat views *before*
            # materialising (tobytes() on the full array would memcpy
            # multi-GB pytrees once per gradient call)
            if not a.flags.c_contiguous:
                a = np.ascontiguousarray(a)
            flat = a.reshape(-1)
            n = flat.shape[0]
            k = max(1, 2048 // max(1, a.itemsize))
            for sl in (flat[:k], flat[max(0, n // 2 - k // 2):
                                      n // 2 + k // 2 + 1], flat[-k:]):
                crc = zlib.crc32(np.ascontiguousarray(sl).tobytes(), crc)
    return f"{crc:08x}"


def _fwd_callback(static: _Static, params, carry0, xs, batch):
    spec, cfg = static.spec, static.cfg
    ops = _get_ops(spec, static.xs_treedef, static.xs_mask, static.inner)
    n = chain_length(xs)
    handle = next(_HANDLES)

    def fwd_op(state, k):
        return ops.fwd(params, state, index_xs(xs, k), batch)

    if cfg.strategy == "multistage_async":
        runner_kind = _select_runner(cfg)
        backend, tmpdir = _make_backend(cfg)
        engine = None
        try:
            if cfg.mesh is not None:
                # rebind: fwd_op's closure is late-binding, so the placed
                # (sharded) arrays drive the probes and the forward sweep
                params, carry0, xs, batch = _mesh_place(
                    cfg, backend, params, carry0, xs, batch)
            recovered = None
            fingerprint = None
            if cfg.journal_dir is not None:
                fingerprint = _input_fingerprint(params, carry0, xs, batch)
            if cfg.resume:
                # what survived the crash: durable boundary keys + the last
                # plan cursor.  Unusable recoveries (no cursor, a cleanly
                # finished run, a different chain length, or inputs that
                # do not match the crashed run's fingerprint) fall back to
                # a fresh — still journaled — run.
                recovered = backend.recover()
                cur = recovered.cursor
                old_fp = recovered.meta.get("fingerprint")
                if cur is None or cur.phase == "done" or cur.n != n or \
                        (old_fp is not None and old_fp != fingerprint):
                    recovered = None
            stream_leaves = None
            n_experts = 0
            param_stream_bytes = 0
            if cfg.offload_params is not None:
                # host copies of the streamed leaves (frozen np views feed
                # the Level-2 lane bit-exactly); the runner's xs keep 0-d
                # placeholders at those flat positions so the treedef — and
                # with it the jit cache identity — is preserved
                leaf_ids = _expert_leaf_ids(xs)
                if not leaf_ids:
                    raise ValueError(
                        "offload_params='moe_experts' found no per-expert "
                        "parameter leaves in the chain inputs (expected "
                        "stacked MoE weights w_gate/w_up/w_down under a "
                        "'moe' subtree)")
                flat_leaves = jax.tree_util.tree_leaves(xs)
                stream_leaves = {i: np.asarray(flat_leaves[i])
                                 for i in leaf_ids}
                n_experts = int(next(iter(
                    stream_leaves.values())).shape[1])
                param_stream_bytes = sum(
                    int(a[0].nbytes) for a in stream_leaves.values())
            if recovered is not None:
                # the journal cursor pins the schedule: resuming under a
                # different (I, s) than the crashed run would orphan its
                # durable boundaries
                tuner = _TUNERS.get(cfg.tuner_id, at.GLOBAL_TUNER)
                tune = tuner.manual(static.spec.name, n=n,
                                    interval=recovered.cursor.interval,
                                    slots=recovered.cursor.s_l1)
            else:
                tune = _resolve_schedule(static, ops, params, carry0, xs,
                                         batch, n, backend,
                                         runner=runner_kind,
                                         param_stream_bytes=
                                         param_stream_bytes)
            engine = AsyncTransferEngine(backend)
            ex = CheckpointExecutor(fwd_op, None)
            runner = None
            param_stream = None
            if cfg.engine == "compiled":
                # one jitted advance/reverse call per segment (O(n/I) host
                # dispatches); the runner also collects per-step input
                # cotangents segment-wise during the reverse sweep
                if runner_kind == "pallas":
                    # fused kernel: the boundary copy overlaps the next
                    # chunk's compute inside advance (advance_with_store)
                    runner = PallasSegmentRunner(ops.cops, params, xs,
                                                 batch, s_l1=tune.slots)
                elif stream_leaves is not None:
                    param_stream = ParamStream(engine, stream_leaves,
                                               n_experts=n_experts)
                    leaves, treedef = jax.tree_util.tree_flatten(xs)
                    xs_runner = jax.tree_util.tree_unflatten(treedef, [
                        np.zeros((), _dtype_of(leaf))
                        if i in stream_leaves else leaf
                        for i, leaf in enumerate(leaves)])
                    runner = ParamStreamSegmentRunner(
                        ops.cops, params, xs_runner, batch,
                        s_l1=tune.slots, stream=param_stream,
                        inner=static.inner)
                else:
                    runner = CompiledSegmentRunner(ops.cops, params, xs,
                                                   batch, s_l1=tune.slots,
                                                   inner=static.inner)
            x_n, run = ex.multistage_forward(
                carry0, n, interval=tune.interval, s_l1=tune.slots,
                engine=engine, runner=runner, resume_from=recovered,
                inner=static.inner, param_stream=param_stream,
                run_meta={"fingerprint": fingerprint}
                if fingerprint is not None else None)
        except BaseException:
            # multistage_forward treats a passed-in engine as borrowed and
            # won't close it on error — engine and backend are ours, so
            # close both here (a journaled backend holds an open WAL fd;
            # leaking it across an in-process retry loop piles up fds).
            if engine is not None:
                try:
                    engine.close()
                except Exception:
                    pass
            bclose = getattr(backend, "close", None)
            if bclose is not None:
                try:
                    bclose()
                except Exception:
                    pass
            if tmpdir is not None:
                shutil.rmtree(tmpdir, ignore_errors=True)
            raise
        # the run borrows nothing: it owns the engine and must close it
        run.own_engine = True
        _push_run(handle, _RunRecord(cfg.strategy, tune, run, tmpdir=tmpdir))
        _LAST["plan"] = run.plan
    else:
        tune = _resolve_schedule(static, ops, params, carry0, xs, batch, n,
                                 None)
        x_n = ops.scan_fwd(params, carry0, xs, batch)
        _push_run(handle, _RunRecord(cfg.strategy, tune))
        _LAST["plan"] = None
    _LAST["tune"] = tune
    return x_n, np.int32(handle)


def _bwd_callback(static: _Static, handle, params, carry0, xs, batch, dcarry):
    spec = static.spec
    rec = _pop_run(int(handle))
    ops = _get_ops(spec, static.xs_treedef, static.xs_mask, static.inner)
    n = chain_length(xs)
    if static.cfg.mesh is not None:
        # the reverse sweep reassembles boundaries under their recorded
        # shardings; place the remaining operands to match (backend=None —
        # the forward pass already recorded the carry shardings on it)
        params, carry0, xs, batch, dcarry = _mesh_place(
            static.cfg, None, params, carry0, xs, batch, dcarry)
    xs_diff, xs_nondiff = partition(xs, static.xs_mask)
    collect_dx = any(static.xs_mask)
    dx_slices: Dict[int, Any] = {}

    def fwd_op(state, k):
        return ops.fwd(params, state, index_xs(xs, k), batch)

    def bwd_op(state, adjoint, k):
        dc, gacc = adjoint
        xd = [leaf[k] for leaf in xs_diff]
        xnd = [leaf[k] for leaf in xs_nondiff]
        dc, gacc, dxd = ops.bwd(params, state, xd, xnd, batch, dc, gacc)
        if collect_dx:
            dx_slices[k] = dxd
        return dc, gacc

    ex = CheckpointExecutor(fwd_op, bwd_op)
    adjoint0 = (dcarry, ops.zero_grads(params))
    runner = rec.run.runner if rec.run is not None else None

    # Journaled runs checkpoint each reversed segment's per-step input
    # cotangents alongside the adjoint cursor, so a mid-sweep resume can
    # still stitch the full-chain dxs without re-reversing anything.
    def artifact_fn(seg):
        if isinstance(runner, CompiledSegmentRunner):
            return runner.dx_segments.get(seg.begin)
        if collect_dx:
            return {k: dx_slices[k]
                    for k in range(seg.begin, seg.end) if k in dx_slices}
        return None

    def restore_artifact_fn(begin, artifact):
        if artifact is None:
            return
        if isinstance(runner, CompiledSegmentRunner):
            runner.dx_segments[begin] = artifact
        else:
            dx_slices.update(artifact)

    try:
        if rec.strategy == "multistage_async":
            adjoint, stats = ex.multistage_reverse(
                rec.run, adjoint0, artifact_fn=artifact_fn,
                restore_artifact_fn=restore_artifact_fn)
        elif rec.strategy == "revolve":
            adjoint, stats = ex.run_revolve(carry0, n, adjoint0,
                                            s=rec.tune.slots)
        else:  # conventional
            adjoint, stats = ex.run_conventional(carry0, n, adjoint0)
    finally:
        rec.dispose()  # idempotent: reverse already closed the run's engine
    _LAST["stats"] = stats
    dcarry0, gparams = adjoint
    if not collect_dx:
        dxs_diff = []
    elif isinstance(runner, CompiledSegmentRunner):
        # per-segment stacked cotangents, stitched back into full arrays
        dxs_diff = runner.collect_dx(rec.run.plan)
    else:
        dxs_diff = [
            jnp.stack([dx_slices[k][i] for k in range(n)])
            for i in range(len(xs_diff))
        ]
    return gparams, dcarry0, dxs_diff


# ---------------------------------------------------------------------------
# the custom_vjp chain
# ---------------------------------------------------------------------------


def _sds(tree):
    return jax.tree_util.tree_map(
        lambda leaf: jax.ShapeDtypeStruct(np.shape(leaf), _dtype_of(leaf)),
        tree)


def _chain_primal(static: _Static, params, carry0, xs, batch):
    """Primal: semantically just the scan (value-only calls never pay for
    checkpointing); differentiation swaps in the executor via fwd/bwd."""
    spec = static.spec

    def step(c, x):
        return spec.body(params, c, x, batch), None

    carry, _ = lax.scan(step, carry0, xs)
    return carry


_chain = jax.custom_vjp(_chain_primal, nondiff_argnums=(0,))


def _chain_fwd(static: _Static, params, carry0, xs, batch):
    out_sds = jax.eval_shape(
        functools.partial(_chain_primal, static),
        params, carry0, xs, batch)
    for leaf in jax.tree_util.tree_leaves(out_sds):
        if not _is_inexact(leaf):
            raise TypeError(
                "chain carry leaves must be inexact (float) arrays; fold "
                "integer state into xs/batch instead")
    carry_n, handle = io_callback(
        functools.partial(_fwd_callback, static),
        (out_sds, jax.ShapeDtypeStruct((), np.int32)),
        params, carry0, xs, batch)
    return carry_n, (params, carry0, xs, batch, handle)


def _chain_bwd(static: _Static, res, dcarry):
    params, carry0, xs, batch, handle = res
    xs_diff, xs_nondiff = partition(xs, static.xs_mask)
    out_sds = (_sds(params), _sds(carry0), _sds(xs_diff))
    gparams, dcarry0, dxs_diff = io_callback(
        functools.partial(_bwd_callback, static), out_sds,
        handle, params, carry0, xs, batch, dcarry)
    dxs = combine(dxs_diff, [zero_cotangent(leaf) for leaf in xs_nondiff],
                  static.xs_treedef, static.xs_mask)
    dbatch = jax.tree_util.tree_map(zero_cotangent, batch)
    return gparams, dcarry0, dxs, dbatch


_chain.defvjp(_chain_fwd, _chain_bwd)


# ---------------------------------------------------------------------------
# the trace-native scan engine (engine="scan")
# ---------------------------------------------------------------------------


def _resolve_scan_schedule(spec: ChainSpec, cfg: OffloadConfig, params,
                           carry0, xs, batch, n: int) -> at.TuneResult:
    """Schedule for a scan-engine chain.  Runs at trace time (the arguments
    may be tracers); measurement probes use zero stand-ins built from shapes
    only, and the result lands in the shared tuner cache under the
    ``"<spec>:scan"`` engine-qualified name."""
    tuner = _TUNERS.get(cfg.tuner_id, at.GLOBAL_TUNER)
    if cfg.interval is not None:
        return tuner.manual(spec.name, n=n, interval=cfg.interval,
                            slots=cfg.slots)
    if not cfg.autotune:
        return tuner.manual(spec.name, n=n, interval=max(1, min(n, 32)),
                            slots=cfg.slots)
    tune = tuner.measure_scan(f"{spec.name}:scan", body=spec.body,
                              params=params, carry0=carry0, xs=xs,
                              batch=batch, n=n,
                              segment_len=max(1, min(n, 32)))
    if cfg.slots is not None:
        tune = dataclasses.replace(tune, slots=cfg.slots)
    return tune


def _scan_loss(spec: ChainSpec, cfg: OffloadConfig
               ) -> Callable[[Any, Any], Any]:
    """The loss with its chain segment rewritten as a plan-driven
    ``multistage_scan``: segment boundaries offload to XLA host memory
    (compiler-scheduled copy-start/copy-done — the paper's async Level-2
    transfers) and segment interiors recompute at the plan's inner chunk
    granularity.  Everything stays inside the trace — no io_callback, no run
    registry — so the transform composes with ``jax.jit``, ``jax.vmap`` and
    mesh sharding.  On backends that cannot lower host placement (CPU) the
    boundaries stay in HBM: plain plan-segmented remat, same schedule."""

    def loss(params, batch):
        with jax.named_scope(ofl.SCOPE_PRELUDE):
            carry0, xs = spec.prelude(params, batch)
        n = chain_length(xs)
        tune = _resolve_scan_schedule(spec, cfg, params, carry0, xs, batch, n)
        plan = ms.segment_plan(n, tune.interval, tune.slots)
        _LAST["tune"] = tune
        _LAST["plan"] = plan
        _LAST["stats"] = None

        def step(c, x):
            return spec.body(params, c, x, batch), None

        carry_n, _ = multistage_scan(
            step, carry0, xs, plan=plan,
            offload=ofl.host_offload_supported())
        with jax.named_scope(ofl.SCOPE_READOUT):
            return spec.readout(params, carry_n, batch)

    return loss


# ---------------------------------------------------------------------------
# public front-end
# ---------------------------------------------------------------------------


def _as_chain_spec(loss_fn) -> Optional[ChainSpec]:
    if isinstance(loss_fn, ChainSpec):
        return loss_fn
    return getattr(loss_fn, "chain_spec", None)


def default_engine() -> str:
    """The engine :func:`value_and_grad_offloaded` runs when none is given:
    ``"scan"`` on a TPU, where the executor engines cannot run (see
    :func:`offloaded_loss`), and ``"compiled"`` elsewhere."""
    return "scan" if jax.default_backend() == "tpu" else "compiled"


def offloaded_loss(spec: ChainSpec, cfg: OffloadConfig
                   ) -> Callable[[Any, Any], Any]:
    """The loss with its chain segment rerouted through the configured
    engine: the checkpointing executor (``engine="compiled"|"interpreted"``,
    via custom_vjp + io_callback) or the trace-native plan-driven scan
    (``engine="scan"``).  Differentiable; prelude/readout gradients flow via
    ordinary autodiff (stacked-layer cotangents scatter back into params
    through the prelude's vjp).

    The executor engines raise on a TPU.  ``io_callback`` runs its host
    callback with the CPU as the default device and the arguments placed
    there, so their segments would compile and run on the host CPU while
    the chip idles; dispatching the segments to the TPU from inside the
    callback deadlocks instead."""

    if cfg.engine == "scan":
        return _scan_loss(spec, cfg)
    if jax.default_backend() == "tpu":
        raise NotImplementedError(
            f"engine={cfg.engine!r} cannot run on a TPU: jax.io_callback "
            "runs the executor's segments on the host CPU, and dispatching "
            "them to the TPU from inside the callback deadlocks; use "
            "engine='scan', which keeps the chain in one XLA program with "
            "segment boundaries in pinned host memory")

    def loss(params, batch):
        carry0, xs = spec.prelude(params, batch)
        treedef, mask = diff_mask(xs)
        inner = _resolve_inner(spec, cfg, params, carry0, xs, batch)
        static = _Static(spec=spec, cfg=cfg, xs_treedef=treedef,
                         xs_mask=mask, inner=inner)
        carry_n = _chain(static, params, carry0, xs, batch)
        if inner is not None and inner.head_chunks > 1:
            if spec.readout_chunked is None:
                raise ValueError(
                    f"2D plan wants head_chunks={inner.head_chunks} but "
                    f"chain {spec.name!r} has no readout_chunked")
            return spec.readout_chunked(params, carry_n, batch,
                                        inner.head_chunks)
        return spec.readout(params, carry_n, batch)

    return loss


def value_and_grad_offloaded(
    loss_fn,
    *,
    strategy: str = "multistage_async",
    interval: Optional[int] = None,
    slots: Optional[int] = None,
    storage: str = "ram",
    storage_dir: Optional[str] = None,
    l2_capacity_bytes: Optional[int] = None,
    backend: Optional[Any] = None,
    journal_dir: Optional[str] = None,
    resume: bool = False,
    journal_repair: bool = False,
    autotune: bool = True,
    tuner: Optional[at.AutoTuner] = None,
    fallback: bool = True,
    engine: Optional[str] = None,
    runner: str = "compiled",
    mesh: Optional[Any] = None,
    state_spec: Optional[Any] = None,
    step_memory_budget: Optional[int] = None,
    plan_2d: Optional[Tuple[int, int]] = None,
    offload_params: Optional[str] = None,
) -> Callable[[Any, Any], Tuple[Any, Any]]:
    """Drop-in ``jax.value_and_grad`` with multistage-offloaded backprop.

    ``loss_fn`` is a :class:`ChainSpec`, or a callable carrying one as a
    ``chain_spec`` attribute (the model factory attaches these).  A plain
    callable with no chain structure falls back to ``jax.value_and_grad``
    when ``fallback=True`` (with a warning), so call sites can pass whatever
    loss they have.

    Returns ``f(params, batch) -> (loss, grads)``.

    Keyword args: ``strategy`` is one of ``multistage_async`` (the paper:
    async Level-2 stores every ``I`` steps + prefetch, Revolve inside
    intervals), ``revolve`` (single-stage baseline) or ``conventional``
    (store everything); ``interval``/``slots`` pin the schedule, otherwise
    the autotuner measures ``T_A``/``T_T`` on first call and applies §3's
    ``I = ceil(T_T/T_A)``; ``storage`` picks the Level-2 backend
    (``"ram"``, ``"disk"``, ``"compressed"`` — int8-quantised boundary
    states, ~4x smaller at a bounded precision cost — or ``"tiered"``, a
    capacity-bounded fast tier over a disk slow tier).  ``l2_capacity_bytes``
    (required with ``storage="tiered"``) is the fast-tier budget: the
    Level-2 *store* never exceeds it — cold boundaries write-behind spill
    to disk in plan-aware (Belady) order and are promoted back ahead of
    need (the reverse sweep additionally holds up to ``prefetch_depth``
    boundary states in Level-1-bound transit staging, reported as
    ``last_stats().l2_staged_peak_bytes``) — and the autotuner probes
    *both* tiers, choosing ``I`` from
    the capacity-aware effective transfer time (a budget that forces
    spills yields a larger interval so the slow tier keeps up).

    ``backend=`` bypasses the storage kinds entirely and hands the
    transform a live, already-built Level-2 store — the multi-tenant
    serving path passes a ``NamespacedStorage`` view of ONE shared
    capacity-bounded ``TieredStorage`` here, so concurrent runs obey a
    common fast-tier budget and per-tenant quotas
    (``TieredStorage.set_quota``).  Mutually exclusive with
    ``storage``/``storage_dir``/``l2_capacity_bytes``; ``journal_dir``
    still composes on top (the WAL records the run's own keys, outside the
    shared namespace).  The shared store is never closed by run disposal.

    ``journal_dir`` makes the offloaded run *crash-consistent*: every
    Level-2 store/delete is write-ahead-logged (CRC + fsync) together
    with a plan cursor checkpointed at segment granularity, so a run
    killed mid-sweep (writer-thread death, OOM, preemption, truncated
    spill) can be resumed step-exactly with :func:`resume_offloaded` —
    replaying at most one interval of forward steps
    (``last_stats().replayed_advances``) and never re-reversing a
    completed segment.  Requires an executor engine
    (``"compiled"``/``"interpreted"``); storage failures surface as typed
    :class:`repro.core.faults.StorageFault` subclasses.

    ``engine`` selects how segments execute — all three drive the same
    ``SegmentPlan`` IR (``api.last_plan()``): ``"compiled"`` runs one
    jitted ``lax.scan``/checkpointed-vjp call per segment — O(n/I) host
    dispatches, compiled once per segment length; ``"interpreted"`` is the
    step-granular paper-faithful interpreter (O(n) dispatches, exact
    Revolve-optimal advance counts); ``"scan"`` stays entirely inside the
    XLA trace (one dispatch, boundaries offloaded to pinned host memory by
    the compiler where supported) and composes with ``jax.jit``,
    ``jax.vmap`` and mesh sharding.  The scan engine implements the
    ``multistage_async`` strategy with the XLA host backend only
    (``storage`` must stay ``"ram"``).  ``None`` picks
    :func:`default_engine`: ``"scan"`` on a TPU, where the two executor
    engines raise (see :func:`offloaded_loss`), ``"compiled"`` elsewhere.

    ``runner`` (compiled engine only) selects the per-segment kernel:
    ``"compiled"`` (default) is one jitted scan per segment with the
    boundary store issued from the host; ``"pallas"`` fuses the segment
    into a Pallas kernel that double-buffers the boundary-state DMA to
    host memory while the next chunk computes, and reverses segments with
    Echo-style in-kernel recompute.  It runs in interpret mode only
    (``REPRO_PALLAS_INTERPRET=1``): on a TPU it raises, because Mosaic
    cannot lower the kernels' chunk loops, and anywhere else it falls back
    to ``"compiled"`` with a one-line warning.  Gradients are
    bit-identical across runners on matching chunking (fp32).

    ``mesh`` (executor engines only) makes the offloaded run first-class
    on a multi-device mesh: chain inputs are committed to the mesh inside
    the gradient's host callbacks, every jitted segment op runs SPMD, and
    each device streams *its shard* of every boundary state to its own
    Level-2 stream (a per-device ``ShardedStorage`` fan-out behind the
    configured ``storage`` kind — composes with the journal and the
    tiered budget).  ``state_spec`` pins the boundary carry's
    ``PartitionSpec`` (fitted per-leaf to each shape); by default the
    carry's leading axis shards over the mesh's data axes when divisible,
    else replicates.  The autotuner measures the per-stream *and*
    single-stream transfer times and applies §3 to the smaller — the
    sharded interval never exceeds the single-device one
    (``last_tune().t_t_global``, ``.shard_streams``); per-stream traffic
    shows up in ``last_stats().l2_stream_bytes``.

    ``step_memory_budget`` (compiled engine + runner only) bounds the
    *per-step* reverse peak in bytes and makes the planner two-dimensional:
    when one chain step's own activations exceed the budget — deep per-step
    layer stacks, or a logits/loss head larger than everything else — the
    step itself is chunked.  The chain's real per-layer byte profile
    (``analysis.jaxpr_cost``) feeds a Gruslys-style DP
    (``perfmodel.choose_2d_plan``) that picks the fewest rematted layer
    sub-ranges (and logits/loss head chunks) that fit; the outer interval
    stays the tuner's §3 optimum.  Needs a chain with a layer
    decomposition (``ChainSpec.layer_body``/``n_layers`` — the model
    factories attach these); an infeasible budget raises, naming the
    smallest feasible one.  ``plan_2d=(layer_chunks, head_chunks)`` pins
    the inner axis instead.  ``api.last_plan()`` reports both axes
    (``plan.inner``), ``api.last_stats()`` the per-axis recompute and peak
    counters (``inner_recomputed_layers``, ``inner_peak_bytes``).
    Gradients stay bit-identical to the 1D plan's (fp32): inner chunking
    only changes *when* interiors are recomputed, never what is computed.

    ``offload_params="moe_experts"`` (compiled engine + runner only)
    generalises the Level-2 lane from boundary states to *parameters*:
    the chain's stacked per-(layer, expert) MoE weights
    (``w_gate``/``w_up``/``w_down``) move to the Level-2 store up front
    and stream back one blob per (layer, expert) with plan-aware prefetch
    one segment ahead of both sweeps, so resident parameter memory drops
    from ``O(n_layers * n_experts)`` to ``O(I * n_experts)``.  Boundary
    states and expert blobs share one capacity budget under
    ``storage="tiered"`` (one merged ``ResourceAccessPlan`` drives Belady
    eviction for both).  Gradients are bit-identical to the non-streamed
    path; prefetch traffic shows up as ``last_stats().param_prefetches``
    / ``param_fetch_stalls`` / ``param_bytes_moved``.

    Example — a tiny chain, pinned schedule, gradients match autodiff:

    >>> import jax, jax.numpy as jnp, numpy as np
    >>> from repro import api
    >>> spec = api.ChainSpec(
    ...     prelude=lambda params, batch: (jnp.float32(0.0), batch["xs"]),
    ...     body=lambda params, c, x, batch: c + params["w"] * jnp.tanh(x + c),
    ...     readout=lambda params, c, batch: c,
    ...     name="doc-vg-chain")
    >>> params = {"w": jnp.float32(0.5)}
    >>> batch = {"xs": jnp.linspace(-1.0, 1.0, 8)}
    >>> vg = api.value_and_grad_offloaded(spec, interval=4, slots=2)
    >>> loss, grads = vg(params, batch)
    >>> ref_loss, ref_grads = jax.value_and_grad(spec.loss_fn())(params, batch)
    >>> bool(np.allclose(loss, ref_loss))
    True
    >>> bool(np.allclose(grads["w"], ref_grads["w"]))
    True
    """
    if backend is not None:
        # ``backend=`` hands the transform a live, already-built Level-2
        # store (typically a NamespacedStorage view of one shared
        # capacity-bounded TieredStorage, so concurrent runs obey a common
        # budget and per-tenant quotas).  It replaces the storage kind
        # entirely; a journal_dir still composes on top.
        if storage != "ram" or storage_dir is not None or \
                l2_capacity_bytes is not None:
            raise ValueError(
                "pass either backend= (an already-built Level-2 store) or "
                "the storage=/storage_dir=/l2_capacity_bytes= kind knobs, "
                "not both")
        storage = "shared"
    spec = _as_chain_spec(loss_fn)
    if spec is None:
        if not fallback:
            raise TypeError(
                "loss_fn has no chain decomposition (expected a ChainSpec "
                "or a callable with a .chain_spec attribute)")
        warnings.warn(
            "value_and_grad_offloaded: loss has no chain decomposition; "
            "falling back to jax.value_and_grad (no offloading)",
            stacklevel=2)
        return jax.value_and_grad(loss_fn)

    if engine is None:
        engine = default_engine()
    cfg = OffloadConfig(strategy=strategy, interval=interval, slots=slots,
                        storage=storage, storage_dir=storage_dir,
                        l2_capacity_bytes=l2_capacity_bytes,
                        journal_dir=journal_dir, resume=resume,
                        journal_repair=journal_repair,
                        autotune=autotune, tuner_id=_register_tuner(tuner),
                        backend_id=_register_shared_backend(backend),
                        engine=engine, runner=runner,
                        mesh=mesh, state_spec=state_spec,
                        step_memory_budget=step_memory_budget,
                        plan_2d=tuple(plan_2d) if plan_2d is not None
                        else None,
                        offload_params=offload_params)
    vg = jax.value_and_grad(offloaded_loss(spec, cfg))
    vg.chain_spec = spec
    vg.offload_config = cfg
    # keep the weak registry entries alive for as long as the transform is
    vg.tuner = tuner
    vg.backend = backend
    return vg


def resume_offloaded(
    loss_fn,
    params,
    batch,
    *,
    journal_dir: str,
    repair: bool = False,
    **opts,
) -> Tuple[Any, Any]:
    """Resume a crashed offloaded gradient from its write-ahead journal.

    Recovers the journal in ``journal_dir`` (written by a
    ``value_and_grad_offloaded(..., journal_dir=...)`` transform that was
    killed mid-run) and finishes the gradient step-exactly: a
    forward-phase crash replays from the last durable boundary (at most
    one interval of steps — ``last_stats().replayed_advances``), a
    reverse-phase crash restarts mid-sweep from the journaled adjoint
    cursor without re-reversing any completed segment.  ``params`` and
    ``batch`` must be the ones the crashed run used — determinism is what
    makes the resumed gradient bit-identical to the fault-free one.

    Returns ``(loss, grads)`` exactly like the transform would have.  If
    the journal holds nothing resumable (no cursor, or a run that already
    completed), the gradient is simply recomputed from scratch — still
    journaled, so the call is safe to use as the generic retry path.

    ``repair=True`` truncates a CRC-damaged journal back to its last good
    record instead of raising
    :class:`~repro.core.faults.ChecksumError` (resume then replays from
    whatever precedes the damage).  Remaining keyword options are those
    of :func:`value_and_grad_offloaded` — pass the same ``storage``/
    ``engine`` configuration the crashed run used.
    """
    vg = value_and_grad_offloaded(loss_fn, journal_dir=journal_dir,
                                  resume=True, journal_repair=repair,
                                  **opts)
    return vg(params, batch)


def checkpointed_bptt(
    body: Callable[[Any, Any, Any], Tuple[Any, Any]],
    **opts,
) -> Callable[[Any, Any, Any], Tuple[Any, Any]]:
    """BPTT through ``lax.scan``-style chains with offloaded checkpointing.

    ``body(params, carry, x) -> (carry, loss_k)`` is one chain step (an RNN
    time step, a transformer layer, ...).  Returns
    ``bptt(params, carry0, xs) -> (total_loss, grads)`` where ``total_loss``
    is the sum of the per-step losses and ``grads`` matches ``params`` —
    the multistage counterpart of
    ``jax.value_and_grad(lambda p: sum-of-scan(body))``.

    Keyword options are those of :func:`value_and_grad_offloaded`.

    >>> import jax, jax.numpy as jnp, numpy as np
    >>> from repro import api
    >>> def body(params, carry, x):
    ...     carry = jnp.tanh(carry + params * x)
    ...     return carry, carry ** 2
    >>> bptt = api.checkpointed_bptt(body, interval=4, slots=2)
    >>> loss, grad = bptt(jnp.float32(0.3), jnp.float32(0.0),
    ...                   jnp.linspace(0.0, 1.0, 8))
    >>> def ref(p):
    ...     def step(c, x):
    ...         c, out = body(p, c, x)
    ...         return c, out
    ...     _, outs = jax.lax.scan(step, jnp.float32(0.0),
    ...                            jnp.linspace(0.0, 1.0, 8))
    ...     return jnp.sum(outs)
    >>> ref_loss, ref_grad = jax.value_and_grad(ref)(jnp.float32(0.3))
    >>> bool(np.allclose(loss, ref_loss)), bool(np.allclose(grad, ref_grad))
    (True, True)
    """

    def prelude(params, batch):
        carry0, xs = batch
        return (carry0, jnp.zeros((), jnp.float32)), xs

    def chain_body(params, c, x, batch):
        carry, acc = c
        carry, loss_k = body(params, carry, x)
        return carry, acc + jnp.sum(loss_k).astype(jnp.float32)

    def readout(params, c, batch):
        return c[1]

    spec = ChainSpec(prelude, chain_body, readout,
                     name=getattr(body, "__name__", "bptt"))
    vg = value_and_grad_offloaded(spec, **opts)

    def bptt(params, carry0, xs):
        return vg(params, (carry0, xs))

    bptt.chain_spec = spec
    return bptt
