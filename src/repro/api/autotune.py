"""Schedule auto-tuning from the paper's §3 performance model.

The multistage strategy has two knobs: the Level-2 store interval ``I`` and
the Level-1 Revolve slot count ``s``.  §3 gives the optimum directly:
``I = ceil(T_T / T_A)`` — the smallest interval at which the asynchronous
Level-2 transfers keep up with compute, so the forward pass never stalls and
the recompute factor stays at the constant ``R(I, s)``.

Two ways to obtain ``(T_A, T_T)``:

* **measure** — time the jitted forward step and a Level-2 store of the
  boundary state on the live engine (done on the first call of an offloaded
  gradient function, then cached per ``(model, seq-len, hardware)``); a
  capacity-bounded tiered backend is probed per tier and ``I`` comes from
  the *effective* transfer time (``perfmodel.choose_tiered_interval``);
* **roofline** — derive them from compiled-HLO roofline terms via
  ``repro.core.perfmodel.times_from_roofline`` (the dry-run path; no
  execution needed).

The measured interval is snapped with ``snap_interval`` onto a nearby
divisor of the chain length when one exists — never below the optimum,
which is the *minimum* no-stall interval (even segments mean one
compiled/trace segment variant instead of two — uneven tails are otherwise
first-class in the ``SegmentPlan`` IR), and the result is cached so
subsequent steps pay nothing.  Every engine shares the cache; the engine is
part of the cached name (``"<spec>:compiled"`` / ``":interpreted"`` /
``":scan"``) because each engine's ``T_A``/``T_T`` probes differ.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import SingleDeviceSharding

from repro.core import offload as ofl
from repro.core.perfmodel import (HardwareSpec, StepTimes,
                                  choose_interval_with_params,
                                  choose_sharded_interval,
                                  choose_tiered_interval,
                                  effective_transfer_time, hardware_for,
                                  optimal_interval, times_from_roofline)
from repro.core.storage import TieredStorage, tree_bytes


@dataclasses.dataclass(frozen=True)
class TuneResult:
    """A chosen schedule plus the measurements behind it."""

    interval: int
    slots: int
    t_a: float            # forward time of one chain step (s)
    t_t: float            # Level-2 transfer time of one boundary state (s)
    state_bytes: int
    n: int
    source: str           # "measured" | "roofline" | "manual"
    # Two-tier (capacity-bounded) Level 2 only: the slow tier's per-state
    # transfer time and the fast-tier budget behind the chosen interval.
    t_t_slow: float = 0.0
    capacity_bytes: Optional[int] = None
    # Sharded Level 2 only (``ShardedStorage`` fan-out): the measured
    # single-stream transfer time of the whole (gathered) state, the
    # number of per-device streams behind the fan-out ``t_t``, and the
    # per-mesh-axis single-stream times ``((axis, T_T), ...)`` — what the
    # transfer would cost if the state were sharded along that axis alone.
    t_t_global: float = 0.0
    shard_streams: int = 0
    t_t_axes: Tuple = ()
    # Parameter streaming (``offload_params=``) only: measured Level-2
    # read-back time of one chain step's streamed parameter blobs (s).
    t_t_param: float = 0.0

    @property
    def never_stalls(self) -> bool:
        """The §3 no-stall predicate: one boundary transfer (``T_T``)
        hides completely behind its interval's compute (``I * T_A``)."""
        return self.t_t <= self.interval * self.t_a


def snap_interval(n: int, target: int) -> int:
    """Snap the §3 optimum onto the chain: prefer a nearby divisor of ``n``
    (even segments — one compiled/trace segment variant instead of two), but
    never *below* the optimum — ``I = ceil(T_T / T_A)`` is the minimum
    no-stall interval, so snapping down re-enters the stall regime the
    tuner exists to avoid.  The smallest divisor of ``n`` in
    ``[target, 2*target]`` wins; with none in range (prime-ish ``n``) the
    target itself is kept and the plan simply ends in a shorter tail
    segment (uneven tails are first-class in the
    :class:`~repro.core.schedule.SegmentPlan` IR)."""
    target = max(1, min(target, n))
    hi = min(n, 2 * target)
    for i in range(target, hi + 1):
        if n % i == 0:
            return i
    return target


def _aval_dtype(leaf: Any) -> np.dtype:
    dt = getattr(leaf, "dtype", None)
    return dt if dt is not None else np.asarray(leaf).dtype


def _aval_bytes(tree: Any) -> int:
    """``tree_bytes`` from shapes/dtypes alone — works on tracers."""
    return int(sum(
        int(np.prod(np.shape(leaf), dtype=np.int64))
        * np.dtype(_aval_dtype(leaf)).itemsize
        for leaf in jax.tree_util.tree_leaves(tree)))


def _zeros_of(tree: Any) -> Any:
    """Concrete zero-filled stand-in for a (possibly traced) pytree."""
    return jax.tree_util.tree_map(
        lambda leaf: jnp.zeros(np.shape(leaf), _aval_dtype(leaf)), tree)


def default_slots(interval: int, l1_budget_states: int = 16) -> int:
    """Level-1 slots for Revolve inside one interval.  ``interval <= s``
    degenerates to store-all within the segment (R(I, s) == 1, the paper's
    preferred operating point); larger intervals get the full budget."""
    return max(1, min(interval, l1_budget_states))


class AutoTuner:
    """Measures (T_A, T_T) once and caches the chosen schedule.

    Cache key: ``(name, n, state_bytes, level2-kind, backend)`` — the
    model/chain identity, sequence length, boundary-state size, Level-2
    medium and compute hardware, i.e. everything the §3 optimum depends on.
    """

    def __init__(self, l1_budget_states: int = 16, repeats: int = 3):
        """``l1_budget_states`` caps Level-1 slots ``s``; ``repeats`` is
        the best-of-N count each timing probe uses."""
        self.l1_budget_states = l1_budget_states
        self.repeats = repeats
        self._cache: Dict[Tuple, TuneResult] = {}
        self._lock = threading.Lock()

    # ------------------------------------------------------------------ cache
    def _key(self, name: str, n: int, state_bytes: int,
             level2: str) -> Tuple:
        # T_T depends on the Level-2 medium, so the backend kind is part of
        # the identity — a RAM-tuned interval must never be reused for disk.
        return (name, n, state_bytes, level2, jax.default_backend())

    def lookup(self, name: str, n: int, state_bytes: int,
               level2: str) -> Optional[TuneResult]:
        """Return the cached schedule for this identity, or ``None``."""
        with self._lock:
            return self._cache.get(self._key(name, n, state_bytes, level2))

    def store(self, name: str, n: int, state_bytes: int, level2: str,
              result: TuneResult) -> TuneResult:
        """Cache ``result`` under this identity and return it."""
        with self._lock:
            self._cache[self._key(name, n, state_bytes, level2)] = result
        return result

    def clear(self) -> None:
        """Drop every cached schedule (tests; hardware changes)."""
        with self._lock:
            self._cache.clear()

    # ---------------------------------------------------------------- measure
    def _time(self, fn: Callable[[], Any]) -> float:
        fn()  # warmup (jit compile / first-touch)
        t0 = time.perf_counter()
        for _ in range(self.repeats):
            fn()
        return (time.perf_counter() - t0) / self.repeats

    def measure(self, name: str, *,
                forward_step: Optional[Callable[[Any, int], Any]] = None,
                state0: Any, n: int, backend: Any,
                forward_segment: Optional[Callable[[Any], Any]] = None,
                segment_len: int = 1,
                store_state0: Any = None,
                mesh: Any = None,
                param_stream_bytes: int = 0) -> TuneResult:
        """Time the forward compute and one Level-2 store; derive ``I`` per §3.

        Two probes, matching the two execution engines:

        * ``forward_step(state, k) -> state`` — the step-granular interpreter
          op; one timed call gives ``T_A`` directly (but includes the per-step
          Python dispatch overhead).
        * ``forward_segment(state) -> state`` over ``segment_len`` steps — a
          compiled ``advance_segment`` probe; ``T_A`` is the segment time
          divided by its length, i.e. the *amortised* per-step time the
          segment-compiled engine actually achieves.  This is the honest
          input to ``I = ceil(T_T/T_A)``: the compiled engine's smaller
          ``T_A`` correctly yields a larger interval.

        ``backend`` is the Level-2 storage backend the run will use (its
        put/delete pair is what we time).  A capacity-bounded
        ``TieredStorage`` backend gets a second probe of its *slow* tier,
        and the interval comes from the capacity-aware effective transfer
        time (``perfmodel.choose_tiered_interval``): if the boundaries at
        the fast-tier optimum would overflow the budget, ``I`` grows until
        either they fit or the slow tier keeps up — §3's rule applied to
        the medium that actually rate-limits the stores.

        ``store_state0`` (optional) substitutes the value fed to the
        store probes while ``state0`` still drives the compute probe and
        the cache identity.  The fused Pallas runner passes a
        host-resident copy here: its kernel has already DMA'd the
        boundary off the device by the time the store is issued, so the
        honest ``T_T`` is the un-hidden residual (serialisation +
        backend write), not a device→host transfer the kernel hides.

        A sharded backend (``ShardedStorage`` fan-out, possibly behind a
        journal) is probed twice more: once through a *single* inner
        stream with the gathered global state (``t_t_global``, the
        single-device baseline), and — when ``mesh`` is given — once per
        mesh axis with the state's leading dim cut to ``1/k``.  The
        fan-out ``T_T`` is clamped by the global time before §3's rule
        (``perfmodel.choose_sharded_interval``), so the sharded interval
        never exceeds the single-device one.

        ``param_stream_bytes`` (parameter streaming, ``offload_params=``)
        is the byte size of one chain step's streamed parameter blobs.
        When non-zero, a third probe measures their Level-2 *read-back*
        time (``t_t_param`` — the traffic the prefetch lane adds behind
        every segment) and the interval is widened per
        ``perfmodel.choose_interval_with_params`` so the boundary store
        still hides behind the compute left over after the reads.
        """
        state_bytes = tree_bytes(state0)
        level2 = type(backend).__name__
        if isinstance(backend, TieredStorage):
            # the optimum depends on the budget: key it into the cache
            level2 = f"{level2}[{backend.capacity_bytes}]"
        if param_stream_bytes:
            # added per-segment read traffic changes the optimum
            level2 = f"pstream[{param_stream_bytes}]:{level2}"
        streams = int(getattr(backend, "shard_streams", 0) or 0)
        if streams > 1:
            # the per-stream payload (hence T_T, hence I) depends on the
            # fan-out width: key it into the cache identity
            level2 = f"sharded[{streams}]:{level2}"
        cached = self.lookup(name, n, state_bytes, level2)
        if cached is not None:
            return cached

        if forward_segment is not None:
            def one_probe():
                jax.block_until_ready(forward_segment(state0))

            t_a = self._time(one_probe) / max(1, segment_len)
        else:
            if forward_step is None:
                raise TypeError("measure() needs forward_step or "
                                "forward_segment")

            def one_probe():
                jax.block_until_ready(forward_step(state0, 0))

            t_a = self._time(one_probe)

        tune_key = ("__autotune__", name)
        store_val = state0 if store_state0 is None else store_state0

        def one_store():
            backend.put(tune_key, store_val)

        t_t = self._time(one_store)
        backend.delete(tune_key)

        t_t_global = 0.0
        t_t_axes: Tuple = ()
        if streams > 1:
            inners = getattr(backend, "inners", None)
            if inners:
                # single-stream baseline: the whole (gathered) state
                # through one inner backend — what a 1-device run pays.
                host_global = jax.tree_util.tree_map(
                    lambda a: np.asarray(a), store_val)
                gkey = ("__autotune_global__", name)

                def one_global():
                    inners[0].put(gkey, host_global)

                t_t_global = self._time(one_global)
                inners[0].delete(gkey)
                if mesh is not None:
                    axes = []
                    for axis, k in dict(mesh.shape).items():
                        k = int(k)
                        if k <= 1:
                            axes.append((axis, t_t_global))
                            continue

                        def cut(a, k=k):
                            nd = getattr(a, "ndim", 0)
                            if nd and a.shape[0] % k == 0 and a.shape[0] >= k:
                                return a[: a.shape[0] // k]
                            return a

                        sliced = jax.tree_util.tree_map(cut, host_global)

                        def one_axis():
                            inners[0].put(gkey, sliced)

                        axes.append((axis, self._time(one_axis)))
                        inners[0].delete(gkey)
                    t_t_axes = tuple(axes)

        t_t_slow = 0.0
        capacity = None
        if isinstance(backend, TieredStorage):
            capacity = backend.capacity_bytes

            def one_slow_store():
                backend.slow.put(tune_key, store_val)

            t_t_slow = self._time(one_slow_store)
            backend.slow.delete(tune_key)
            if state_bytes > capacity:
                # the fast probe itself spilled: it measured the slow path,
                # so recover the fast tier's own time as the cheaper of the
                # two (everything bypasses anyway — t_t_eff is slow)
                t_t = min(t_t, t_t_slow)
            target = choose_tiered_interval(
                n, state_bytes, capacity, t_a, t_t, t_t_slow)
        elif streams > 1 and t_t_global > 0.0:
            # clamp: the fan-out streams only ever shrink the per-stream
            # payload, so a noisy-slow fan-out probe must not pick a
            # larger interval than the single-device baseline would
            t_t = min(t_t, t_t_global)
            target = choose_sharded_interval(t_a, t_t, t_t_global)
        else:
            target = optimal_interval(t_t, t_a)

        t_t_param = 0.0
        if param_stream_bytes:
            # probe the read-back path the prefetch lane uses: put one
            # step's worth of blob bytes, then time the non-promoting
            # peek (falling back to get on backends without one)
            blob = np.zeros(max(1, param_stream_bytes // 4), np.float32)
            pkey = ("__autotune_param__", name)
            backend.put(pkey, blob)
            read = getattr(backend, "peek", None) or backend.get

            def one_read():
                read(pkey)

            t_t_param = self._time(one_read)
            backend.delete(pkey)
            # widen, never shrink: T_P eats into the compute window that
            # hides the boundary store, so the tiered/sharded minimum
            # stays a floor
            target = max(target, choose_interval_with_params(
                t_a, t_t, t_t_param))

        interval = snap_interval(n, target)
        if capacity is not None and interval < target:
            # choose_tiered_interval's result is a *minimum viable*
            # interval (boundaries fit the budget, or the slow tier keeps
            # up); snapping onto a smaller divisor of n can re-enter the
            # spill-and-stall regime.  Keep the snap only if the effective
            # transfer time still hides behind the segment's compute.
            t_t_eff = effective_transfer_time(n, interval, state_bytes,
                                              capacity, t_t, t_t_slow)
            if t_t_eff > interval * t_a:
                interval = target
        slots = default_slots(interval, self.l1_budget_states)
        return self.store(name, n, state_bytes, level2, TuneResult(
            interval=interval, slots=slots, t_a=t_a, t_t=t_t,
            state_bytes=state_bytes, n=n, source="measured",
            t_t_slow=t_t_slow, capacity_bytes=capacity,
            t_t_global=t_t_global, shard_streams=streams,
            t_t_axes=t_t_axes, t_t_param=t_t_param))

    # ------------------------------------------------------- scan engine
    def measure_scan(self, name: str, *, body: Callable[..., Any],
                     params: Any, carry0: Any, xs: Any, batch: Any,
                     n: int, segment_len: int = 32) -> TuneResult:
        """Schedule for the trace-native scan engine.

        The scan engine resolves its schedule at *trace* time — ``params`` /
        ``carry0`` / ``xs`` / ``batch`` may be tracers, so every probe runs
        on zero-filled stand-ins built from shapes/dtypes alone (constant
        creation is eager even inside a trace).  Two probes:

        * ``T_A`` — the amortised per-step time of one jitted ``lax.scan``
          segment of ``segment_len`` steps, i.e. the compute rate the scan
          engine's compiled segments actually achieve;
        * ``T_T`` — a measured device->host ``device_put`` of the boundary
          state when the backend lowers host memory spaces (the XLA
          copy-start/copy-done path the offload policy compiles to),
          otherwise the §3 roofline estimate ``state_bytes / d2h_bw`` from
          the hardware table.

        Results share the cross-engine tuner cache: the key's Level-2 kind
        is ``"xla_host"`` / ``"roofline-<hw>"``, and callers put the engine
        in ``name`` (the front-end passes ``"<spec>:scan"``), so a
        scan-tuned interval is never reused for the threaded backends.
        """
        state_bytes = _aval_bytes(carry0)
        offloads = ofl.host_offload_supported()
        device = jax.devices()[0]
        level2 = "xla_host" if offloads else \
            f"roofline-{hardware_for(device).name}"
        cached = self.lookup(name, n, state_bytes, level2)
        if cached is not None:
            return cached

        segment_len = max(1, min(segment_len, n))

        @jax.jit
        def probe(p, c, xs_, b):
            def step(c_, x):
                return body(p, c_, x, b), None

            c, _ = lax.scan(step, c, xs_)
            return c

        # The caller may be tracing (the scan engine resolves its schedule
        # inside jit); without this the stand-ins and probes would be
        # staged into that trace and the timings would measure tracing.
        with jax.ensure_compile_time_eval():
            zp, zc, zb = (_zeros_of(params), _zeros_of(carry0),
                          _zeros_of(batch))
            zxs = jax.tree_util.tree_map(
                lambda leaf: jnp.zeros(
                    (segment_len,) + tuple(np.shape(leaf)[1:]),
                    _aval_dtype(leaf)),
                xs)
            t_a = self._time(
                lambda: jax.block_until_ready(probe(zp, zc, zxs, zb))
            ) / segment_len

            if offloads:
                host = SingleDeviceSharding(device, memory_kind=ofl.HOST)

                def one_store():
                    jax.block_until_ready(jax.device_put(zc, host))

                t_t = self._time(one_store)
            else:
                t_t = state_bytes / hardware_for(device).d2h_bw

        interval = snap_interval(n, optimal_interval(t_t, t_a))
        slots = default_slots(interval, self.l1_budget_states)
        return self.store(name, n, state_bytes, level2, TuneResult(
            interval=interval, slots=slots, t_a=t_a, t_t=t_t,
            state_bytes=state_bytes, n=n, source="measured"))

    # --------------------------------------------------------------- roofline
    def from_roofline(self, name: str, *, n: int, step_flops: float,
                      step_hbm_bytes: float, state_bytes: int,
                      hw: HardwareSpec) -> TuneResult:
        """Analytic path: derive the schedule from compiled-HLO roofline
        terms (see ``analysis.roofline`` / ``launch.dryrun``) without running
        a step — used when planning runs on hardware we are not on."""
        level2 = f"roofline-{hw.name}"
        cached = self.lookup(name, n, state_bytes, level2)
        if cached is not None:
            return cached
        st: StepTimes = times_from_roofline(step_flops, step_hbm_bytes,
                                            state_bytes, hw)
        interval = snap_interval(n, st.interval)
        slots = default_slots(interval, self.l1_budget_states)
        return self.store(name, n, state_bytes, level2, TuneResult(
            interval=interval, slots=slots, t_a=st.t_a, t_t=st.t_t,
            state_bytes=state_bytes, n=n, source="roofline"))

    def plan_2d(self, tune: TuneResult, *, n: int, state_bytes: float,
                layer_bytes, budget_bytes: float, head_bytes: float = 0.0):
        """Pick 1D vs 2D for a measured schedule under a per-step budget.

        Couples a :meth:`measure` result (the outer axis: §3's interval
        from real ``T_A``/``T_T``) to the 2D overhead model
        (``perfmodel.choose_2d_plan``): ``layer_bytes``/``head_bytes`` are
        the chain's per-step byte profile
        (``analysis.jaxpr_cost.chain_step_byte_profile``), and the returned
        ``Plan2D`` carries the chosen inner axis (``.inner is None`` when
        time-only segmentation already fits), the modeled per-step peak and
        the combined recompute factor of both axes."""
        from repro.core import perfmodel as pm

        return pm.choose_2d_plan(
            n, t_a=tune.t_a, t_t=tune.t_t, s_l1=tune.slots,
            state_bytes=state_bytes, layer_bytes=layer_bytes,
            budget_bytes=budget_bytes, head_bytes=head_bytes,
            interval=tune.interval)

    def manual(self, name: str, *, n: int, interval: int,
               slots: Optional[int] = None,
               state_bytes: int = 0) -> TuneResult:
        """Build a pinned schedule with no measurement (``source="manual"``)
        — what the front-end uses when ``interval=``/``slots=`` are given.

        >>> AutoTuner().manual("doc", n=32, interval=8).interval
        8
        """
        return TuneResult(
            interval=max(1, min(interval, n)),
            slots=slots if slots is not None
            else default_slots(interval, self.l1_budget_states),
            t_a=0.0, t_t=0.0, state_bytes=state_bytes, n=n, source="manual")


# The process-wide tuner used by the front-end when none is supplied.
GLOBAL_TUNER = AutoTuner()
