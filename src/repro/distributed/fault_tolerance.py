"""Fault tolerance: straggler watchdog, elastic re-meshing, retry wrapper.

At 1000+ nodes the failure model is: (a) slow hosts (stragglers) that drag
every synchronous step, (b) lost hosts that kill the job.  The framework's
answers: per-step EMA timing with outlier detection (a), and
checkpoint/restart onto a *rebuilt* mesh from the surviving device count with
automatic state resharding (b) — combined with the async checkpointing in
``repro.ckpt`` the recovery path is restore-latest + elastic_mesh +
reshard_state.
"""
from __future__ import annotations

import logging
import time
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

log = logging.getLogger("repro.ft")


@dataclass
class StragglerWatchdog:
    """EMA step-time tracker; flags steps slower than ``threshold`` x EMA.

    On a real pod each host feeds its own step time; here the single-process
    variant flags pathological steps (GC pauses, host interference) so the
    training loop can log and, on repeated hits, trigger a checkpoint.
    """

    alpha: float = 0.1
    threshold: float = 2.0
    warmup: int = 5
    ema: float = 0.0
    count: int = 0
    slow_steps: List[Tuple[int, float]] = field(default_factory=list)
    _t0: Optional[float] = None

    def start(self) -> None:
        self._t0 = time.perf_counter()

    def stop(self, step: int) -> bool:
        """Returns True if this step was a straggler."""
        assert self._t0 is not None
        dt = time.perf_counter() - self._t0
        self._t0 = None
        self.count += 1
        if self.count <= self.warmup:
            self.ema = dt if self.ema == 0 else (
                self.alpha * dt + (1 - self.alpha) * self.ema)
            return False
        slow = dt > self.threshold * self.ema
        if slow:
            self.slow_steps.append((step, dt))
            log.warning("straggler: step %d took %.3fs (ema %.3fs)",
                        step, dt, self.ema)
        else:
            self.ema = self.alpha * dt + (1 - self.alpha) * self.ema
        return slow


def elastic_mesh(n_alive: int, *, model_parallelism: int = 16,
                 axis_names: Tuple[str, ...] = ("data", "model"),
                 devices: Optional[list] = None) -> Mesh:
    """Largest (data, model) mesh buildable from the surviving devices.

    Keeps the model axis fixed (TP degree is a property of the sharded
    weights' layout) and shrinks the data axis — dropping at most
    ``model_parallelism - 1`` devices.
    """
    devices = devices if devices is not None else jax.devices()
    n_alive = min(n_alive, len(devices))
    if n_alive < 1:
        raise RuntimeError(f"cannot build a mesh from {n_alive} devices")
    tp = max(1, min(model_parallelism, n_alive))
    dp = n_alive // tp
    if dp < 1:
        raise RuntimeError(f"cannot build a mesh from {n_alive} devices")
    use = devices[: dp * tp]
    import numpy as np
    arr = np.array(use).reshape(dp, tp)
    return Mesh(arr, axis_names)


def reshard_state(state: Any, new_mesh: Mesh, pspec_fn: Callable) -> Any:
    """Re-place a restored state pytree onto a new mesh (elastic restart)."""

    def one(path, leaf):
        spec = pspec_fn(path, leaf)
        fixed = tuple(a if (a is None or a in new_mesh.axis_names) else None
                      for a in spec)
        return jax.device_put(leaf, NamedSharding(new_mesh, P(*fixed)))

    return jax.tree_util.tree_map_with_path(one, state)


# Status prefixes of runtime errors that a retry repeats exactly: the
# program does not fit in device memory, or the compiler cannot build it.
_PERMANENT_STATUS = ("RESOURCE_EXHAUSTED", "UNIMPLEMENTED")


def is_permanent(err: BaseException) -> bool:
    """Whether re-running the step would fail the same way: out of device
    memory, or a lowering/compile error (``NotImplementedError`` from a
    lowering rule, or an XLA/Mosaic compile failure)."""
    if isinstance(err, NotImplementedError):
        return True
    if not isinstance(err, jax.errors.JaxRuntimeError):
        return False
    msg = str(err)
    return msg.startswith(_PERMANENT_STATUS) or "failed to compile" in msg


def with_retries(fn: Callable, *, retries: int = 3,
                 on_retry: Optional[Callable[[int, Exception], None]] = None,
                 recover: Optional[Callable[[int, Exception], None]] = None):
    """Retry wrapper for steps that may die to transient runtime errors
    (preemption, DMA timeout, Level-2 storage faults — the typed
    ``repro.core.faults.StorageFault`` hierarchy subclasses RuntimeError
    precisely so it lands here).  Deterministic data + checkpointed state
    make the retried step bit-identical.

    ``recover(attempt, err)`` runs *before* each re-attempt (after
    ``on_retry``, which is notification-only): hook the job's recovery
    path into it — e.g. restore the train state from
    ``ckpt.CheckpointManager`` and let the offloaded-gradient journal
    (``OffloadConfig(journal_dir=...)``) resume the crashed sweep from its
    last durable boundary, so the retried step reproduces the gradient it
    would have produced, bit for bit.  An exception from ``recover``
    aborts the retry loop (a broken recovery path must not silently spin).
    Failures a retry would only repeat (:func:`is_permanent`) raise at
    once: re-running a step that donated its buffers would otherwise bury
    the real error under "Array has been deleted".
    """

    def wrapped(*a, **kw):
        for attempt in range(retries + 1):
            try:
                return fn(*a, **kw)
            except (RuntimeError, jax.errors.JaxRuntimeError) as e:
                if attempt == retries or is_permanent(e):
                    raise
                if on_retry is not None:
                    on_retry(attempt, e)
                log.warning("retry %d after %s", attempt + 1, e)
                if recover is not None:
                    recover(attempt, e)

    return wrapped
