"""One run of a training cell.

Set-up builds one object, the jitted training step of the system under
test (``repro.train.make_train_step``, jitted with the state donated as
``repro.launch.train`` jits it), with its state made on the device from the
seed.  It drives that step through its first three steps, on the feed the
window uses, and keeps what the check needs.  The window then drives the
same step for ``--seconds``: each step takes its batch from a prefetch
thread, calls the step and reads the loss back, as ``repro.launch.train``
does.  With ``--trace 1`` a few more steps run under the profiler.  Once
the window has closed, the device memory has been read and the program's
state is freed, the plain reference follows the first three steps.

A cell of more than one chip trains data-parallel on a mesh of its chips:
the state replicated, each batch split by rows, and the same step jitted,
so that the compiler puts in the gradient's all-reduce.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import os
import queue
import shutil
import statistics
import sys
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from harness import check, scopes, tokens
from harness import trace as tr
from harness.peaks import peaks_for
from harness.spec import Cell

# Traced steps: as many as fit in about TRACE_TARGET_S, 1 to 12.  The
# profiler keeps some 6.3 million device events (a TPU v5e), and one step of
# lstm-paper.bptt-64k makes about 4 million.
TRACE_TARGET_S = 2.0
FAULTS = ("state_unchanged", "half_batch", "grad_doubled",
          "exchange_left_out")


def log(**fields) -> None:
    print(" ".join(f"{k}={v}" for k, v in fields.items()), file=sys.stderr,
          flush=True)


@dataclasses.dataclass
class Shape:
    rows: int
    seq_len: int
    strategy: Optional[str]
    engine: Optional[str]
    interval: Optional[int]


def shape_of(cell: Cell, smoke: bool) -> Shape:
    t = dict(cell.traffic)
    if smoke:
        t.update(t.get("smoke", {}))
    return Shape(t["batch"], t["seq_len"], t.get("strategy"), t.get("engine"),
                 t.get("interval"))


def sizes_of(cell: Cell, smoke: bool) -> Dict[str, Any]:
    sizes = dict(cell.config["sizes"])
    if smoke:
        sizes.update(cell.config.get("smoke", {}))
    return sizes


def key_of(seed: int):
    """The init key: a seed of any size folds into a 32-bit key."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


class Feed:
    """Batches ``0, 1, ...`` of the seed, made and put on the device by one
    background thread, two ahead."""

    def __init__(self, make: Callable[[int], Any]):
        self._q: "queue.Queue" = queue.Queue(maxsize=2)
        self._stop = threading.Event()
        self._err: Optional[BaseException] = None
        self._t = threading.Thread(target=self._loop, args=(make, 0),
                                   daemon=True)
        self._t.start()

    def _loop(self, make, step):
        try:
            while not self._stop.is_set():
                item = make(step)
                while not self._stop.is_set():
                    try:
                        self._q.put(item, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                step += 1
        except BaseException as e:    # raised again by next()
            self._err = e
            self._stop.set()

    def next(self):
        while True:
            try:
                return self._q.get(timeout=0.1)
            except queue.Empty:
                if self._err is not None:
                    raise self._err
                if self._stop.is_set():
                    raise RuntimeError("feed stopped")

    def close(self) -> None:
        self._stop.set()
        self._t.join(timeout=30)
        if self._t.is_alive():
            raise RuntimeError("feed thread did not stop")
        while not self._q.empty():
            self._q.get_nowait()


class CompileCounter:
    """Counts XLA compilations (and persistent-cache hits and misses)."""

    def __init__(self):
        from jax import monitoring

        self.counts = {"compile_or_load": 0, "cache_hits": 0,
                       "cache_misses": 0}

        def on_duration(name, _secs, **_kw):
            if name.endswith("backend_compile_duration"):
                self.counts["compile_or_load"] += 1

        def on_event(name, **_kw):
            for k in ("cache_hits", "cache_misses"):
                if name.endswith(k):
                    self.counts[k] += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    def snapshot(self) -> Dict[str, int]:
        return dict(self.counts)


def faults_of(cell: Cell) -> Tuple[str, ...]:
    """The faults that ``cell``'s timed path can have: the exchange between
    chips only where there are chips to exchange between."""
    return tuple(f for f in FAULTS
                 if f != "exchange_left_out" or cell.chips > 1)


def _planted(fault: Optional[str], raw_step, mesh):
    """The timed step broken underneath, for the fault tests
    (``grad_doubled`` is planted in the optimizer instead)."""
    import jax
    from jax.sharding import PartitionSpec as P

    if fault == "state_unchanged":
        def step(state, batch):
            _, metrics = raw_step(state, batch)
            return state, metrics
        return step
    if fault == "half_batch":
        def step(state, batch):
            half = jax.tree_util.tree_map(lambda x: x[:x.shape[0] // 2],
                                          batch)
            return raw_step(state, half)
        return step
    if fault == "exchange_left_out":
        if mesh is None:
            raise ValueError("exchange_left_out needs a mesh of chips")

        # each chip steps on its own rows' gradient, with no mean across
        # chips; the state claims to be replicated and is not
        def step(state, batch):
            return jax.shard_map(raw_step, mesh=mesh,
                                 in_specs=(P(), P("data")), out_specs=P(),
                                 check_vma=False)(state, batch)
        return step
    if fault not in (None, "grad_doubled"):
        raise ValueError(f"unknown fault {fault!r} (have {FAULTS})")
    return raw_step


def _optimizer(cfg_opt: Dict[str, Any], fault: Optional[str]):
    import jax
    from repro.optim import adamw
    from repro.optim.optimizers import Optimizer

    if cfg_opt["kind"] != "adamw":
        raise ValueError(f"optimizer {cfg_opt['kind']!r}: only adamw is "
                         "followed by the reference")
    opt = adamw(cfg_opt["lr"], b1=cfg_opt["b1"], b2=cfg_opt["b2"],
                eps=cfg_opt["eps"], weight_decay=cfg_opt["weight_decay"],
                max_grad_norm=cfg_opt["max_grad_norm"])
    if fault != "grad_doubled":
        return opt

    def update(grads, state, params, step):
        flat, tree = jax.tree_util.tree_flatten(grads)
        flat[-1] = flat[-1] * 2        # one leaf's gradient, doubled
        return opt.update(jax.tree_util.tree_unflatten(tree, flat), state,
                          params, step)

    return Optimizer(init=opt.init, update=update)


class Trainer:
    """The compiled step of one cell, its state and its feed.

    Building it is the run's set-up: the state made on the device from the
    seed, the step compiled (from the persistent cache after a cell's first
    run) and driven through its first ``check.CHECK_STEPS`` steps, whose
    readings (``self.prog``) the check compares.  ``fault`` breaks the
    timed path underneath (``FAULTS``), for the fault tests only."""

    def __init__(self, cell: Cell, seed: int, *, smoke: bool = False,
                 fault: Optional[str] = None):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P

        from repro import api
        from repro.configs import get_config
        from repro.models import get_model
        from repro.train import init_train_state, make_train_step

        marks = [time.time()]
        self.counter = CompileCounter()
        self.shape = shape = shape_of(cell, smoke)
        self.sizes = sizes_of(cell, smoke)
        cfg_opt = cell.config["optimizer"]
        precision = cell.config.get("matmul_precision")
        if precision:
            jax.config.update("jax_default_matmul_precision", precision)
        model = get_model(get_config(cell.config["program"]["arch"],
                                     smoke=smoke))
        opt = _optimizer(cfg_opt, fault)
        self.devices = jax.devices()
        self.used = self.devices[:cell.chips]
        if cell.chips > 1:
            mesh = Mesh(np.array(self.used), ("data",))
            state_sh = NamedSharding(mesh, P())
            rows_sh = NamedSharding(mesh, P("data"))
        else:
            mesh = None
            state_sh = rows_sh = jax.sharding.SingleDeviceSharding(
                self.devices[0])
        raw = make_train_step(
            model, opt, strategy=shape.strategy, engine=shape.engine,
            offload_opts=({"interval": shape.interval}
                          if shape.interval is not None else None))
        raw = _planted(fault, raw, mesh)
        marks.append(time.time())
        vocab = self.sizes["vocab_size"]
        self.host_batch = lambda step: tokens.batch(
            seed, step, shape.rows, shape.seq_len, vocab)
        proto = self.host_batch(0)
        batch_sh = jax.tree_util.tree_map(lambda _: rows_sh, proto)

        self.key = key_of(seed)
        state = jax.jit(lambda k: init_train_state(model, opt, k),
                        out_shardings=state_sh)(self.key)
        jax.block_until_ready(state)
        marks.append(time.time())
        spec = jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            proto, batch_sh)
        lowered = jax.jit(raw, donate_argnums=(0,)).lower(state, spec)
        hits = self.counter.counts["cache_hits"]
        self.compiled = lowered.compile()
        self.step_from_cache = self.counter.counts["cache_hits"] > hits
        marks.append(time.time())
        self.mem = self.compiled.memory_analysis()
        self.hlo_text = self.compiled.as_text()
        self.copy_names = tr.host_copy_names(self.hlo_text)
        tune = api.last_tune() if shape.strategy else None
        self.interval = None if tune is None else tune.interval
        norms = check.leaf_norm_fn()
        change = jax.jit(lambda p, k: norms(jax.tree_util.tree_map(
            jnp.subtract, p, model.init(k))))

        self.losses: List[float] = []
        # each step's host seconds in fetch, dispatch and readout
        self.step_s: List[List[float]] = []
        self.feed = Feed(lambda step: jax.device_put(self.host_batch(step),
                                                     batch_sh))
        self.state = state
        try:
            self.step()
            # AdamW's first moment after one step is (1 - b1) g
            unscale = 1 / (1 - cfg_opt["b1"])
            m = self.state["opt"]["m"]
            grad_norms = {k: v * unscale
                          for k, v in check.to_host(norms(m)).items()}
            first_grad = check.host_leaves(m, unscale)
            for _ in range(check.CHECK_STEPS - 1):
                self.step()
            self.prog = check.Readings(
                list(self.losses), grad_norms,
                check.to_host(change(self.state["params"], self.key)),
                first_grad)
        except BaseException:
            self.feed.close()
            raise
        marks.append(time.time())
        # seconds of set-up spent in each part: the model, step and backend
        # made; the state made on the device; the step lowered (autotune
        # probes included) and compiled or loaded from the cache; the checked
        # steps and their readings
        self.setup_parts = dict(zip(
            ("model_and_backend", "init_state", "compile", "checked_steps"),
            (b - a for a, b in zip(marks, marks[1:]))))

    def step(self) -> None:
        import jax

        t = [time.time()]
        with jax.profiler.TraceAnnotation("bench.step"):
            with jax.profiler.TraceAnnotation("bench.fetch"):
                b = self.feed.next()
            t.append(time.time())
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                self.state, metrics = self.compiled(self.state, b)
            t.append(time.time())
            with jax.profiler.TraceAnnotation("bench.readout"):
                self.losses.append(float(metrics["loss"]))
            t.append(time.time())
        self.step_s.append([b - a for a, b in zip(t, t[1:])])

    def window(self, seconds: float):
        """Steps for ``seconds`` (at least one); returns their losses and
        the window's length."""
        n0 = len(self.losses)
        t0 = time.time()
        self.step()
        while time.time() - t0 < seconds:
            self.step()
        return self.losses[n0:], time.time() - t0

    def traced(self, n: int) -> Dict[str, Any]:
        """``n`` steps under the profiler, the trace reduced and deleted."""
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        tmp = tempfile.mkdtemp(prefix="bench-trace-")
        t0 = time.time()
        try:
            jax.profiler.start_trace(tmp, profiler_options=opts)
            try:
                for _ in range(n):
                    self.step()
            finally:
                jax.profiler.stop_trace()
            t1 = time.time()
            t = tr.load_xplane(tmp)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        t2 = time.time()
        if not t.device_ops:
            raise RuntimeError("the trace holds no device operations")
        lo, hi = tr.window_of(t)
        last_op = max(s + d for ops in t.device_ops.values()
                      for _, s, d in ops)
        out = {"trace": t, "steps": tr.steps_in(t),
               "window_s": (hi - lo) / 1e9,
               "busy_s": tr.mean_busy_ns(t) / 1e9,
               "top_ops": tr.top_ops(t), "idle_gaps": tr.idle_gaps(t),
               "name_ns": scopes.name_ns(t)}
        log(traced_steps=n, trace_s=t1 - t0, parse_s=t2 - t1,
            reduce_s=time.time() - t2,
            device_events={k: len(v) for k, v in t.device_ops.items()},
            last_op_before_window_end_s=(hi - last_op) / 1e9)
        return out

    def close(self) -> Optional[int]:
        """Stop the feed, read the device's peak memory, free the state."""
        import jax

        self.feed.close()
        peak = _peak_bytes(self.used)
        jax.tree_util.tree_map(lambda a: a.delete(), self.state)
        self.state = self.compiled = None
        gc.collect()
        return peak


def reference(cell: Cell, seed: int, *, smoke: bool = False,
              control: bool = False, rows: Optional[int] = None
              ) -> check.Readings:
    """The plain reference over the first steps of ``seed``'s batches,
    each step's rows split across the cell's chips."""
    import jax

    shape, sizes = shape_of(cell, smoke), sizes_of(cell, smoke)
    batches = [tokens.batch(seed, k, shape.rows, shape.seq_len,
                            sizes["vocab_size"])
               for k in range(check.CHECK_STEPS)]
    return check.reference_readings(cell.reference(), sizes, key_of(seed),
                                    batches, cell.config["optimizer"],
                                    control=control, rows=rows,
                                    devices=jax.devices()[:cell.chips])


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, smoke: bool = False, fault: Optional[str] = None,
        control: bool = False) -> Dict[str, Any]:
    """One run of ``cell``; returns the result line's object.

    ``smoke`` runs the configuration's small sizes on any device, ``fault``
    breaks the timed path, and ``control`` puts the reference, in the
    precision below the configuration's, in the program's place: all three
    for tests only."""
    t_built = time.time()
    s = Trainer(cell, seed, smoke=smoke, fault=fault)
    try:
        before = s.counter.snapshot()
        setup_s = time.time() - t_start
        first = len(s.step_s)
        in_window, window_s = s.window(seconds)
        after = s.counter.snapshot()
        step_s = window_s / len(in_window)
        summary = None
        if trace:
            summary = s.traced(max(1, min(12, int(
                TRACE_TARGET_S / step_s))))
    finally:
        peak = s.close()

    tc = time.time()
    ref = reference(cell, seed, smoke=smoke)
    prog = (reference(cell, seed, smoke=smoke, control=True) if control
            else s.prog)
    numbers = check.compare(prog, ref)
    check_s = time.time() - tc
    failed = sum(1 for x in in_window if not math.isfinite(x))
    limits = cell.smoke_limits if smoke else cell.limits
    correct = check.judge(numbers, limits) and failed == 0

    shape, mem = s.shape, s.mem
    tokens_per_s = len(in_window) * shape.rows * shape.seq_len / window_s
    dev = s.devices[0]
    device: Dict[str, Any] = {"platform": dev.platform,
                              "kind": dev.device_kind,
                              "count": len(s.devices),
                              "memory_peak_bytes": peak}
    if trace:
        op_names = scopes.op_names_from_hlo(s.hlo_text)
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        metrics = _per_layer(cell, {
            "tokens_per_s": tokens_per_s,
            "flops_per_token": cell.flops().flops_per_token(s.sizes),
            "chips": len(s.used),
            "peaks": (peaks_for(dev.device_kind) if dev.platform == "tpu"
                      else None),
            "memory": mem,
            "trace": summary,
            "host_copy_names": s.copy_names,
            "op_names": op_names,
            "opcodes": tr.opcodes_from_hlo(s.hlo_text),
            "sizes": s.sizes,
            "shape": dataclasses.asdict(shape),
        })
    else:
        metrics = {
            "tokens_per_s": {"value": tokens_per_s, "unit": "tokens/s"},
            "device_mem_gb": {"value": _device_bytes(mem) / 1e9,
                              "unit": "GB"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        metrics = {m.name: metrics[m.name] for m in cell.end_to_end}
    win_parts = s.step_s[first:first + len(in_window)]
    win_steps = [sum(p) for p in win_parts]
    slowest = sorted(range(len(win_steps)), key=lambda i: -win_steps[i])[:3]
    log(setup_s=setup_s, before_trainer_s=t_built - t_start,
        **{f"{k}_s": v for k, v in s.setup_parts.items()})
    log(window_steps=len(in_window), step_s=step_s,
        step_s_min=min(win_steps), step_s_median=statistics.median(win_steps),
        step_s_max=max(win_steps),
        slowest_steps_fetch_dispatch_readout={
            i: [round(x, 4) for x in win_parts[i]] for i in slowest},
        host_load_1m=os.getloadavg()[0],
        check_s=check_s, interval=s.interval, setup_compiles=before,
        window_compiles_or_loads=(after["compile_or_load"]
                                  - before["compile_or_load"]))
    log(compiler_device_bytes=_device_bytes(mem),
        argument_bytes=mem.argument_size_in_bytes,
        output_bytes=mem.output_size_in_bytes,
        alias_bytes=mem.alias_size_in_bytes,
        temp_bytes=mem.temp_size_in_bytes,
        host_bytes=_host_bytes(mem),
        allocator_peak_bytes_in_use=peak)
    log(program_losses=prog.losses, reference_losses=ref.losses)
    if trace:
        parts = scopes.part_ms(summary["trace"], op_names,
                               summary["name_ns"])
        log(step_from_cache=s.step_from_cache,
            hlo_ops_in_scope={sc: sum(1 for n in op_names.values()
                                      if scopes.in_scope(n, sc))
                              for sc in scopes.SCOPES},
            parts_busy_ms=parts["busy_ms"],
            parts_unscoped_ms=parts["unscoped_ms"])
    out: Dict[str, Any] = {"correct": bool(correct),
                           "attempted": len(in_window), "failed": failed,
                           "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = {"device_ops": summary["top_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    for k, v in numbers.items():
        if k not in limits:
            log(not_compared=k, value=v["value"], at=v["at"])
    log(update_gap_leaves_left_out=numbers["update_gap"]["left_out"])
    out["check"] = {k: {"value": numbers[k]["value"], "limit": lim}
                    for k, lim in limits.items()}
    for k, lim in limits.items():
        log(check=k, value=numbers[k]["value"], limit=lim,
            at=numbers[k]["at"])
    return out


def _per_layer(cell: Cell, ctx: Dict[str, Any]) -> Dict[str, Any]:
    out = {}
    for m in cell.per_layer:
        value = m.reader(cell.bench_dir).read(ctx)
        if value is not None:
            out[m.name] = {"value": value, "unit": m.unit}
    return out


def _device_bytes(mem) -> int:
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)


def _host_bytes(mem) -> int:
    return (mem.host_argument_size_in_bytes + mem.host_output_size_in_bytes
            + mem.host_temp_size_in_bytes - mem.host_alias_size_in_bytes)


def _peak_bytes(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None
