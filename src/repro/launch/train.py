"""Training launcher.

Runs real steps on whatever devices exist (CPU smoke runs, or a TPU slice),
with the full production loop: background-prefetched deterministic data,
straggler watchdog, periodic asynchronous checkpoints, auto-resume from the
latest checkpoint (``--resume STEP`` pins an exact step and refuses to
substitute another), optional elastic re-meshing on restart, and
retry-wrapped steps whose recovery path spans both failure layers:
model state from the checkpoint store, and — with ``--journal-dir`` —
crash-consistent Level-2 boundary states for the offloaded backward pass,
so a killed step restarts with bit-identical gradients.

Offloaded-backprop strategies ride the same flags the API exposes: pass
``--strategy multistage_async`` (plus ``--engine``/``--interval``/``--slots``,
and ``--storage``/``--l2-capacity`` to bound the Level-2 host-RAM footprint
with the tiered RAM-over-disk backend) to route the backward pass through
the planner-driven engines.  ``--step-memory-budget BYTES`` caps one step's
Level-1 activations: when they exceed the cap the planner switches to a 2D
(time x layer) plan, chunking the per-step layer stack and loss head so the
chunk peak fits (infeasible budgets fail fast, naming the smallest feasible
one).  With
``--engine scan`` the whole train step stays one XLA computation, so on a
multi-device host the launcher jits it over a data-parallel mesh with
sharded batches (the sharded step executes the identical ``SegmentPlan``
the single-host engines use).

Examples::

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-4b --smoke \
        --steps 20
    PYTHONPATH=src python -m repro.launch.train --arch mamba2-370m --smoke \
        --steps 50 --ckpt-dir /tmp/ck --ckpt-every 10
    XLA_FLAGS=--xla_force_host_platform_device_count=2 \
        PYTHONPATH=src python -m repro.launch.train --arch lstm-paper \
        --smoke --steps 8 --strategy multistage_async --engine scan
    PYTHONPATH=src python -m repro.launch.train --arch lstm-paper --smoke \
        --steps 8 --strategy multistage_async --l2-capacity 1000000
    PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b --smoke \
        --steps 4 --strategy multistage_async --step-memory-budget 2000000
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import SMOKE_SHAPE, get_config
from repro.configs.base import ShapeSpec
from repro.data import Prefetcher, SyntheticDataset
from repro.distributed.fault_tolerance import (StragglerWatchdog,
                                               with_retries)
from repro.ckpt import CheckpointManager
from repro.models import get_model
from repro.optim import adamw, cosine_schedule
from repro.train import init_train_state, make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--log-every", type=int, default=1)
    ap.add_argument("--policy", default=None,
                    help="remat/offload policy override")
    ap.add_argument("--strategy", default=None,
                    choices=("multistage_async", "revolve", "conventional"),
                    help="offloaded-backprop strategy (None: plain autodiff)")
    ap.add_argument("--engine", default=None,
                    choices=("compiled", "interpreted", "scan"),
                    help="execution engine behind --strategy (default: scan on "
                         "a TPU, compiled elsewhere)")
    ap.add_argument("--interval", type=int, default=None,
                    help="pin the Level-2 store interval I (None: autotune)")
    ap.add_argument("--slots", type=int, default=None,
                    help="pin the Level-1 snapshot budget s")
    ap.add_argument("--storage", default=None,
                    choices=("ram", "disk", "compressed", "tiered"),
                    help="Level-2 backend for the executor engines "
                         "(default ram; implied tiered by --l2-capacity)")
    ap.add_argument("--l2-capacity", type=int, default=None, metavar="BYTES",
                    help="fast-tier budget for storage=tiered: the Level-2 "
                         "store never exceeds this; cold boundaries spill "
                         "to disk and autotune sizes I from the effective "
                         "(capacity-aware) transfer time")
    ap.add_argument("--step-memory-budget", type=int, default=None,
                    metavar="BYTES",
                    help="per-step Level-1 activation budget: when one "
                         "step's activations exceed it, the planner adds "
                         "the inner (layer/head) axis — a 2D plan whose "
                         "chunking the Gruslys-style DP sizes from the "
                         "chain's measured byte profile "
                         "(requires --strategy multistage_async with "
                         "--engine compiled); an infeasible budget fails "
                         "fast naming the smallest feasible one")
    ap.add_argument("--offload-params", default=None, dest="offload_params",
                    choices=("moe_experts",),
                    help="stream these parameters through the Level-2 store "
                         "alongside boundary states: 'moe_experts' moves "
                         "the stacked per-(layer, expert) FFN weights off "
                         "the fast tier and prefetches each segment's blobs "
                         "one segment ahead (requires --strategy "
                         "multistage_async with --engine compiled; "
                         "incompatible with --journal-dir and "
                         "--sharded-offload)")
    ap.add_argument("--journal-dir", default=None, metavar="DIR",
                    help="write-ahead journal for the offloaded backward "
                         "pass: Level-2 boundary stores become "
                         "crash-consistent (CRC + fsync) and a killed step "
                         "restarts with bit-identical gradients; requires "
                         "--strategy multistage_async with an executor "
                         "engine")
    ap.add_argument("--resume", type=int, default=None, metavar="STEP",
                    dest="resume_step",
                    help="restore this exact checkpoint step instead of the "
                         "latest; raises (listing what exists) if the step "
                         "was never saved or has been garbage-collected")
    ap.add_argument("--sharded-offload", action="store_true",
                    help="multi-device executor engines: run the offloaded "
                         "chain SPMD on a local mesh and stream each "
                         "device's shard of every Level-2 boundary to its "
                         "own per-device stream (requires --strategy "
                         "multistage_async with --engine "
                         "compiled/interpreted)")
    ap.add_argument("--mesh-model", type=int, default=1, metavar="N",
                    help="model (tensor-parallel) axis size of the local "
                         "mesh (--sharded-offload); must divide the device "
                         "count, remainder goes to the data axis")
    ap.add_argument("--host-devices", type=int, default=None, metavar="N",
                    help="force N CPU devices (XLA_FLAGS "
                         "--xla_force_host_platform_device_count) for mesh "
                         "smoke runs; must be set before jax initialises, "
                         "i.e. only effective as a launcher flag")
    args = ap.parse_args(argv)

    # Overlap flags (latency-hiding scheduler, async collectives) and any
    # forced host device count must land in XLA_FLAGS before the first
    # backend init — do it before anything touches a jax device.
    from repro.launch.perf_env import (configure_compile_cache,
                                       configure_perf_env)

    configure_perf_env(host_device_count=args.host_devices)
    configure_compile_cache()

    if args.strategy is not None and args.engine != "scan":
        # The executor engines escape the jitted step via io_callback and
        # dispatch nested segment computations from the callback thread.
        # With XLA's async CPU dispatch the outer program occupies the
        # (nproc-sized) execution pool, so on few-core hosts the nested
        # dispatch starves and the step deadlocks; synchronous CPU
        # dispatch makes the nesting safe and costs nothing here (host
        # "transfers" are memcpys).  The flag is read once, when the CPU
        # client is created — it must be set before anything initialises a
        # backend (even ``jax.default_backend()`` would), so this cannot
        # be guarded on the detected platform; it is a no-op for
        # accelerator clients anyway.
        jax.config.update("jax_cpu_enable_async_dispatch", False)

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.policy:
        cfg = cfg.replace(remat_policy=args.policy)
    shape = ShapeSpec(
        "cli",
        args.seq_len or SMOKE_SHAPE.seq_len,
        args.batch or SMOKE_SHAPE.global_batch,
        "train")
    api = get_model(cfg)
    opt = adamw(cosine_schedule(args.lr, warmup=max(2, args.steps // 10),
                                total=args.steps))

    state = init_train_state(api, opt, jax.random.PRNGKey(0))
    start_step = 0
    cm = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    if args.resume_step is not None and cm is None:
        ap.error("--resume STEP needs --ckpt-dir (no checkpoint store to "
                 "restore from)")
    if cm is not None and (cm.all_steps() or args.resume_step is not None):
        # an explicit --resume STEP must hit exactly that step — restore()
        # raises (listing cm.all_steps()) when it was GC'd or never saved
        state, start_step = cm.restore(state, step=args.resume_step)
        print(f"[resume] restored step {start_step} from {args.ckpt_dir}")

    if args.strategy is None and (args.engine or args.interval is not None
                                  or args.slots is not None
                                  or args.storage is not None
                                  or args.l2_capacity is not None
                                  or args.journal_dir is not None
                                  or args.step_memory_budget is not None
                                  or args.offload_params is not None):
        ap.error("--engine/--interval/--slots/--storage/--l2-capacity/"
                 "--journal-dir/--step-memory-budget/--offload-params "
                 "configure an offloaded "
                 "strategy; pass --strategy as well")
    if args.strategy is not None and args.engine is None:
        # scan on a TPU (the executor engines cannot run there), else
        # compiled — resolved here so the mesh choice below sees it
        from repro.api import default_engine

        args.engine = default_engine()
    if args.offload_params is not None:
        if args.engine in ("scan", "interpreted"):
            ap.error("--offload-params streams parameter blobs through the "
                     "compiled engine's segment runner; drop --engine or "
                     "pass --engine compiled")
        if args.journal_dir is not None:
            ap.error("--offload-params keeps transient parameter blobs in "
                     "Level-2, which the write-ahead journal cannot "
                     "replay; drop --journal-dir")
        if args.sharded_offload:
            ap.error("--offload-params drives a single Level-2 parameter "
                     "lane; drop --sharded-offload")
        if args.storage == "compressed":
            ap.error("--offload-params reads blobs back uncompressed; use "
                     "--storage ram/disk/tiered")
    if args.step_memory_budget is not None \
            and args.engine in ("scan", "interpreted"):
        ap.error("--step-memory-budget selects 2D (time x layer) plans, "
                 "which execute in the compiled engine's segment runner; "
                 "drop --engine or pass --engine compiled")
    if args.journal_dir is not None and args.engine == "scan":
        ap.error("--journal-dir needs an executor engine "
                 "(compiled/interpreted); --engine scan runs entirely "
                 "inside XLA and cannot be journaled")
    if args.l2_capacity is not None and args.storage in (None, "tiered"):
        args.storage = "tiered"   # --l2-capacity implies the tiered backend
    elif args.l2_capacity is not None:
        ap.error(f"--l2-capacity needs --storage tiered "
                 f"(got --storage {args.storage})")
    if args.storage == "tiered" and args.l2_capacity is None:
        ap.error("--storage tiered needs --l2-capacity BYTES")
    offload_opts = {}
    if args.interval is not None:
        offload_opts["interval"] = args.interval
    if args.slots is not None:
        offload_opts["slots"] = args.slots
    if args.storage is not None:
        offload_opts["storage"] = args.storage
    if args.l2_capacity is not None:
        offload_opts["l2_capacity_bytes"] = args.l2_capacity
    if args.step_memory_budget is not None:
        offload_opts["step_memory_budget"] = args.step_memory_budget
    if args.offload_params is not None:
        offload_opts["offload_params"] = args.offload_params
    if args.journal_dir is not None:
        offload_opts["journal_dir"] = args.journal_dir
        # standing resume mode: every gradient call first consults the
        # journal — a clean epoch recovers to "nothing to do" (fresh run),
        # while a retry after a mid-sweep crash genuinely resumes from the
        # last durable boundary instead of redoing the O(n) forward
        offload_opts["resume"] = True

    # Multi-device placement.  Two sharded paths: the trace-native ones
    # (plain autodiff / --engine scan) jit the whole step over a
    # data-parallel mesh with sharded batches; --sharded-offload instead
    # hands the mesh to the executor engines, whose gradient callbacks
    # commit the chain to the mesh themselves and stream each device's
    # boundary shard to its own Level-2 stream (the outer jit stays
    # unsharded — the io_callback boundary is where SPMD begins).
    mesh = None
    sharded_offload = False
    if args.sharded_offload:
        if args.strategy != "multistage_async" or args.engine == "scan":
            ap.error("--sharded-offload shards the executor engines' "
                     "Level-2 streams; pass --strategy multistage_async "
                     "with --engine compiled/interpreted")
        from repro.launch.mesh import make_local_mesh

        mesh = make_local_mesh(model=args.mesh_model)
        offload_opts["mesh"] = mesh
        sharded_offload = True
        print(f"[mesh] sharded Level-2 offload over "
              f"{mesh.devices.size} device(s), axes {dict(mesh.shape)}")
    raw_step = make_train_step(api, opt, grad_accum=args.grad_accum,
                               strategy=args.strategy, engine=args.engine,
                               offload_opts=offload_opts or None)

    if mesh is None and jax.device_count() > 1 and (
            args.strategy is None or args.engine == "scan"):
        from repro.launch.mesh import make_local_mesh

        mesh = make_local_mesh()
        print(f"[mesh] data-parallel over {jax.device_count()} devices")
    elif mesh is None and jax.device_count() > 1:
        print(f"[mesh] {jax.device_count()} devices present but engine="
              f"{args.engine} escapes the trace; running "
              "single-device (use --engine scan to shard, or "
              "--sharded-offload for per-device Level-2 streams)")
    def _recover(attempt, err):
        # Two recovery layers.  In-process retry (here): the step re-runs
        # with the same state/batch, and with --journal-dir its
        # OffloadConfig carries resume=True, so the crashed sweep's
        # Level-2 journal is genuinely resumed from the last durable
        # boundary (not recomputed from t=0) — deterministic inputs make
        # the retried gradients bit-identical.  Process death: the next
        # launch auto-restores the newest async checkpoint (printed below
        # so the operator knows where a relaunch would land) and the
        # journal's input fingerprint guards against resuming a stale
        # sweep under the restored — possibly older — weights.
        print(f"[retry] attempt {attempt + 1} recovering after "
              f"{type(err).__name__}: {err}")
        if cm is not None and cm.all_steps():
            print(f"[retry] relaunch would restore step "
                  f"{cm.all_steps()[-1]} from {args.ckpt_dir}")
        if args.journal_dir is not None:
            print(f"[retry] offload journal at {args.journal_dir} resumes "
                  "the sweep from its last durable boundary")

    # Donation and in-process retry are incompatible: a failed jitted call
    # has already consumed its donated state buffers, so every re-attempt
    # would die on 'Array has been deleted' instead of resuming.  A
    # journaled run is exactly the one that wants the retry path to work,
    # so it keeps the state buffers alive (one extra state copy on
    # accelerators); unjournaled runs keep the donation.
    donate = () if args.journal_dir is not None else (0,)
    jit_step = jax.jit(raw_step, donate_argnums=donate)

    def run_step(state, batch):
        out = jit_step(state, batch)
        # join the computation *inside* the retry boundary: dispatch is
        # async, so a storage fault inside an io_callback would otherwise
        # only surface at the metrics readout, past with_retries
        jax.block_until_ready(out)
        return out

    step_fn = with_retries(run_step, recover=_recover)
    ds = SyntheticDataset(cfg, shape)
    it = Prefetcher((ds.batch(s) for s in range(start_step, args.steps)),
                    depth=2)
    wd = StragglerWatchdog()

    n_params = sum(p.size for p in jax.tree_util.tree_leaves(state["params"]))
    print(f"[train] arch={cfg.name} params={n_params/1e6:.2f}M "
          f"seq={shape.seq_len} batch={shape.global_batch} "
          f"steps={start_step}..{args.steps}")
    t0 = time.time()
    batch_sh = None
    for step, batch in zip(range(start_step, args.steps), it):
        wd.start()
        batch = jax.tree_util.tree_map(jnp.asarray, batch)
        if mesh is not None and not sharded_offload:
            if batch_sh is None:
                from repro.distributed.sharding import batch_shardings

                batch_sh = batch_shardings(mesh, batch)
            batch = jax.device_put(batch, batch_sh)
        state, metrics = step_fn(state, batch)
        loss = float(metrics["loss"])
        wd.stop(step)
        if step % args.log_every == 0:
            print(f"  step {step:5d} loss {loss:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f}")
        if cm is not None and (step + 1) % args.ckpt_every == 0:
            cm.save(state, step + 1)
    if cm is not None:
        cm.save(state, args.steps)
        cm.close()
    it.close()
    dt = time.time() - t0
    n = max(1, args.steps - start_step)
    print(f"[train] done: {n} steps in {dt:.1f}s "
          f"({dt/n*1e3:.0f} ms/step); stragglers={len(wd.slow_steps)}")
    return state


if __name__ == "__main__":
    main()
