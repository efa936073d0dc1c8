"""Smoke run of the offloaded backward pass on a TPU, through the entry points
a user calls: ``repro.api.value_and_grad_offloaded`` and
``repro.train.make_train_step``, jitted the way ``repro.launch.train`` jits
it.  Weights and data are random, made from ``--seed``.

    python chip_smoke.py                 # one TPU chip: phases a and b
    python chip_smoke.py --chips 4       # four TPU chips: the data-parallel path
    JAX_PLATFORMS=cpu python chip_smoke.py --smoke   # rehearsal, any device

Phases (one chip):

a. The paper's chain: lstm-paper at its published widths (embed 64,
   hidden 256, vocab 96) over time, B=64, T=4096.  The multistage_async
   gradient through the scan engine, under ``jax.jit`` and eagerly, against
   ``jax.value_and_grad`` of the plain scan, with fp32 matmuls at "highest"
   precision.  Under jit the compiled program must place the segment
   boundaries in host memory.  On a TPU the executor engines
   (``engine="compiled"``/``"interpreted"``) must refuse to build; off a
   TPU they run and are held to the same tolerance.
b. mamba2-370m at published widths (48 layers, d_model 1024, d_state 128,
   headdim 64, vocab 50280), B=2, T=2048: three jitted train steps from one
   init with plain autodiff and with the scan engine (interval 2).  Every
   loss must be finite; the first-step losses and grad norms must agree.

``--chips 4`` runs only mamba2-370m through the scan engine at global batch
8, data-parallel over four chips, against the same two steps on one chip
as four microbatches of 2; outputs must span all four devices.

``--smoke`` runs the same phases at the ``smoke=True`` configs and small
shapes on whatever device JAX has.  Without it the script refuses to run
unless JAX's first device is a TPU.  Each phase runs under its own
wall-clock limit: a phase that overruns ends the process with its name.
Every line but the last is information, never a claim; the last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# Phase a: fp32 at "highest" matmul precision.  The loss is a sum of T
# per-step means, and the segmented sweep adds the parameter gradient up
# in another order than the plain scan's transpose.
LSTM_LOSS_RTOL = 1e-4
LSTM_GRAD_RTOL = 1e-4          # max |g - g_ref| / max |g_ref|, per leaf
# Phase b and --chips 4: the model computes in bf16, and remat or a
# microbatch split lets XLA fuse and round differently.
MAMBA_LOSS_RTOL = 5e-3
MAMBA_GNORM_RTOL = 5e-2
# Each scan-engine segment keeps its layers' activations for the reverse
# (about 1 GB per mamba2 layer at B=2, T=2048 on a v5e), so the interval
# bounds the step's footprint: 2 leaves headroom on a 16 GB chip.
MAMBA_INTERVAL = 2

PHASE_LIMIT_S = {"a_lstm_paper": 420, "b_mamba2": 600, "dp4_mamba2": 900}


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def report(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)


@contextlib.contextmanager
def phase(name: str):
    """Run a phase under its wall-clock limit.  An overrun ends the process
    (exit 124) naming the phase; an exception propagates after naming it."""
    limit = PHASE_LIMIT_S[name]
    done = threading.Event()

    def watchdog():
        if not done.wait(limit):
            print(f"phase {name}: FAILED, still running after {limit} s",
                  file=sys.stderr, flush=True)
            os._exit(124)

    threading.Thread(target=watchdog, daemon=True).start()
    t0 = time.perf_counter()
    try:
        yield
    except BaseException:
        print(f"phase {name}: FAILED", file=sys.stderr, flush=True)
        raise
    finally:
        done.set()
    report(phase=name, status="ok", seconds=time.perf_counter() - t0)


def _peak_bytes(jax):
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def _timed(jax, fn, *args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def _rel_err(jax, got, want) -> float:
    import numpy as np

    worst = 0.0
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        scale = max(float(np.max(np.abs(b))), 1e-30)
        worst = max(worst, float(np.max(np.abs(a - b))) / scale)
    return worst


def _stats(api) -> dict:
    st = api.last_stats()
    if st is None:
        return {"l2_stores": None, "peak_l1_states": None}
    return {"l2_stores": st.l2_stores, "peak_l1_states": st.peak_l1_states}


def phase_lstm_paper(jax, args) -> None:
    import jax.numpy as jnp

    from repro import api
    from repro.configs import get_config
    from repro.configs.base import ShapeSpec
    from repro.data import SyntheticDataset
    from repro.models.lstm import forward_loss, init_lstm, train_chain

    cfg = get_config("lstm-paper", smoke=args.smoke)
    B, T = (4, 64) if args.smoke else (64, 4096)
    params = init_lstm(jax.random.PRNGKey(args.seed), cfg.vocab,
                       cfg.d_model, cfg.d_ff)
    ds = SyntheticDataset(cfg, ShapeSpec("chip-smoke", T, B, "train"),
                          seed=args.seed)
    batch = {"tokens": jnp.asarray(ds.batch(0)["tokens"])}
    on_tpu = jax.devices()[0].platform == "tpu"
    spec = train_chain(cfg)

    ref = jax.jit(jax.value_and_grad(
        lambda p, b: forward_loss(p, b["tokens"])))
    (v_ref, g_ref), t_ref = _timed(jax, ref, params, batch)
    report(phase="a_lstm_paper", case="reference", B=B, T=T,
           loss=float(v_ref), first_call_s=t_ref)

    def vg_of(engine):
        return api.value_and_grad_offloaded(
            spec, strategy="multistage_async", engine=engine)

    def compare(case, v, g, extra):
        loss_err = abs(float(v) - float(v_ref)) / abs(float(v_ref))
        grad_err = _rel_err(jax, g, g_ref)
        tune = api.last_tune()
        report(phase="a_lstm_paper", case=case, loss_rel_err=loss_err,
               grad_rel_err=grad_err,
               interval=None if tune is None else tune.interval,
               peak_bytes_in_use=_peak_bytes(jax), **extra)
        check(loss_err <= LSTM_LOSS_RTOL,
              f"{case}: loss rel err {loss_err} > {LSTM_LOSS_RTOL}")
        check(grad_err <= LSTM_GRAD_RTOL,
              f"{case}: grad rel err {grad_err} > {LSTM_GRAD_RTOL}")

    # scan engine under jit: compile ahead so the HLO can be checked
    t0 = time.perf_counter()
    compiled = jax.jit(vg_of("scan")).lower(params, batch).compile()
    t_compile = time.perf_counter() - t0
    hlo = compiled.as_text()
    host_boundaries = "S(5)" in hlo          # the TPU host memory space
    (v, g), t_first = _timed(jax, compiled, params, batch)
    (v, g), t_step = _timed(jax, compiled, params, batch)
    compare("scan/jit", v, g, dict(
        _stats(api), host_boundaries=host_boundaries,
        copy_starts=hlo.count("copy-start"),
        host_temp_bytes=compiled.memory_analysis().host_temp_size_in_bytes,
        compile_s=t_compile, first_call_s=t_first, step_s=t_step))
    if on_tpu:
        check(host_boundaries, "scan/jit: no host-placed boundaries in HLO")

    vg = vg_of("scan")
    (v, g), t_first = _timed(jax, vg, params, batch)
    (v, g), t_step = _timed(jax, vg, params, batch)
    compare("scan/eager", v, g, dict(_stats(api), first_call_s=t_first,
                                     step_s=t_step))

    for engine in ("compiled", "interpreted"):
        if on_tpu:
            try:
                vg_of(engine)
            except NotImplementedError as e:
                check("engine='scan'" in str(e),
                      f"{engine}: refusal does not name engine='scan'")
                report(phase="a_lstm_paper", case=f"{engine}",
                       refused_on_tpu=True)
                continue
            raise SmokeFailure(f"engine={engine!r} built on a TPU")
        for mode in ("eager", "jit"):
            vg = vg_of(engine)
            fn = jax.jit(vg) if mode == "jit" else vg
            (v, g), t_first = _timed(jax, fn, params, batch)
            (v, g), t_step = _timed(jax, fn, params, batch)
            stats = _stats(api)
            compare(f"{engine}/{mode}", v, g, dict(
                stats, first_call_s=t_first, step_s=t_step))
            check((stats["l2_stores"] or 0) > 0,
                  f"{engine}/{mode}: no Level-2 stores")


def _mamba2_run(jax, args, cfg, batches, *, phase_name, label,
                grad_accum=1, strategy=None, mesh=None):
    """Jitted train steps from the seed's init, as ``repro.launch.train``
    runs them; returns per-step (loss, grad_norm) and the final state."""
    import numpy as np

    from repro.models import get_model
    from repro.optim import adamw, cosine_schedule
    from repro.train import init_train_state, make_train_step

    model = get_model(cfg)
    steps = len(batches)
    opt = adamw(cosine_schedule(3e-4, warmup=max(2, steps // 10),
                                total=steps))
    opts = {}
    if strategy is not None:
        opts = dict(strategy=strategy, engine="scan",
                    offload_opts={"interval": MAMBA_INTERVAL})
    raw = make_train_step(model, opt, grad_accum=grad_accum, **opts)
    state = init_train_state(model, opt, jax.random.PRNGKey(args.seed))
    if mesh is not None:
        from repro.distributed.sharding import batch_shardings

        sh = batch_shardings(mesh, batches[0])
        batches = [jax.device_put(b, sh) for b in batches]
    jit_step = jax.jit(raw, donate_argnums=(0,))
    t0 = time.perf_counter()
    compiled = jit_step.lower(state, batches[0]).compile()
    t_compile = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    out, times = [], []
    for b in batches:
        (state, metrics), dt = _timed(jax, compiled, state, b)
        out.append((float(metrics["loss"]), float(metrics["grad_norm"])))
        times.append(dt)
    report(phase=phase_name, case=label, losses=[o[0] for o in out],
           grad_norms=[o[1] for o in out], compile_s=t_compile,
           step_s=times, argument_bytes=mem.argument_size_in_bytes,
           temp_bytes=mem.temp_size_in_bytes,
           peak_bytes_in_use=_peak_bytes(jax))
    check(all(np.isfinite(v) for o in out for v in o),
          f"{label}: non-finite loss or grad norm {out}")
    return out, state, metrics


def _agree(label, got, want) -> None:
    (l1, g1), (l0, g0) = got, want
    check(abs(l1 - l0) <= MAMBA_LOSS_RTOL * abs(l0),
          f"{label}: first-step loss {l1} vs {l0} (rtol {MAMBA_LOSS_RTOL})")
    check(abs(g1 - g0) <= MAMBA_GNORM_RTOL * abs(g0),
          f"{label}: first-step grad norm {g1} vs {g0} "
          f"(rtol {MAMBA_GNORM_RTOL})")


def _mamba2_batches(args, cfg, B, T, steps):
    from repro.configs.base import ShapeSpec
    from repro.data import SyntheticDataset

    ds = SyntheticDataset(cfg, ShapeSpec("chip-smoke", T, B, "train"),
                          seed=args.seed)
    return [ds.batch(s) for s in range(steps)]


def phase_mamba2(jax, args) -> None:
    from repro.configs import get_config

    cfg = get_config("mamba2-370m", smoke=args.smoke)
    T = 32 if args.smoke else 2048
    batches = _mamba2_batches(args, cfg, 2, T, 3)
    run = functools.partial(_mamba2_run, jax, args, cfg, batches,
                            phase_name="b_mamba2")
    plain, _, _ = run(label="plain")
    scan, _, _ = run(label="scan", strategy="multistage_async")
    _agree("scan vs plain", scan[0], plain[0])


def phase_dp4_mamba2(jax, args) -> None:
    from repro.configs import get_config
    from repro.launch.mesh import make_local_mesh

    check(jax.device_count() >= 4,
          f"--chips 4 needs 4 devices, JAX has {jax.device_count()}")
    cfg = get_config("mamba2-370m", smoke=args.smoke)
    T = 32 if args.smoke else 2048
    batches = _mamba2_batches(args, cfg, 8, T, 2)
    run = functools.partial(_mamba2_run, jax, args, cfg, batches,
                            phase_name="dp4_mamba2",
                            strategy="multistage_async")
    one, _, _ = run(label="1chip_accum4", grad_accum=4)
    dp, state, metrics = run(label="4chip_dp", mesh=make_local_mesh())
    spans = {len(leaf.sharding.device_set) for leaf in
             jax.tree_util.tree_leaves((state, metrics))}
    report(phase="dp4_mamba2", case="4chip_dp",
           output_device_counts=sorted(spans))
    check(spans == {4}, f"4-chip outputs span {spans} devices, not 4")
    for k, (got, want) in enumerate(zip(dp, one)):
        _agree(f"4-chip vs 1-chip step {k}", got, want)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="smoke configs and small shapes on any device")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the data-parallel four-chip path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.launch.perf_env import configure_compile_cache

    configure_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.smoke:
        print(f"chip_smoke: JAX found no TPU (first device: {dev.platform}); "
              "pass --smoke to rehearse elsewhere", file=sys.stderr)
        return 2
    report(device=dev.platform, kind=dev.device_kind,
           count=len(jax.devices()), jax=jax.__version__, smoke=args.smoke)

    prev = jax.config.jax_default_matmul_precision
    if args.chips == 4:
        with phase("dp4_mamba2"):
            phase_dp4_mamba2(jax, args)
    else:
        # set globally, not in a thread-local context: the executor
        # engines run their segments on a callback thread
        jax.config.update("jax_default_matmul_precision", "highest")
        try:
            with phase("a_lstm_paper"):
                phase_lstm_paper(jax, args)
        finally:
            jax.config.update("jax_default_matmul_precision", prev)
        with phase("b_mamba2"):
            phase_mamba2(jax, args)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
