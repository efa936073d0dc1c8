"""Split a cell's traced step into the program's named parts, on the chip.

    python3 bench/phases.py --workload mamba2-370m.offload-2k --seed 7

Builds the cell's step as a run does (``harness.train.Trainer``), drives
it untraced for ``--seconds``, then for ``--steps`` steps under the
profiler as a traced run does (``Trainer.traced``), and gives each instant
of the device's busy time to the innermost op running then and that op to
a part of the program by its ``op_name`` metadata (``harness.scopes``):
the segments' forward, recompute and backward sweeps, the optimizer, the
loss before and after the chain, and the SSD scans.  Standard error gets
each part's ms per step, the busy time left in no part, the idle gaps
inside loops with the part of their loop, the plan's counters
(``repro.api.last_plan()`` / ``last_tune()``), and the median step
untraced and under the profiler.  The last line of standard output holds
the same numbers as JSON.

The persistent compile cache is the one runs use
(``run_cell.configure_cache``), whose key holds the op metadata.
``--record PATH`` writes the reduced trace with the ``op_name`` of each op
in it (for the tests; use ``--smoke``).  The benchmark's own runs never
run this.
"""
import argparse
import json
import os
import statistics
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def log(**fields) -> None:
    print(" ".join(f"{k}={v}" for k, v in fields.items()), file=sys.stderr,
          flush=True)


def plan_counters() -> dict:
    """The offload plan of the step as traced: interval, segments,
    recomputed chain steps and Level-2 stores per step, boundary bytes."""
    from repro import api

    plan, tune = api.last_plan(), api.last_tune()
    if plan is None:
        return {}
    # the scan engine replays each segment once in the backward sweep, and
    # once more where the plan chunks a segment inside (nested remat)
    chunks = [plan.inner_chunk(s) for s in plan.segments]
    recomputed = sum(s.length * (2 if c is not None else 1)
                     for s, c in zip(plan.segments, chunks))
    out = {"chain_steps": plan.n, "interval": plan.interval,
           "segments": plan.num_segments, "s_l1": plan.s_l1,
           "inner_chunks": sorted({c for c in chunks if c is not None}),
           "recomputed_steps": recomputed,
           "recompute_ratio": recomputed / plan.n,
           "l2_stores": plan.num_segments}
    if tune is not None and tune.state_bytes:   # 0: a pinned interval
        out["boundary_bytes"] = tune.state_bytes * plan.num_segments
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0,
                    help="the untraced window")
    ap.add_argument("--steps", type=int, default=1,
                    help="traced steps (the profiler keeps about 6.29 "
                         "million device events: an lstm-paper step makes "
                         "4.2 million, so more cut its trace short)")
    ap.add_argument("--smoke", action="store_true",
                    help="the configuration's small sizes, on any device")
    ap.add_argument("--record", help="write the reduced trace and its ops' "
                                     "op_name here (JSON)")
    args = ap.parse_args(argv)

    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from run_cell import configure_cache

    configure_cache()
    import jax

    from harness import scopes as sc
    from harness.spec import load_cell
    from harness.train import Trainer

    cell = load_cell(ROOT, args.workload)
    if not args.smoke and (jax.devices()[0].platform != "tpu"
                           or len(jax.devices()) < cell.chips):
        print("phases: needs the cell's TPU chips", file=sys.stderr)
        return 2
    s = Trainer(cell, args.seed, smoke=args.smoke)
    try:
        op_names = sc.op_names_from_hlo(s.compiled.as_text())
        plan = plan_counters()
        first = len(s.step_s)
        s.window(args.seconds)
        untraced = [sum(p) for p in s.step_s[first:]]
        step_s = statistics.median(untraced)
        first = len(s.step_s)
        summary = s.traced(args.steps)
        traced = [sum(p) for p in s.step_s[first:]]
    finally:
        s.close()

    t = summary["trace"]
    t0 = time.time()
    in_trace = {name for ops in t.device_ops.values() for name, _, _ in ops}
    ns = summary["name_ns"]
    parts = sc.part_ms(t, op_names, ns)
    busy = parts.pop("busy_ms")
    unscoped = parts.pop("unscoped_ms")
    out = {
        "workload": args.workload, "seed": args.seed, "smoke": args.smoke,
        "device": jax.devices()[0].device_kind,
        "traced_steps": summary["steps"], "parts_ms": parts, "busy_ms": busy,
        "window_ms": summary["window_s"] * 1e3 / summary["steps"],
        "scoped_share": 1 - unscoped / busy if busy else None,
        "unscoped_ms": unscoped,
        "unscoped_ops": sc.unscoped_ops(t, op_names, ns=ns),
        "while_gaps": sc.while_gaps(summary["idle_gaps"], op_names),
        "top_ops": [[name, sec, sc.label(op_names.get(name))]
                    for name, sec in summary["top_ops"]],
        "plan": plan,
        "step_s_untraced_median": step_s,
        "step_s_traced_median": statistics.median(traced),
        "untraced_steps": len(untraced),
        "ops_in_trace": len(in_trace),
        "ops_named": sum(1 for k in in_trace if k in op_names),
    }
    for k, v in parts.items():
        log(part=k, ms_per_step=v)
    log(busy_ms_per_step=busy, unscoped_ms_per_step=unscoped,
        scoped_share=out["scoped_share"])
    for name, sec, lab in out["while_gaps"]:
        log(idle_gap=name, s=sec, part=lab)
    log(plan=json.dumps(plan))
    log(step_s_untraced_median=step_s,
        step_s_traced_median=out["step_s_traced_median"],
        reduce_s=time.time() - t0)
    if args.record:
        d = t.to_json()
        d["op_names"] = {k: op_names[k] for k in sorted(in_trace)
                         if k in op_names}
        with open(args.record, "w") as f:
            json.dump(d, f, separators=(",", ":"))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
