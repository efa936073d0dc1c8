"""Read the numbers that set a cell's limits, on the chip, at the cell's size.

    python3 bench/calibrate.py --workload mamba2-370m.offload-2k \
        --seeds 12 --faulted-seeds 3

For each of ``--seeds`` seeds it builds the cell's step as a run does,
drives its first steps and compares them with the reference: the lower
readings.  For the first ``--faulted-seeds`` of them it also reads the
control (the reference in the precision below the configuration's, in the
program's place) and each planted fault that the cell's timed path can
have (``harness.train.faults_of``): the upper readings.  ``--witness N``
reads, on the first N seeds, the reference that rounds what the system
stores in bfloat16 (where the configuration's reference has that mode)
against the fp32 reference, and prints each leaf's ``grad_err`` beside the
program's on the same seed.  A state left unchanged reads 1 on ``grad_gap`` and
``update_gap`` by construction and is printed without a run.  Each reading
is a JSON line on standard output; the last line sums them up per number:
the largest sound reading, and the smallest reading of the control and of
each fault.  The benchmark's own runs never run this.
"""
import time

T_START = time.time()

import argparse            # noqa: E402
import json                # noqa: E402
import os                  # noqa: E402
import sys                 # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
FIRST_SEED = 3_000_000_000


def leaf_errors(got, ref):
    """Each leaf's ``grad_err``: the norm of the first gradients'
    difference over the larger of the leaf's and the median leaf's
    reference norm."""
    import numpy as np

    median = float(np.median(list(ref.grad_norms.values())))
    return {k: float(np.linalg.norm((got.first_grad[k] - v).ravel()))
            / max(ref.grad_norms[k], median, 1e-30)
            for k, v in ref.first_grad.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--faulted-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=FIRST_SEED)
    ap.add_argument("--witness", type=int, default=0,
                    help="seeds on which to read the bfloat16-storage "
                         "reference too")
    ap.add_argument("--smoke", action="store_true",
                    help="the configuration's small sizes, on any device")
    args = ap.parse_args(argv)

    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from run_cell import configure_cache

    configure_cache()
    import jax

    from harness import check
    from harness.spec import load_cell
    from harness.train import Trainer, faults_of, reference

    cell = load_cell(ROOT, args.workload)
    if not args.smoke and (jax.devices()[0].platform != "tpu"
                           or len(jax.devices()) < cell.chips):
        print("calibrate: needs the cell's TPU chips", file=sys.stderr)
        return 2
    readings = {}

    def note(kind, seed, numbers, seconds):
        values = {k: v["value"] for k, v in numbers.items()}
        readings.setdefault(kind, []).append(values)
        print(json.dumps({"kind": kind, "seed": seed, "seconds": seconds,
                          **{k: [v["value"], v["at"]]
                             for k, v in numbers.items()}}), flush=True)

    for i in range(args.seeds):
        seed = args.first_seed + i
        t0 = time.time()
        s = Trainer(cell, seed, smoke=args.smoke)
        prog = s.prog
        s.close()
        t1 = time.time()
        ref = reference(cell, seed, smoke=args.smoke)
        note("program", seed, check.compare(prog, ref), [t1 - t0,
                                                        time.time() - t1])
        if i < args.witness:
            t0 = time.time()
            wit = reference(cell, seed, smoke=args.smoke, control="bfloat16")
            note("witness_bf16", seed, check.compare(wit, ref),
                 time.time() - t0)
            print(json.dumps({"grad_err_per_leaf": seed, "leaves": {
                k: [e, leaf_errors(wit, ref)[k]]
                for k, e in leaf_errors(prog, ref).items()}}), flush=True)
        if i >= args.faulted_seeds:
            continue
        t0 = time.time()
        ctl = reference(cell, seed, smoke=args.smoke, control=True)
        note("control", seed, check.compare(ctl, ref), time.time() - t0)
        note("state_unchanged", seed,
             check.compare(check.unchanged_state_readings(ref), ref), 0.0)
        for fault in faults_of(cell):
            if fault == "state_unchanged":
                continue
            t0 = time.time()
            s = Trainer(cell, seed, smoke=args.smoke, fault=fault)
            bad = s.prog
            s.close()
            note(fault, seed, check.compare(bad, ref), time.time() - t0)

    summary = {}
    for kind, rows in readings.items():
        pick = max if kind in ("program", "witness_bf16") else min
        summary[kind] = {k: pick(r[k] for r in rows) for k in rows[0]}
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "summary": summary,
                      "seconds": time.time() - T_START}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
