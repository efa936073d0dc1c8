"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only NAME] [--smoke]

Each module prints a CSV block and asserts its paper-claim invariants.
Modules may *return* a JSON-serialisable payload; the overhead benchmark's
payload (recompute factor, stall seconds, wall time and host-dispatch counts
per strategy, plus the compiled-vs-interpreted engine comparison) is written
to ``BENCH_overhead.json`` at the repo root — CI uploads it on main as the
perf-trajectory artifact.  The kernel benchmark's fused-vs-compiled
head-to-head payload is merged into the same file under ``"kernels"``, and
the multi-tenant serving trace (latency percentiles, preemption count,
admission-contract audit) under ``"serve"``.

``--only`` takes comma-separated substrings (``--only fig5,serve``).

Sections are imported lazily, one at a time: a module that fails to import
is reported with its traceback and counts as a failed section (nonzero
exit) — the remaining sections still run, but a section that could not
even load never looks green.
"""
import argparse
import importlib
import inspect
import json
import os
import sys
import time
import traceback

ALL = [
    ("fig3_recompute_factors", "benchmarks.bench_recompute"),
    ("fig4_peak_memory", "benchmarks.bench_memory"),
    ("fig5_measured_overhead", "benchmarks.bench_overhead"),
    ("sec3_perf_model", "benchmarks.bench_perfmodel"),
    ("kernel_rooflines", "benchmarks.bench_kernels"),
    ("serve_scheduler", "benchmarks.bench_serve"),
]

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OVERHEAD_JSON = os.path.join(REPO_ROOT, "BENCH_overhead.json")


def run(only=None, smoke=False, out_path=OVERHEAD_JSON, sections=None):
    """Run the selected benchmark sections; returns a process exit code.

    ``sections`` overrides the registry (tests inject fakes); entries are
    ``(name, module_path)`` pairs resolved with ``importlib`` only when the
    section is actually selected, so one unimportable module cannot take
    down — or silently shrink — the rest of the harness.
    """
    # The executor-engine sections dispatch nested segment jits from inside
    # io_callbacks; with XLA's async CPU dispatch the outer program occupies
    # the (nproc-sized) execution pool, so on few-core hosts the nested
    # dispatch starves and the bench deadlocks.  The flag is read once, at
    # CPU client creation, so it must be set before any section touches a
    # backend (tests get the same treatment from conftest.py).
    import jax

    jax.config.update("jax_cpu_enable_async_dispatch", False)

    failures = []
    payloads = {}
    patterns = [p for p in (only or "").split(",") if p]
    for name, module_path in (ALL if sections is None else sections):
        if patterns and not any(p in name for p in patterns):
            continue
        print(f"\n== {name} ==")
        try:
            fn = importlib.import_module(module_path).main
        except Exception as e:  # broken module: a failure; keep going
            traceback.print_exc()
            print(f"-- FAILED {name}: cannot import {module_path}: {e!r}")
            failures.append((name, repr(e)))
            continue
        kwargs = {}
        if smoke and "smoke" in inspect.signature(fn).parameters:
            kwargs["smoke"] = True
        t0 = time.time()
        try:
            payloads[name] = fn(**kwargs)
            print(f"-- ok in {time.time()-t0:.1f}s")
        except Exception as e:  # keep going; report at the end
            traceback.print_exc()
            failures.append((name, repr(e)))
    overhead = payloads.get("fig5_measured_overhead")
    serve = payloads.get("serve_scheduler")
    if overhead is not None or serve is not None:
        doc = {"smoke": smoke}
        if overhead is not None:
            doc["payload"] = overhead
        kernels = payloads.get("kernel_rooflines")
        if kernels is not None:
            doc["kernels"] = kernels
        if serve is not None:
            doc["serve"] = serve
        with open(out_path, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
        print(f"\nwrote {out_path}")
    if failures:
        print("\nBENCH FAILURES:", failures)
        return 1
    print("\nall benchmarks passed")
    return 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced workloads for CI (minutes, not hours)")
    args = ap.parse_args()
    sys.exit(run(only=args.only, smoke=args.smoke))


if __name__ == "__main__":
    main()
