"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run_cell.py --workload mamba2-370m.offload-2k \
        --seed 12345 --seconds 30 --trace 0

Runs from the root of a checkout that holds ``BENCHMARK.json``, this
directory and the system under test under ``src/``.  Needs a TPU, and as
many chips as the cell asks for: without them it prints no result and
exits 2.  ``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1``
its per-layer metrics from a profiler trace of a few more steps.  The
numbers that decide ``correct`` are printed beside their limits, as the
last lines on standard error and under ``check`` in the result.
"""
import time

T_START = time.time()      # set-up is counted from here

import argparse            # noqa: E402
import json                # noqa: E402
import os                  # noqa: E402
import sys                 # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def configure_cache() -> str:
    """JAX's persistent compile cache at a fixed path: the one
    ``JAX_COMPILATION_CACHE_DIR`` names, else ``<checkout>/.jax_cache``.
    Its key holds the op metadata, so that two checkouts whose steps
    differ only in their named scopes, sharing one such directory, never
    load each other's step: the part metrics read that metadata."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, BENCH)
    from harness.spec import SpecError, load_cell

    try:
        cell = load_cell(ROOT, args.workload)
    except SpecError as e:
        print(f"run_cell: {e}", file=sys.stderr)
        return 2
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"run_cell: no system under test at {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    configure_cache()
    import jax

    t0 = time.time()
    devices = jax.devices()
    print(f"imports_s={t0 - T_START} backend_init_s={time.time() - t0}",
          file=sys.stderr, flush=True)
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"run_cell: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX has {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    from harness.train import run

    result = run(cell, args.seed, args.seconds, bool(args.trace),
                 t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
