"""Serving launcher: continuous-batching decode over ``repro.serve``.

A thin CLI around :class:`repro.serve.DecodeSession` — each prompt is
prefilled at its TRUE length and joined into the running batch through the
model-declared cache spec (``ModelAPI.cache_spec``), so every cache leaf
with a sequence axis is padded to the horizon (not just the attention KV
tensors) and every slot decodes at its own ``(B,)`` position.  Mixed
prompt lengths are first-class: ``--prompt-lens 5,8,12`` serves a ragged
batch whose per-slot tokens match what each prompt would produce alone.

``--preemptible`` builds the decode step WITHOUT cache donation so the
session can be parked into a storage tier and resumed (the multi-tenant
scheduler's preemption path); the default keeps donation for the in-place
cache update.

Example::

    PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --smoke \
        --prompt-len 32 --decode-steps 32 --batch 4
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.perf_env import configure_compile_cache
from repro.models import get_model
from repro.serve import DecodeSession


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--prompt-lens", type=str, default=None,
                    help="comma-separated per-slot prompt lengths "
                    "(mixed-length batch; overrides --batch/--prompt-len)")
    ap.add_argument("--decode-steps", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--preemptible", action="store_true",
                    help="disable cache donation so the session can be "
                    "parked/resumed (scheduler preemption)")
    args = ap.parse_args(argv)
    configure_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    api = get_model(cfg)
    if api.prefill is None:
        raise SystemExit(f"{cfg.name} has no serving path")
    params = api.init(jax.random.PRNGKey(0))

    if args.prompt_lens:
        plens = [int(x) for x in args.prompt_lens.split(",")]
    else:
        plens = [args.prompt_len] * args.batch
    batch = len(plens)
    max_len = max(plens) + args.decode_steps
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=(n,)) for n in plens]

    session = DecodeSession(api, params, batch=batch, max_len=max_len,
                            decode_steps=args.decode_steps,
                            preemptible=args.preemptible,
                            temperature=args.temperature)
    t0 = time.time()
    for p in prompts:
        session.add_request(p)
    jax.block_until_ready(session.cache)
    t_prefill = time.time() - t0

    t0 = time.time()
    n_rounds = 0
    while not session.done():
        session.step()
        n_rounds += 1
    jax.block_until_ready(session.tok)
    t_decode = time.time() - t0

    toks = np.asarray(session.generated)
    n_prompt = sum(plens)
    n_new = batch * args.decode_steps
    print(f"[serve] arch={cfg.name} batch={batch} "
          f"prompt_lens={plens} decode={args.decode_steps} "
          f"preemptible={args.preemptible}")
    print(f"  prefill: {t_prefill*1e3:.1f} ms "
          f"({n_prompt/t_prefill:.0f} tok/s)")
    print(f"  decode:  {t_decode*1e3:.1f} ms total, "
          f"{t_decode/max(n_rounds, 1)*1e3:.2f} ms/step, "
          f"{n_new/t_decode:.0f} tok/s")
    print(f"  sample token ids: {toks[0][:16].tolist()}")
    return toks


if __name__ == "__main__":
    main()
