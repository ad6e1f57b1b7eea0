"""Serving launcher: batched decode over the slot engine (CPU smoke or pod).

Example:
    python -m repro.launch.serve --arch tinyllama-1.1b --requests 8 \
        --max-new 16 --slots 4
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_smoke, get as get_config
from repro.launch.compile_cache import use_compile_cache
from repro.models.api import build_model
from repro.serving import Request, ServingEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--full", action="store_true",
                    help="published config (default: smoke config)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch) if args.full else get_smoke(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    engine = ServingEngine(model, params, batch_slots=args.slots,
                           max_seq=args.max_seq)
    rng = np.random.default_rng(args.seed)
    reqs = [Request(uid=i,
                    prompt=rng.integers(1, cfg.vocab_size,
                                        size=args.prompt_len),
                    max_new_tokens=args.max_new,
                    temperature=args.temperature, seed=args.seed)
            for i in range(args.requests)]
    t0 = time.time()
    results = engine.run(reqs)
    wall = time.time() - t0
    toks = sum(len(r.tokens) for r in results)
    lat = [r.latency_s for r in results]
    print(f"[serve] {args.arch}: {len(results)} requests, {toks} tokens in "
          f"{wall:.2f}s ({toks/wall:.1f} tok/s); "
          f"latency p50={np.median(lat)*1e3:.0f}ms "
          f"p99={np.percentile(lat, 99)*1e3:.0f}ms; "
          f"decode steps={engine.decode_steps}")
    for r in results[:3]:
        print(f"  uid={r.uid} tokens={r.tokens[:8]}...")


if __name__ == "__main__":
    main()
