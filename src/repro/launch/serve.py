"""Serving driver: batched requests through the ServingEngine with the
paper's interval controller (Algorithm 1 + migrations) in the loop.

  PYTHONPATH=src python -m repro.launch.serve --arch musicgen-large \
      --reduced --requests 8 --tokens 24 [--straggler 0]

``main`` returns the engine it served with, so a caller (``chip_smoke.py``)
can inspect what the run did.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import use_compile_cache
from repro.launch.train import reduced_for_cpu
from repro.serving.engine import make_engine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="musicgen-large")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--min-prompt-len", type=int, default=None,
                    help="shortest prompt under --mixed-lengths "
                         "(default: half of --prompt-len)")
    ap.add_argument("--max-seq", type=int, default=None,
                    help="per-slot token capacity (default: prompt + "
                         "tokens + 8, rounded up to a page)")
    ap.add_argument("--tokens", type=int, default=24)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--lam", type=int, default=8,
                    help="controller interval (decode steps)")
    ap.add_argument("--straggler", type=int, default=-1,
                    help="slow this device 20x after the first "
                         "controller interval")
    ap.add_argument("--engine", default="auto",
                    choices=("auto", "continuous", "wave"),
                    help="continuous batching (default for linear-cache "
                         "archs) or the wave baseline")
    ap.add_argument("--mixed-lengths", action="store_true",
                    help="vary prompt lengths per request (the workload "
                         "continuous batching exists for)")
    ap.add_argument("--use-kernel", action="store_true",
                    help="decode through the placement-driven Pallas "
                         "flash-decode kernel (auto-interpret on CPU); "
                         "greedy streams must match the jnp path")
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV cache (continuous engine splices "
                         "quantized values + scales per slot)")
    ap.add_argument("--pipeline-k", type=int, default=1,
                    help="decode tokens in flight across slot groups "
                         "(must divide --slots)")
    ap.add_argument("--search", default="rescoring",
                    choices=("rescoring", "bottleneck"),
                    help="controller placement search: the PR-3 rescoring "
                         "path or the bottleneck-targeted search "
                         "(pipeline-k > 1)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: pooled page store + per-slot "
                         "page tables, chunked prefill (continuous "
                         "engine only); streams must match the dense "
                         "engine at the same seed")
    ap.add_argument("--page-size", type=int, default=8,
                    help="tokens per KV page (--paged)")
    args = ap.parse_args(argv)

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_for_cpu(cfg)
    if args.kv_quant:
        cfg = cfg.with_overrides(kv_quant=True)
    kw = {}
    mode = args.engine
    if args.paged:
        # pages divide max_seq; the paged path is continuous-engine only
        kw.update(paged=True, page_size=args.page_size)
        mode = "continuous"
    max_seq = args.max_seq or args.prompt_len + args.tokens + 8
    if args.paged and max_seq % args.page_size:
        max_seq += args.page_size - max_seq % args.page_size
    eng = make_engine(cfg, mode=mode, n_slots=args.slots,
                      max_seq=max_seq,
                      lam=args.lam, use_kernel=args.use_kernel,
                      pipeline_k=args.pipeline_k, search=args.search, **kw)
    print(f"[serve] engine: {type(eng).__name__}")
    if args.straggler >= 0:
        # the device slows down mid-decode, once the first controller
        # interval has placed the model: later intervals see it and move
        # heads away (slowed before the first plan, the device is simply
        # left out of that plan and nothing ever migrates)
        def slow_after_first_interval(req, tok, done):
            if eng.migration_log:
                eng.token_sink = None
                eng.net.inject_straggler(args.straggler, slowdown=20.0)
                print(f"[serve] injected straggler on slot {args.straggler}"
                      f" at decode step {eng.decode_steps}")
        eng.token_sink = slow_after_first_interval
    rng = np.random.default_rng(0)
    t0 = time.time()
    for i in range(args.requests):
        if args.mixed_lengths:
            lo = args.min_prompt_len or args.prompt_len // 2
            plen = int(rng.integers(max(2, lo), args.prompt_len + 1))
        else:
            plen = args.prompt_len
        eng.submit(rng.integers(0, cfg.vocab_size, size=plen),
                   max_new_tokens=args.tokens)
    done = eng.run()
    wall = time.time() - t0
    total_toks = sum(len(r.out_tokens) for r in done)
    print(f"[serve] {len(done)} requests, {total_toks} tokens in "
          f"{wall:.1f}s ({total_toks/wall:.1f} tok/s)")
    migr = sum(m["n_migrations"] for m in eng.migration_log)
    print(f"[serve] controller intervals={len(eng.migration_log)} "
          f"head-migrations={migr}")
    if hasattr(eng, "slot_busy_steps") and eng.decode_steps:
        util = eng.slot_busy_steps / (eng.decode_steps * eng.n_slots)
        print(f"[serve] slot utilization {util:.0%}, prefill buckets "
              f"{sorted(eng.prefill_buckets_used)}")
    for r in done[:3]:
        print(f"  req {r.rid}: ttft={r.t_first - r.t_submit:.2f}s "
              f"total={r.t_done - r.t_submit:.2f}s "
              f"tokens={r.out_tokens[:8]}...")
    return eng


if __name__ == "__main__":
    main()
