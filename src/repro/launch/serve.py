"""Serving launcher: batched generation with continuous batching.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --block-style skipless_merged --requests 8 --max-new 16

With ``--merged-from-skipless`` the launcher builds a skipless model, runs
the paper's QP-removal merge, and serves the merged weights — reporting the
weight/bandwidth savings next to the generated tokens.

``--cache paged`` serves through the block-pool KV cache adapter
(``serving.PagedCacheAdapter``: admission by pages instead of a worst-case
slot cap, direct-to-page prefill) — ``--slots`` then sizes the page pool in
dense-slot equivalents while every request gets its own batch row.
``--cache paged_q8`` is the same pool with int8 pages + per-(page,
kv-head) scales (the SAME dense-slot-equivalent budget buys ~4x the
pages, and the report adds the quantized-pool byte telemetry).

Per-request serving stats (prompt_len, time-to-first-token, decode tok/s)
come straight from ``Engine.generate``'s RequestResults.

On a TPU, serve published widths cut in depth, with bfloat16 weights and
the compiled Pallas kernels (the full 32-layer model in float32 does not
fit one 16 GB chip):

  PYTHONPATH=src python -m repro.launch.serve --arch mistral-7b \
      --n-layers 4 --param-dtype bfloat16 --impl pallas --cache paged \
      --prompt-len 256 --max-new 32 --max-len 512

The persistent compile cache goes where ``repro.launch.compile_cache``
says: ``$JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--block-style", default=None)
    ap.add_argument("--merged-from-skipless", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--cache", default="dense",
                    choices=("dense", "paged", "paged_q8"))
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n-layers", type=int, default=0,
                    help="cut the model to this depth (0: the config's)")
    ap.add_argument("--impl", default="xla",
                    choices=("xla", "pallas", "pallas_interpret"))
    ap.add_argument("--param-dtype", default=None,
                    choices=("float32", "bfloat16"),
                    help="weight dtype (default: the config's)")
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from repro.configs import get_config, reduce_config
    from repro.core import merge_skipless
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import count_params, init_params
    from repro.serving import (Engine, PagedCacheAdapter,
                               PagedQ8CacheAdapter, ServeConfig)

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_config(cfg)
    if args.n_layers:
        cfg = cfg.with_(n_layers=args.n_layers)
    if args.param_dtype:
        cfg = cfg.with_(param_dtype=args.param_dtype)
    if args.merged_from_skipless:
        cfg = cfg.with_(block_style="skipless")
    elif args.block_style:
        cfg = cfg.with_(block_style=args.block_style)
    cfg.validate_style()

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    n0 = count_params(params)
    if args.merged_from_skipless:
        params, cfg = merge_skipless(params, cfg, "qp")
        n1 = count_params(params)
        print(f"QP removal: {n0:,d} -> {n1:,d} params "
              f"({100 * (n0 - n1) / n0:.1f}% removed)", flush=True)

    if args.cache in ("paged", "paged_q8"):
        sc = ServeConfig(n_slots=args.requests, max_len=args.max_len,
                         temperature=args.temperature, seed=args.seed)
        cls = PagedCacheAdapter if args.cache == "paged" \
            else PagedQ8CacheAdapter
        cache = cls(
            block_size=args.block_size,
            n_blocks=args.slots * args.max_len // args.block_size)
    else:
        sc = ServeConfig(n_slots=args.slots, max_len=args.max_len,
                         temperature=args.temperature, seed=args.seed)
        cache = "dense"
    eng = Engine(cfg, params, sc, impl=args.impl, cache=cache)
    rng = np.random.RandomState(args.seed)
    prompts = [rng.randint(0, cfg.vocab_size, size=(args.prompt_len,))
               for _ in range(args.requests)]
    t0 = time.perf_counter()  # monotonic: NTP steps can't skew a duration
    outs = eng.generate(prompts, max_new_tokens=args.max_new)
    dt = time.perf_counter() - t0
    total_tokens = sum(len(o) for o in outs)
    ttfts = [o.ttft_s for o in outs]
    print(f"served {args.requests} requests, {total_tokens} tokens "
          f"in {dt:.2f}s ({total_tokens / dt:.1f} tok/s); "
          f"TTFT mean {np.mean(ttfts):.3f}s / max {np.max(ttfts):.3f}s",
          flush=True)
    if args.cache in ("paged", "paged_q8"):
        a = eng.pm.allocator
        print(f"  paged pool: {a.n_blocks} pages, peak used {a.peak_used}, "
              f"peak streams {eng.stats['peak_active']}, "
              f"shared {a.n_shared_hits}, cow {a.n_cow}, "
              f"deferred {eng.stats['n_deferred']}, "
              f"preempted {eng.stats['n_preempted']}", flush=True)
        if args.cache == "paged_q8":
            print(f"  q8 pool: {eng.pm.pool_bytes / 1e6:.2f} MB resident "
                  f"(int8 pages + scales)", flush=True)
    for i, o in enumerate(outs[:4]):
        # decode_tok_s is None for single-token requests (no decode phase)
        rate = "n/a" if o.decode_tok_s is None else f"{o.decode_tok_s:.1f}"
        print(f"  req{i}: {list(o[:12])}{'…' if len(o) > 12 else ''} "
              f"(ttft {o.ttft_s:.3f}s, {rate} tok/s decode)")


if __name__ == "__main__":
    main()
