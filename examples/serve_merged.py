"""Serving demo: continuous batching with a QP-removed model, dense or
paged KV cache.

Removing Q/P (paper Fig 1b) cuts the per-token WEIGHT stream; what turns
that into throughput is batching enough concurrent requests over the
remaining K*/V* reads.  The dense cache caps concurrency at
``HBM / (L · max_len · Hkv · Dh)`` worst-case slots; ``--cache paged``
spends the same bytes on a block pool (vLLM-style: free-list allocator,
per-request block tables, prefix sharing with copy-on-write), so a
mixed-length request mix runs many more streams per HBM byte — watch
``peak streams`` between the two runs.  (Run on the CPU, the absolute
tok/s is illustrative; the bandwidth accounting is the TPU-relevant
part.  ``chip_smoke.py`` is the path that runs on a TPU.)

  PYTHONPATH=src python examples/serve_merged.py [--arch llama3.2-1b]
                                                 [--cache dense|paged]
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, reduce_config
from repro.core import decode_ms_per_token, merge_skipless, weight_table
from repro.models import count_params, init_params
from repro.serving import Engine, PagedCacheAdapter, ServeConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--cache", default="dense", choices=("dense", "paged"))
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    args = ap.parse_args()

    cfg = reduce_config(get_config(args.arch)).with_(
        block_style="skipless", dtype="float32", param_dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    mparams, mcfg = merge_skipless(params, cfg, "qp")
    n0, n1 = count_params(params), count_params(mparams)
    print(f"serving {cfg.name} with QP removed: {n0:,} -> {n1:,} params "
          f"({args.cache} cache)")

    if args.cache == "paged":
        # slots are just batch rows; the POOL (sized like `--slots` dense
        # slots) is what admission control spends — prefill writes prompt
        # KV direct-to-page (no worst-case intermediate buffer)
        sc = ServeConfig(n_slots=args.requests, max_len=128)
        cache = PagedCacheAdapter(block_size=16,
                                  n_blocks=args.slots * 128 // 16)
    else:
        sc = ServeConfig(n_slots=args.slots, max_len=128)
        cache = "dense"
    eng = Engine(mcfg, mparams, sc, cache=cache)
    print(f"  merged fast path: decode={eng.merged_fast_path} "
          f"prefill={eng.merged_prefill_fast_path} (Q/P weights never "
          f"read in either serving phase)")
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, size=(rng.randint(6, 24),))
               for _ in range(args.requests)]
    t0 = time.perf_counter()  # monotonic: NTP steps can't skew a duration
    outs = eng.generate(prompts, max_new_tokens=args.max_new)
    dt = time.perf_counter() - t0
    n_tok = sum(len(o) for o in outs)
    ttfts = [o.ttft_s for o in outs]
    print(f"{args.requests} requests, {n_tok} tokens in {dt:.2f}s "
          f"({n_tok / dt:.1f} tok/s on CPU), "
          f"peak streams {eng.stats['peak_active']}, "
          f"TTFT mean {np.mean(ttfts):.3f}s")
    if args.cache == "paged":
        a = eng.pm.allocator
        print(f"  pool: {a.n_blocks} pages, peak used {a.peak_used}, "
              f"prefix-shared {a.n_shared_hits}, copy-on-write {a.n_cow}, "
              f"deferred {eng.stats['n_deferred']}, "
              f"preempted {eng.stats['n_preempted']}")

    # the TPU-relevant accounting (paper §3 model, full-size arch):
    full = get_config(args.arch)
    t = weight_table(full)
    ms_w = decode_ms_per_token(t["total"])
    ms_wo = decode_ms_per_token(t["total_without_qp"])
    print(f"\n{full.name} @ v5e batch-1 decode (weights streaming, bf16):")
    print(f"  with Q+P   : {ms_w:.2f} ms/token")
    print(f"  without Q+P: {ms_wo:.2f} ms/token   -> {ms_w / ms_wo:.2f}x")
    for i, o in enumerate(outs[:3]):
        print(f"  req{i}: {o}")
    print("OK")


if __name__ == "__main__":
    main()
