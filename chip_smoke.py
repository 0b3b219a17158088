#!/usr/bin/env python3
"""Chip smoke: the serving hot path on a TPU at Mistral-7B widths.

Serves ``mistral-7b`` at its published widths (d_model 4096, 32 query /
8 kv heads, d_ff 14336, vocab 32000, window 4096), cut only in depth, with
bfloat16 weights drawn from ``--seed``, through the public serving API:
``ScheduledEngine`` over the paged KV pool with ``impl="pallas"``, i.e.
compiled Mosaic kernels.  Two styles are served:

  standard         the published residual + RMSNorm block;
  skipless_merged  the paper's Q/P-free model, built by
                   ``merge_skipless(params, cfg, "qp")`` from a
                   ``skipless`` model of the same seed.

Checks (the script exits non-zero when any fails):
  * every request returns its full budget of in-vocabulary tokens;
  * the merged engine reports ``merged_fast_path`` and
    ``merged_prefill_fast_path``;
  * pallas vs xla: logits of the whole-sequence forward with
    ``impl="pallas"`` (flash kernels) against ``impl="xla"``, at the last
    prompt position and every generated position of every request;
  * served tokens vs xla: each served token is a greedy choice of the
    ``impl="xla"`` forward over the served sequence (teacher-forced);
  * merged vs skipless: logits of the merged model against its skipless
    source, both ``impl="xla"``.

``--four-chips`` runs only the sharded phase instead: a skipless_merged
model of the same widths and depth served by ``Engine(mesh=...)`` on a
(data=1, model=4) mesh, and by an ``Engine`` on one chip of the four;
their prefill logits must agree.  Mosaic kernels cannot be partitioned
automatically (they need a ``shard_map``), so both sides run
``impl="xla"``.

  python3 chip_smoke.py                 # one chip
  python3 chip_smoke.py --four-chips    # four chips

Times printed are smoke timings of one run, not benchmark numbers.  The
last line of standard output is one JSON object:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# A random skipless stack (no residuals, no norms) loses its signal
# geometrically with depth: attention averages over positions and SwiGLU
# squares sub-unit activations.  At these widths its last-position logits
# measured ~1e-7 at 3 layers, ~1e-11 at 4 and ~1e-19 at 5 (reduced-width
# runs on the CPU, which track d_model 512..2048 closely), and underflow
# to exactly 0 by 6.  4 is the deepest cut whose merged-vs-skipless and
# pallas-vs-xla comparisons are not comparisons of zeros; both styles and
# both phases use it.
DEPTH = 4
PROMPT_LENS = (384, 256, 320, 200)  # one request per slot
MAX_NEW = 32
MAX_LEN = 512
BLOCK = 16  # tokens per KV page
CHUNK = 64  # chunked-prefill width
SEQ_PAD = 512  # reference forwards: every served sequence padded to this
IMPL = "pallas"  # the served path: compiled Mosaic kernels
# Each comparison: max |a - b| over a logit row, divided by the reference
# row's max |logit|, worst row.
REL_TOL = 0.15
TOL_WHY = ("bf16 activations keep 8 significant bits and the two sides "
           "round at different points in every layer: up to 5% of "
           "max|logit| measured at reduced widths on the CPU, so 0.15 "
           "leaves 3x margin, while a wrong index or mask moves logits by "
           "O(100%)")

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def fail(msg: str):
    print(f"FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def say(msg: str):
    print(msg, flush=True)


class CompileLog:
    """Backend compile seconds per program, tagged with the phase that
    triggered them (JAX reports every XLA compile through
    ``jax.monitoring``)."""

    def __init__(self, jax):
        self.phase = "setup"
        self.events = []  # (phase, program, seconds)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, seconds, **kw):
        if event == COMPILE_EVENT:
            self.events.append((self.phase, kw.get("fun_name", "?"), seconds))

    def report(self, since: int) -> int:
        for phase, name, secs in self.events[since:]:
            if secs >= 0.5:
                say(f"  compile [smoke timing] {phase}: {name} {secs:.2f}s")
        n = len(self.events) - since
        total = sum(s for _, _, s in self.events[since:])
        say(f"  compile [smoke timing] {n} programs, {total:.2f}s total")
        return n


def rel_diff(got, ref):
    """Worst row of max|got - ref| / max|ref|; fails on vacuous (all-zero
    or non-finite) reference rows."""
    import numpy as np
    got = np.asarray(got, np.float64).reshape(-1, got.shape[-1])
    ref = np.asarray(ref, np.float64).reshape(-1, ref.shape[-1])
    if not (np.isfinite(got).all() and np.isfinite(ref).all()):
        fail("non-finite logits")
    scale = np.abs(ref).max(axis=1)
    if not (scale > 0).all():
        fail("reference logits are all zero: the comparison would be vacuous")
    return float((np.abs(got - ref).max(axis=1) / scale).max()), \
        float(scale.min())


def check(name: str, diff: float, floor: float):
    verdict = "PASS" if diff <= REL_TOL else "FAIL"
    say(f"check {name}: max rel logit diff {diff:.3e} <= tol {REL_TOL} "
        f"({TOL_WHY}; smallest reference row max|logit| {floor:.3e}) "
        f"-> {verdict}")
    if diff > REL_TOL:
        fail(f"{name}: {diff:.3e} > {REL_TOL}")


def serve(label, cfg, params, seed, log):
    """Serve one batch cold (compiles) and one warm through
    ScheduledEngine(impl=IMPL) over the paged pool; returns the engine,
    the cold prompts and their token lists."""
    import numpy as np
    from repro.serving import (PagedCacheAdapter, SchedConfig, ServeConfig,
                               ScheduledEngine)
    eng = ScheduledEngine(
        cfg, params,
        ServeConfig(n_slots=len(PROMPT_LENS), max_len=MAX_LEN, seed=seed),
        scfg=SchedConfig(token_budget=256, chunk_tokens=CHUNK),
        impl=IMPL, cache=PagedCacheAdapter(block_size=BLOCK))
    say(f"{label}: ScheduledEngine impl={eng.impl} cache={eng.kv.kind} "
        f"merged_fast_path={eng.merged_fast_path} "
        f"merged_prefill_fast_path={eng.merged_prefill_fast_path}")
    rng = np.random.RandomState(seed)
    runs = []
    for temp in ("cold", "warm"):
        prompts = [rng.randint(0, cfg.vocab_size, size=n).astype(np.int32)
                   for n in PROMPT_LENS]
        log.phase = f"{label} serve ({temp})"
        n0 = len(log.events)
        t0 = time.perf_counter()
        outs = eng.generate(prompts, max_new_tokens=MAX_NEW)
        dt = time.perf_counter() - t0
        n_tok = sum(len(o) for o in outs)
        say(f"{label}: served {len(outs)} requests ({list(PROMPT_LENS)} "
            f"prompt tokens, {MAX_NEW} new each) {temp} in {dt:.2f}s "
            f"[smoke timing, {'compile included' if temp == 'cold' else 'no compile expected'}]"
            f", {n_tok} tokens")
        log.report(n0)
        for o in outs:
            if len(o) != MAX_NEW or not all(0 <= t < cfg.vocab_size
                                            for t in o):
                fail(f"{label}: bad token stream {list(o)[:8]}...")
        runs.append((prompts, [list(o) for o in outs]))
    return eng, runs[0]


def served_batch(prompts, outs):
    """Teacher-forcing batch: each row is prompt + served tokens[:-1],
    right-padded to SEQ_PAD (causal: padding never reaches a real row);
    ``idx`` holds the positions whose logits chose each served token."""
    import numpy as np
    toks = np.zeros((len(prompts), SEQ_PAD), np.int32)
    idx = np.zeros((len(prompts), MAX_NEW), np.int32)
    for r, (p, o) in enumerate(zip(prompts, outs)):
        seq = np.concatenate([p, np.asarray(o[:-1], np.int32)])
        toks[r, :len(seq)] = seq
        idx[r] = np.arange(len(p) - 1, len(p) - 1 + MAX_NEW)
    return toks, idx


def logits_at(cfg, params, toks, idx, impl, log, label):
    """(B, MAX_NEW, V) logits of the whole-sequence forward at ``idx``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import forward_seq
    merged = cfg.block_style == "skipless_merged"

    @jax.jit
    def fwd(p, t, i):
        lg = forward_seq(p, cfg, t, impl=impl, merged_core=merged)[0]
        return jnp.take_along_axis(lg, i[:, :, None], axis=1)

    log.phase = f"{label} forward impl={impl}"
    n0 = len(log.events)
    out = np.asarray(fwd(params, jnp.asarray(toks), jnp.asarray(idx)),
                     np.float32)
    log.report(n0)
    return out


def greedy_check(label, ref, outs):
    """Each served token's reference logit is within REL_TOL·max|row| of
    the row's max: a greedy choice up to the stated numeric tolerance."""
    import numpy as np
    worst = 0.0
    for r, o in enumerate(outs):
        rows = ref[r]  # (MAX_NEW, V)
        chosen = rows[np.arange(len(o)), np.asarray(o)]
        gap = (rows.max(axis=1) - chosen) / np.abs(rows).max(axis=1)
        worst = max(worst, float(gap.max()))
    verdict = "PASS" if worst <= REL_TOL else "FAIL"
    say(f"check {label} served tokens vs xla (teacher-forced greedy): worst "
        f"(max logit - served token's logit)/max|logit| {worst:.3e} <= tol "
        f"{REL_TOL} (served decode runs the paged pallas kernel, the "
        f"reference whole-sequence xla: same bf16 argument) -> {verdict}")
    if worst > REL_TOL:
        fail(f"{label}: served tokens are not greedy under the reference")


def host_init(label, cfg, seed: int, log):
    """``init_params`` from ``seed`` on the host CPU, as numpy arrays: the
    QR-based orthogonal init of skipless styles is several times faster
    there than on the chip (131 s on one v5e for 4 skipless layers)."""
    import jax
    from repro.models import init_params
    log.phase = f"{label} init"
    t0 = time.perf_counter()
    with jax.default_device(jax.devices("cpu")[0]):
        params = jax.device_get(init_params(jax.random.PRNGKey(seed), cfg))
    say(f"{label}: init on the host {time.perf_counter() - t0:.2f}s "
        f"[smoke timing]")
    return params


def one_chip(cfg, seed: int, log):
    import jax
    from repro.core import merge_skipless

    chip = jax.devices()[0]

    # -- standard ---------------------------------------------------------
    params = jax.device_put(host_init("standard", cfg, seed, log), chip)
    eng, (prompts, outs) = serve("standard", cfg, params, seed, log)
    del eng
    toks, idx = served_batch(prompts, outs)
    xla = logits_at(cfg, params, toks, idx, "xla", log, "standard")
    pal = logits_at(cfg, params, toks, idx, IMPL, log, "standard")
    check("standard pallas vs xla", *rel_diff(pal, xla))
    greedy_check("standard", xla, outs)
    del params, xla, pal
    gc.collect()

    # -- skipless -> skipless_merged ---------------------------------------
    scfg = cfg.with_(block_style="skipless")
    sparams = host_init("skipless", scfg, seed, log)
    t0 = time.perf_counter()
    mparams, mcfg = merge_skipless(sparams, scfg, "qp")
    mparams, sparams = jax.device_put((mparams, sparams), chip)
    say(f"skipless_merged: merge_skipless(qp) on the host "
        f"{time.perf_counter() - t0:.2f}s [smoke timing]")
    eng, (prompts, outs) = serve("skipless_merged", mcfg, mparams, seed, log)
    if not (eng.merged_fast_path and eng.merged_prefill_fast_path):
        fail("merged engine is off its fast paths")
    del eng
    toks, idx = served_batch(prompts, outs)
    xla = logits_at(mcfg, mparams, toks, idx, "xla", log, "skipless_merged")
    pal = logits_at(mcfg, mparams, toks, idx, IMPL, log,
                    "skipless_merged")
    check("skipless_merged pallas vs xla", *rel_diff(pal, xla))
    greedy_check("skipless_merged", xla, outs)
    src = logits_at(scfg, sparams, toks, idx, "xla", log, "skipless")
    check("skipless_merged vs skipless source (both xla)",
          *rel_diff(xla, src))


def prefill_logits(eng, prompt):
    """Last-position logits of the engine's own prefill program for one
    request (admission + direct-to-page prefill, as ``Engine.submit``)."""
    import numpy as np
    from repro.serving import Engine
    n_shared = eng.kv.admit(0, prompt)
    logits = eng.kv.prefill(eng.params, 0,
                            Engine.host_to_device(prompt, np.int32)[None],
                            len(prompt), n_shared, None)
    return np.asarray(logits, np.float32)


def placement(tree):
    """(devices the leaves span, leaves split across more than one)."""
    import jax
    devs, split = set(), 0
    for leaf in jax.tree.leaves(tree):
        devs |= set(leaf.sharding.device_set)
        split += leaf.addressable_shards[0].data.shape != leaf.shape
    return devs, split


def four_chips(devices, cfg, seed: int, log):
    """Sharded merged prefill on a (data=1, model=4) mesh vs one chip."""
    import jax
    import numpy as np
    from repro.launch.mesh import make_mesh
    from repro.serving import Engine, PagedCacheAdapter, ServeConfig

    mcfg = cfg.with_(block_style="skipless_merged")
    host = host_init("skipless_merged", mcfg, seed, log)
    prompt = np.random.RandomState(seed).randint(
        0, mcfg.vocab_size, size=PROMPT_LENS[1]).astype(np.int32)
    sc = ServeConfig(n_slots=2, max_len=MAX_LEN, seed=seed)

    log.phase = "one chip prefill"
    n0 = len(log.events)
    one = Engine(mcfg, jax.device_put(host, devices[0]), sc, impl="xla",
                 cache=PagedCacheAdapter(block_size=BLOCK))
    t0 = time.perf_counter()
    ref = prefill_logits(one, prompt)
    say(f"one chip ({devices[0]}): prefill {time.perf_counter() - t0:.2f}s "
        f"[smoke timing, compile included]")
    log.report(n0)
    del one
    gc.collect()

    mesh = make_mesh((1, 4), ("data", "model"))
    log.phase = "mesh prefill"
    n0 = len(log.events)
    four = Engine(mcfg, host, sc, mesh=mesh, impl="xla",
                  cache=PagedCacheAdapter(block_size=BLOCK))
    p_devs, p_split = placement(four.params)
    c_devs, c_split = placement(four.kv.device_cache())
    say(f"mesh {dict(mesh.shape)}: params on {len(p_devs)} devices "
        f"({p_split} leaves split), KV pages on {len(c_devs)} devices "
        f"({c_split} leaves split)")
    if len(p_devs) != 4 or len(c_devs) != 4 or not p_split or not c_split:
        fail("parameters or pages did not spread over the four devices")
    t0 = time.perf_counter()
    got = prefill_logits(four, prompt)
    say(f"mesh: prefill {time.perf_counter() - t0:.2f}s "
        f"[smoke timing, compile included]")
    log.report(n0)
    check("mesh (data=1, model=4) vs one chip prefill", *rel_diff(got, ref))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded (data=1, model=4) phase")
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        fail(f"no repro package under {SRC}: run from a checkout")
    sys.path.insert(0, str(SRC))
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU found: jax.devices()[0].platform is {dev.platform!r}")
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        fail(f"{need} chips needed, {len(devices)} found")

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    log = CompileLog(jax)
    cfg = get_config("mistral-7b").with_(n_layers=DEPTH,
                                         param_dtype="bfloat16")
    say(f"platform={dev.platform} device_kind={dev.device_kind} "
        f"device_count={len(devices)} jax={jax.__version__}")
    say(f"model mistral-7b depth={cfg.n_layers} (of 32) d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size} window={cfg.sliding_window} "
        f"weights={cfg.param_dtype} seed={args.seed}")
    entries = len(list(Path(cache_dir).glob("*"))) \
        if Path(cache_dir).is_dir() else 0
    say(f"compile cache: {cache_dir} ({entries} entries before this run)")

    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(devices, cfg, args.seed, log)
    else:
        one_chip(cfg, args.seed, log)
    say(f"all checks passed in {time.perf_counter() - t0:.2f}s "
        f"[smoke timing]")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
