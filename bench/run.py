#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print one JSON result line.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration file
and a traffic mix; everything else is found by those names (``bench/
spec.py``).  One run:

  set-up   weights made on the chip from ``--seed`` in one jitted call;
           ``ScheduledEngine`` over the paged pool with ``impl="pallas"``
           and greedy sampling; every program the cell uses warmed up; the
           closed loop's first requests prefilled.  ``setup_s`` runs from
           process start to the window's opening.
  window   ``--seconds`` of the mix; every token timed by the harness.
           Compiles inside the window are counted (there should be none).
  check    the program's state freed, the served tokens of a sample of
           the requests (finished, and still decoding when the window
           closed) against the plain float32 reference (``bench/
           check.py``); ``correct`` is every compared number within its
           limit.
  trace    with ``--trace 1``: the per-layer metrics (``bench/metrics``)
           instead of the end-to-end ones, from a profiler trace of part
           of the window and the harness's own records.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for, or when the program is not beside the benchmark.
The last line of standard output is the result; the numbers compared are
printed beside their limits last on standard error and last in that line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import check, flops, serve  # noqa: E402
from bench import trace as trace_mod  # noqa: E402
from bench.spec import Spec  # noqa: E402

TRACE_FROM = 0.25  # the traced part of the window starts a quarter in
TRACE_MAX_S = 10.0  # and lasts at most this long (at most half the window)


class NoChip(RuntimeError):
    pass


def percentile(x, q):
    import numpy as np
    return float(np.percentile(x, q)) if len(x) else float("nan")


def device_info(jax, chips: int, allow_cpu: bool):
    devices = jax.devices()
    if devices[0].platform != "tpu" and not allow_cpu:
        raise NoChip(f"no TPU: jax.devices()[0].platform is "
                     f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"{chips} chips needed, {len(devices)} found")
    return devices[:chips]


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def build_engine(mc, params, eng_spec, seed, impl, pool="paged"):
    """``pool`` "paged": the bfloat16 page pool the cells serve from;
    "paged_q8": the program's int8 pool, run only as a control."""
    from repro.serving import (PagedCacheAdapter, PagedQ8CacheAdapter,
                               SchedConfig, ServeConfig, ScheduledEngine)
    adapter = {"paged": PagedCacheAdapter,
               "paged_q8": PagedQ8CacheAdapter}[pool]
    sc = ServeConfig(n_slots=eng_spec["slots"], max_len=eng_spec["max_len"],
                     seed=seed & 0x7FFFFFFF,
                     block_size=eng_spec["page_tokens"],
                     n_blocks=eng_spec["pool_pages"])
    return ScheduledEngine(
        mc, params, sc,
        scfg=SchedConfig(token_budget=eng_spec["token_budget"],
                         chunk_tokens=eng_spec["chunk_tokens"]),
        impl=impl,
        cache=adapter(block_size=eng_spec["page_tokens"],
                      n_blocks=eng_spec["pool_pages"]))


def warm_up(eng, vocab: int, eng_spec) -> None:
    """Run the shapes the window uses once: a two-chunk prompt and a short
    one (chunk program, first-token sampling, decode step, decode
    sampling), then drop what the prefix cache kept of them."""
    import numpy as np
    rng = np.random.default_rng(0)
    c = eng_spec["chunk_tokens"]
    prompts = [rng.integers(0, vocab, size=n, dtype=np.int32)
               for n in (c + 1, 16)]
    eng.generate(prompts, max_new_tokens=3)
    eng.kv.pm.drop_prefix_cache()


def reference_config(cfg: dict) -> dict:
    """The configuration the reference computes: the file's, with each
    key the program cannot take (``program_departs``) at the value the
    program runs, so that ``correct`` compares the program's own
    arithmetic; ``bench/control.py --witness`` reads the departure."""
    return dict(cfg, **{k: v["runs"]
                        for k, v in cfg.get("program_departs", {}).items()})


def judge(compared) -> bool:
    return all(v["value"] <= v["limit"] for v in compared.values())


def gap_stats(gaps) -> str:
    import numpy as np
    if not len(gaps):
        return "no served tokens"
    return (f"worst {float(gaps.max())}, p99 {float(np.quantile(gaps, .99))}"
            f", mean {float(gaps.mean())}, nonzero "
            f"{float(np.mean(gaps > 0))} over {len(gaps)} tokens")


def run_cell(spec: Spec, workload: str, seed: int, seconds: float,
             trace: bool, *, impl: str = "pallas", pool: str = "paged",
             allow_cpu: bool = False, control: bool = False,
             witness=None, config_override=None, mix_override=None,
             log=None, t_start: float = None):
    """One run of one cell; returns the result object (a dict).  With
    ``control`` the float8 reference is also put in the program's place
    on the same sample and judged by the same limits (``control``).
    ``witness``, a dict of configuration keys, runs a second reference
    with those keys changed over the same sample (``witness_worst_gap``)."""
    import jax
    import numpy as np
    from repro.serving import Request

    from bench import weights
    from bench.model import program_config
    from bench.traffic import Mix

    t_start = T_START if t_start is None else t_start
    wl = spec.workload(workload)
    cfg = spec.config(wl["config"])
    if config_override:
        cfg.update(config_override)
    mix_spec = spec.traffic(wl["traffic"])
    if mix_override:
        mix_spec.update(mix_override)
    eng_spec = mix_spec["engine"]
    devices = device_info(jax, wl["chips"], allow_cpu)
    dev = devices[0]
    peaks = json.loads((spec.bench / "peaks.json").read_text())
    if dev.platform == "tpu" and dev.device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {dev.device_kind!r}")
    peak = peaks.get(dev.device_kind)
    say = lambda m: print(m, flush=True)  # noqa: E731

    # -- set-up --------------------------------------------------------------
    marks = [("start", time.perf_counter())]
    mc = program_config(cfg)
    params = weights.make_params(cfg, seed, jax.numpy.dtype(
        cfg["torch_dtype"]))
    jax.block_until_ready(params)
    marks.append(("weights", time.perf_counter()))
    eng = build_engine(mc, params, eng_spec, seed, impl, pool)
    marks.append(("engine", time.perf_counter()))
    vocab = cfg["vocab_size"]
    warm_up(eng, vocab, eng_spec)
    marks.append(("warm-up", time.perf_counter()))
    mix = Mix(mix_spec, seed, vocab)
    span = jax.profiler.TraceAnnotation if trace else None
    runner = serve.Runner(
        eng, mix, lambda toks, n: Request(prompt=toks, max_new_tokens=n),
        span=span)
    if log is not None:
        log.phase = "fill"
    runner.fill_closed()
    marks.append(("fill", time.perf_counter()))
    say("set-up s: " + ", ".join(
        f"{name} {b - a:.2f}" for (_, a), (name, b) in
        zip([("process", t_start)] + marks, marks)))

    # -- the window ------------------------------------------------------------
    tdir, traced = None, {}
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        lo = seconds * TRACE_FROM
        hi = lo + min(TRACE_MAX_S, seconds / 2)

        def start():
            jax.profiler.start_trace(tdir)
            # made after start_trace: an annotation made before it is
            # not recorded
            traced["span"] = jax.profiler.TraceAnnotation("bench.window")
            traced["span"].__enter__()
            traced["lo"] = runner.now()

        def stop():
            traced["hi"] = runner.now()
            traced["span"].__exit__(None, None, None)
            jax.profiler.stop_trace()

        runner.timers = [(lo, start), (hi, stop)]
    if log is not None:
        log.phase = "window"
    window_s = runner.run_closed(seconds)
    if log is not None:
        log.phase = "after"
    setup_s = runner.t0 - t_start
    res = serve.Result(runner.all, runner.steps, window_s, seconds)
    mem_peak = memory_peak(devices)

    # -- free the program, then the reference ---------------------------------
    served = [SimpleNamespace(prompt=np.asarray(x.req.prompt),
                              out=list(x.req.out_tokens),
                              finished=x.finished_at is not None,
                              admitted_in_window=x.submitted >= 0)
              for x in res.requests if x.req.out_tokens]
    for x in res.requests:
        x.req = None
    del eng, params, runner
    gc.collect()
    t_check = time.perf_counter()
    rows_n = mix_spec["check"]["rows"]
    width = eng_spec["max_len"]
    placed = check.sample(served, seed, rows_n, width)
    ref = spec.reference(cfg)
    ref_cfg = reference_config(cfg)
    key = weights.seed_key(seed)
    fwd = ref.make_forward(ref_cfg)
    rows = check.pack(placed, rows_n, width)
    got = check.served_gaps(fwd, key, rows, vocab)
    gaps = got["gaps"]
    due = serve.in_window(res)
    unanswered = sum(1 for x in due if not x.times
                     and x.arrival.due < seconds - 5.0)
    limit = cfg["check"]["max_logit_gap"]
    compared = {
        "worst_logit_gap": {"value": float(gaps.max()) if len(gaps)
                            else float("inf"), "limit": limit},
        "tokens_out_of_vocab": {"value": got["out_of_vocab"], "limit": 0},
        "unanswered_requests": {"value": unanswered, "limit": 0},
    }
    correct = judge(compared)
    fin = [f for _, _, f in placed if f.finished]
    new = [f for _, _, f in placed if f.admitted_in_window]
    say(f"check: {len(placed)} requests ({len(fin)} finished, {len(new)} "
        f"admitted in the window, {sum(len(f.out) for f in new)} of their "
        f"tokens) against the float32 reference; gaps: {gap_stats(gaps)}")
    ctl = None
    if control:
        cg = check.control_gaps(fwd, ref.make_forward(ref_cfg, "fp8"), key,
                                rows)
        ctl = dict(compared, worst_logit_gap={
            "value": float(cg.max()) if len(cg) else float("inf"),
            "limit": limit})
        say(f"control (reference in float8) gaps: {gap_stats(cg)}")
    wit = None
    if witness:
        wg = check.served_gaps(ref.make_forward(dict(ref_cfg, **witness)),
                               key, rows, vocab)["gaps"]
        wit = float(wg.max()) if len(wg) else float("inf")
        say(f"witness {witness} gaps: {gap_stats(wg)}")
    say(f"check s: {time.perf_counter() - t_check:.2f}")

    # -- numbers ---------------------------------------------------------------
    n_tok = serve.tokens_in_window(res)
    in_window_compiles = log.in_phase("window") if log is not None else []
    in_win = [s for s in res.steps if s.t0 >= 0]
    longest = max((s.t1 - s.t0 for s in in_win), default=0.0)
    say(f"window: {window_s:.4f} s, {n_tok} tokens, {len(in_win)} steps "
        f"(longest {1e3 * longest:.1f} ms), {len(due)} requests due; "
        f"compiles in window: "
        f"{len(in_window_compiles)} {in_window_compiles[:5]}")
    values = {
        "setup_s": (setup_s, "s"),
        "decode_tok_s": (n_tok / window_s, "tokens/s"),
        "itl_p95_ms": (percentile(serve.gaps_ms(res), 95), "ms"),
    }
    metrics = {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    out = {"correct": correct, "attempted": len(res.requests),
           "failed": unanswered}
    if not trace:
        for m in spec.end_to_end(workload):
            v, unit = values[m["name"]]
            metrics[m["name"]] = {"value": v, "unit": unit}
    else:
        tr = trace_mod.load(trace_mod.find_xplane(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
        if not tr.ops:
            raise RuntimeError("the trace holds no device operations")
        lo, hi = trace_mod.window_of(tr, "bench.window")
        busy = sum(trace_mod.busy_seconds(ops, lo, hi)
                   for ops in tr.ops) / len(tr.ops)
        device["busy_s"] = busy
        device["window_s"] = hi - lo
        ctx = SimpleNamespace(
            cfg=cfg, mix=mix_spec, peak=peak, result=res, trace=tr,
            traced=(lo, hi), busy_s=busy, flops=flops, serve=serve,
            traced_steps=[s for s in res.steps
                          if traced["lo"] <= s.t0 and s.t1 <= traced["hi"]])
        for m in spec.per_layer(workload):
            v = spec.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["breakdown"] = trace_mod.breakdown(tr, lo, hi)
    out["metrics"] = metrics
    out["device"] = device
    if ctl is not None:
        out["control"] = {"correct": judge(ctl), "compared": ctl}
    if wit is not None:
        out["witness_worst_gap"] = wit
    out["compared"] = compared
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"FAIL: no program (src/repro) beside the benchmark in {ROOT}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(src))
    # libtpu would log to /tmp/tpu_logs, a path shared between runs
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    spec = Spec()
    try:
        device_info(jax, spec.workload(args.workload)["chips"], False)
    except NoChip as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench.compile_log import CompileLog
    log = CompileLog(jax)
    print(f"compile cache: {cache_dir}", flush=True)
    out = run_cell(spec, args.workload, args.seed, args.seconds,
                   bool(args.trace), log=log)
    for name, v in out["compared"].items():
        print(f"compared {name}: {v['value']} limit {v['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
