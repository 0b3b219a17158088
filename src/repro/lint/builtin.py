"""Built-in lint rules: the paper's structural claims plus the bug
classes this repo has actually shipped, each as a checkable invariant.

  NoForbiddenMatmul       merged (qp) programs compile with EXACTLY two
                          fewer ``dot_general``s than their unmerged
                          source — the Q and P projections are gone from
                          the program, not just from the param tree
                          ("KV-weights are all you need", the paper's
                          whole claim, per registered combo)
  NoOversizedBuffer       paged prefill materializes NO max_len-sized
                          intermediate (the PR 3 direct-to-page win,
                          protected against regression)
  DonationEffective       declared donations really alias an output in
                          the lowered module — an aval mismatch silently
                          downgrades donation to a full pool copy per
                          step, the kind of perf regression nothing
                          functional ever catches
  NoPerLayerPoolSlice     paged decode and chunk programs hold no value
                          of one layer's pool shape (NB, bs, Hkv, D):
                          they carry the (L, …) stack through the layer
                          scan and address it by layer index, where a
                          per-layer xs/ys scan slices each layer's pool
                          out and stacks it back — XLA copies both, and
                          the whole stack, every step
  NoDtypePromotionDrift   no cache-sized buffer appears at a float dtype
                          wider than the cache dtype — an accidental
                          fp32 shadow of a bf16 pool doubles the HBM the
                          paged pool exists to save (kernels' explicit
                          f32 TILE accumulators are by design and pass)
  NoHostTransferInStepLoop  the decode step program contains no host
                          callback / infeed primitive — one host
                          round-trip in the per-token loop serializes
                          every stream in the batch
  NoHostTransferInObsHooks  re-tracing a serving program with the
                          repro.obs observer ACTIVE adds zero
                          host-transfer/callback primitives — the obs
                          subsystem's zero-overhead-on-device guarantee
                          (hooks are host-side; nothing may stage a
                          callback into the compiled program)
  NoDequantizedPoolBuffer paged_q8 programs never materialize a
                          pool-shaped buffer wider than int8 — dequant
                          happens per attention TILE (inside the kernel
                          loop) or per gathered table row, never as a
                          full-precision shadow of the int8 pool, which
                          would spend the exact HBM the quantized pool
                          exists to save
"""
from __future__ import annotations

from typing import List

import jax.numpy as jnp

from repro.lint import walker
from repro.lint.rules import Finding, LintRule, LintTarget, register_rule


class NoForbiddenMatmul(LintRule):
    """Merged programs must drop exactly the wq and wp matmuls.

    The qp-merged rewrite of a model differs from its unmerged source by
    the Q and P projections per (scanned) layer body and nothing else, so
    the merged program must count exactly TWO fewer ``dot_general``
    equations than the same-phase/cache/impl program of the source model.
    Counting the delta (not absolute counts) keeps the rule valid as
    layers gain matmuls; requiring equality (not <=) catches a "merged"
    route that silently re-projects Q somewhere else."""

    name = "NoForbiddenMatmul"
    description = ("merged program has exactly two fewer dot_generals "
                   "than its unmerged source")

    def applies(self, t: LintTarget) -> bool:
        return t.style == "merged" and t.source_jaxpr is not None

    def check(self, t: LintTarget) -> List[Finding]:
        n_src = walker.count_primitive(t.source_jaxpr, "dot_general")
        n_merged = walker.count_primitive(t.jaxpr, "dot_general")
        if n_merged != n_src - 2:
            return [self.finding(
                t, f"merged program has {n_merged} dot_generals, unmerged "
                   f"source has {n_src}; expected exactly {n_src - 2} "
                   f"(wq and wp eliminated, nothing else)",
                detail={"merged": n_merged, "source": n_src})]
        return []


class NoOversizedBuffer(LintRule):
    """Paged prefill must not materialize a max_len-sized buffer.

    Direct-to-page prefill's point is that the program's sequence extents
    are bounded by the prompt bucket, never by the serving max_len; one
    max_len-sized intermediate resurrects the worst-case allocation the
    paged pool exists to delete.  The sweep picks a ``max_len`` that
    collides with no model/pool dimension, so any hit is real."""

    name = "NoOversizedBuffer"
    description = "no max_len-sized intermediate in paged prefill"

    def applies(self, t: LintTarget) -> bool:
        return t.phase == "prefill" \
            and t.cache_kind in ("paged", "paged_q8") \
            and t.max_len is not None

    def check(self, t: LintTarget) -> List[Finding]:
        offending = walker.avals_with_dim(t.jaxpr, t.max_len)
        if offending:
            shapes = sorted({tuple(a.shape) for a in offending})
            return [self.finding(
                t, f"{len(offending)} max_len({t.max_len})-sized buffers "
                   f"in the program, e.g. {shapes[:3]}",
                detail={"max_len": t.max_len,
                        "shapes": [list(s) for s in shapes[:10]]})]
        return []


class DonationEffective(LintRule):
    """Declared donations must be USED in the lowered module.

    ``donate_argnums`` is a request, not a guarantee: when no output
    matches a donated input's aval, jax silently drops the donation and
    the step copies the whole pool every token.  Effective donation shows
    up as a ``tf.aliasing_output`` attribute on the argument in the
    lowered StableHLO — this rule demands it for every donated leaf."""

    name = "DonationEffective"
    description = "every donated arg aliases an output in the lowered module"

    def applies(self, t: LintTarget) -> bool:
        return t.lowered is not None and bool(t.donated_flat)

    def check(self, t: LintTarget) -> List[Finding]:
        attrs = walker.stablehlo_arg_attrs(t.lowered)
        dead = [i for i in t.donated_flat
                if i >= len(attrs) or attrs[i] is None
                or "tf.aliasing_output" not in attrs[i]]
        if dead:
            return [self.finding(
                t, f"{len(dead)}/{len(t.donated_flat)} donated args are NOT "
                   f"aliased to an output (flat positions {dead[:8]}) — the "
                   f"donation silently became a copy",
                detail={"dead_flat_positions": dead,
                        "declared": list(t.donated_flat)})]
        return []


class NoPerLayerPoolSlice(LintRule):
    """Paged decode and chunk programs address the pool stack in place.

    The pools are (L, NB, bs, Hkv, D) stacks shared by every layer.  A
    layer scan that takes each layer's pool as its xs and stacks the
    updated pools as its ys makes XLA copy every layer's pool out and
    back, and the whole stack once more, on every step — about half the
    decode step's device time at Mistral-7B widths, none of it attention
    work.  The paged programs carry the stack through the scan and hand
    the write and the kernel the layer index instead, so no value of one
    layer's pool shape (at any dtype) appears anywhere in the program.
    Gathered table rows are (…, MB, bs, Hkv, D) and pass."""

    name = "NoPerLayerPoolSlice"
    description = ("no value of one layer's pool shape in a paged decode "
                   "or chunk program")

    def applies(self, t: LintTarget) -> bool:
        return t.phase in ("decode", "chunk") \
            and t.cache_kind in ("paged", "paged_q8") \
            and bool(t.cache_shapes)

    def check(self, t: LintTarget) -> List[Finding]:
        layer = {tuple(s[1:]) for s in t.cache_shapes if len(s) == 5}
        hits = [a for a in walker.iter_avals(t.jaxpr)
                if hasattr(a, "shape") and tuple(a.shape) in layer]
        if hits:
            seen = sorted({(tuple(a.shape), str(a.dtype)) for a in hits})
            return [self.finding(
                t, f"{len(hits)} values of one layer's pool shape, e.g. "
                   f"{seen[:3]} — the layer scan slices the pool stack "
                   f"instead of addressing it by layer index",
                detail={"hits": [[list(s), d] for s, d in seen[:10]]})]
        return []


def _wider_float(a, than) -> bool:
    try:
        return (jnp.issubdtype(a, jnp.floating)
                and jnp.issubdtype(than, jnp.floating)
                and jnp.finfo(a).bits > jnp.finfo(than).bits)
    except TypeError:
        return False


class NoDtypePromotionDrift(LintRule):
    """No cache-sized buffer at a float dtype wider than the cache dtype.

    The kernels deliberately accumulate f32 over TILES (explicit
    ``preferred_element_type`` / scratch refs) — that is not drift.  Drift
    is a whole cache/pool-shaped array appearing at fp32 when the cache is
    bf16: a silent 2x of exactly the HBM the merged layout and the paged
    pool are engineered to save.  The rule scans every aval (kernel bodies
    included) for cache-leaf shapes at a wider float dtype.  Only live at
    sub-fp32 cache dtypes, which is why the sweep traces at bfloat16."""

    name = "NoDtypePromotionDrift"
    description = "no cache-shaped buffer wider than the cache dtype"

    def applies(self, t: LintTarget) -> bool:
        return bool(t.cache_shapes) and t.cache_dtype is not None

    def check(self, t: LintTarget) -> List[Finding]:
        shapes = {tuple(s) for s in t.cache_shapes}
        hits = [a for a in walker.iter_avals(t.jaxpr)
                if hasattr(a, "shape") and hasattr(a, "dtype")
                and tuple(a.shape) in shapes
                and _wider_float(a.dtype, t.cache_dtype)]
        if hits:
            seen = sorted({(tuple(a.shape), str(a.dtype)) for a in hits})
            return [self.finding(
                t, f"{len(hits)} cache-shaped buffers wider than the "
                   f"{jnp.dtype(t.cache_dtype).name} cache, e.g. {seen[:3]}",
                detail={"cache_dtype": jnp.dtype(t.cache_dtype).name,
                        "hits": [[list(s), d] for s, d in seen[:10]]})]
        return []


#: primitives whose presence in a decode step means a host round-trip
#: (or an effect pinned to the host) inside the per-token loop
HOST_TRANSFER_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "callback", "infeed", "outfeed", "host_local_array_to_global_array",
})


class NoHostTransferInStepLoop(LintRule):
    """The decode step program must be host-silent.

    Every serving stream in the batch shares one jitted step; a callback
    or infeed primitive anywhere in it (including a kernel body or a
    debug print left behind) forces a device->host->device round-trip per
    decoded token, serializing the whole batch on host latency."""

    name = "NoHostTransferInStepLoop"
    description = "no callback/infeed primitive in the decode step program"

    def applies(self, t: LintTarget) -> bool:
        return t.phase == "decode"

    def check(self, t: LintTarget) -> List[Finding]:
        bad = sorted({eqn.primitive.name for eqn in walker.iter_eqns(t.jaxpr)
                      if eqn.primitive.name in HOST_TRANSFER_PRIMITIVES})
        if bad:
            return [self.finding(
                t, f"host-transfer primitives in the step program: {bad}",
                detail={"primitives": bad})]
        return []


def _host_transfer_counts(jaxpr) -> dict:
    counts: dict = {}
    for eqn in walker.iter_eqns(jaxpr):
        n = eqn.primitive.name
        if n in HOST_TRANSFER_PRIMITIVES:
            counts[n] = counts.get(n, 0) + 1
    return counts


class NoHostTransferInObsHooks(LintRule):
    """Instrumentation must never reach into the compiled program.

    The obs subsystem's discipline is host-side-only hooks at the
    engine's python seams; the temptation it guards against is a kernel
    or forward path consulting ``repro.obs.get_active()`` and staging a
    ``debug_print``/callback when observability is on — which would turn
    "obs on" into a per-token host round-trip.  The sweep re-traces every
    serving program with an ACTIVE observer (``obs.activated(...)``) into
    ``instrumented_jaxpr``; this rule diffs host-transfer primitive
    counts against the uninstrumented trace and demands ZERO new ones.
    (Count-diff, not absence: a program legitimately carrying such a
    primitive is ``NoHostTransferInStepLoop``'s business, not ours.)"""

    name = "NoHostTransferInObsHooks"
    description = ("active-observer re-trace adds zero host-transfer "
                   "primitives to the serving program")

    def applies(self, t: LintTarget) -> bool:
        return t.instrumented_jaxpr is not None

    def check(self, t: LintTarget) -> List[Finding]:
        base = _host_transfer_counts(t.jaxpr)
        instr = _host_transfer_counts(t.instrumented_jaxpr)
        new = {n: c - base.get(n, 0) for n, c in instr.items()
               if c > base.get(n, 0)}
        if new:
            return [self.finding(
                t, f"instrumented program stages host-transfer primitives "
                   f"the plain program does not: {new} — obs hooks must "
                   f"stay host-side",
                detail={"new": new, "base": base, "instrumented": instr})]
        return []


class NoDequantizedPoolBuffer(LintRule):
    """paged_q8 programs must never hold a full-precision pool shadow.

    The int8 pool's whole point is 2x (vs bf16) / 4x (vs fp32) HBM on
    exactly the largest buffers in a serve; the tempting bug is a
    convenience ``pool.astype(f32)`` somewhere in a forward path, which
    silently materializes the very buffer the format deleted.  Dequant
    is only legal at TILE granularity (inside the kernel grid loop) or
    on table-GATHERED rows (bounded by the request's live pages, not the
    pool) — so no aval in a paged_q8 program may have a pool shape
    (layer-stacked OR per-layer sliced, both are in ``cache_shapes``) at
    any dtype wider than one byte.  Itemsize — not float-ness — is the
    test: an int32 shadow would be just as fatal."""

    name = "NoDequantizedPoolBuffer"
    description = ("no pool-shaped buffer wider than int8 in a paged_q8 "
                   "program")

    def applies(self, t: LintTarget) -> bool:
        return t.cache_kind == "paged_q8" and bool(t.cache_shapes)

    def check(self, t: LintTarget) -> List[Finding]:
        shapes = {tuple(s) for s in t.cache_shapes}
        hits = [a for a in walker.iter_avals(t.jaxpr)
                if hasattr(a, "shape") and hasattr(a, "dtype")
                and tuple(a.shape) in shapes
                and jnp.dtype(a.dtype).itemsize > 1]
        if hits:
            seen = sorted({(tuple(a.shape), str(a.dtype)) for a in hits})
            return [self.finding(
                t, f"{len(hits)} pool-shaped buffers wider than int8 in a "
                   f"paged_q8 program, e.g. {seen[:3]} — a dequantized "
                   f"shadow of the quantized pool",
                detail={"hits": [[list(s), d] for s, d in seen[:10]]})]
        return []


BUILTIN_RULES = (NoForbiddenMatmul(), NoOversizedBuffer(),
                 DonationEffective(), NoPerLayerPoolSlice(),
                 NoDtypePromotionDrift(),
                 NoHostTransferInStepLoop(), NoHostTransferInObsHooks(),
                 NoDequantizedPoolBuffer())

for _rule in BUILTIN_RULES:
    register_rule(_rule)
