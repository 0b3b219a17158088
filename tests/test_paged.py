"""Paged KV-cache subsystem: kernels vs oracles, allocator/prefix-sharing
semantics, and the paged engine's greedy token-equality with the dense
engine and the full-sequence oracle (mixed-length MHA+GQA workloads,
xla and pallas routes, prefix sharing with copy-on-write, admission
control and preemption)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.kernels import ops, ref
from repro.kernels.decode_attention import pages_per_block
from repro.kernels.paging import paged_ring_active
from repro.models import (DecodeCache, PagedDecodeCache, PagedQ8DecodeCache,
                          forward_seq, forward_step, init_params)
from repro.serving import Engine, ServeConfig
from repro.serving.paged_kv_cache import BlockAllocator, PagedCacheManager


# ---------------------------------------------------------------------------
# kernels vs oracles
# ---------------------------------------------------------------------------

def _rand_tables(rng, B, MB, NB, bs, lens, shielded=()):
    """Distinct physical pages per slot covering each slot's current
    position (qpos = lens[b]), rest unmapped; ``shielded`` slots' rows
    are then masked to -1, as the manager shields a mid-prefill slot."""
    bt = np.full((B, MB), -1, np.int32)
    perm = rng.permutation(NB)
    ptr = 0
    for b, L in enumerate(lens):
        for j in range(min(MB, (L + bs) // bs)):  # covers position L
            bt[b, j] = perm[ptr]
            ptr += 1
    bt[list(shielded), :] = -1
    return jnp.asarray(bt)


def _lens(rng, lens, B, MB, bs):
    if lens is None:
        return [int(x) for x in rng.randint(1, MB * bs - 1, size=B)]
    assert len(lens) == B
    return list(lens)


# at bs 16 the kernel gathers P pages a block: lengths at the block edges
# 1, P·bs - 1, P·bs, P·bs + 1 and MB·bs - 1, and one shielded slot (its
# table all -1); MB = 4P walks four blocks, MB = 2.5P ends in a ragged one
P16 = pages_per_block(16, 1 << 20)
MB_4P, MB_RAGGED = 4 * P16, 5 * P16 // 2


def _edges(mb, bs=16):
    t = P16 * bs
    return (1, t - 1, t, t + 1, mb * bs - 1, mb * bs // 2)


EDGES_4P, EDGES_RAGGED = _edges(MB_4P), _edges(MB_RAGGED)
NB_4P, NB_RAGGED = 3 * MB_4P, 3 * MB_RAGGED  # pages for all six slots
SHIELD = (5,)


# the kernels read one layer of a stacked (L, NB, bs, Hkv, D) pool: each
# case reads the last of three layers under its own id, and the first
# under its id + "-layer0"; the other layers hold other draws
N_LAYERS, LAST = 3, 2


def _each_layer(cases):
    return [pytest.param(*c.values, layer, marks=c.marks,
                         id=c.id if layer == LAST else f"{c.id}-layer0")
            for c in cases for layer in (LAST, 0)]


def _stack(pool, layer, key):
    """``pool`` as layer ``layer`` of an N_LAYERS stack whose other layers
    hold other draws of the same kind, so a read of another layer shows."""
    shape = (N_LAYERS, *pool.shape)
    if pool.dtype == jnp.int8:
        others = jax.random.randint(key, shape, -127, 128, jnp.int32)
    else:
        others = jax.random.normal(key, shape, jnp.float32) * 2.0 + 0.5
    return others.astype(pool.dtype).at[layer].set(pool)


def _close(out, want, dtype):
    tol = 2e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize(
    "dtype,B,Hq,Hkv,D,NB,bs,MB,win,lens,shielded,layer", _each_layer([
    pytest.param(jnp.float32, 3, 4, 2, 64, 16, 16, 4, 0, None, (),
                 id="float32-3-4-2-64-16-16-4-0"),
    pytest.param(jnp.bfloat16, 3, 4, 2, 64, 16, 16, 4, 0, None, (),
                 id="bfloat16-3-4-2-64-16-16-4-0"),
    pytest.param(jnp.float32, 2, 8, 1, 32, 12, 8, 4, 0, None, (),  # MQA
                 id="float32-2-8-1-32-12-8-4-0"),
    pytest.param(jnp.float32, 2, 4, 4, 16, 14, 8, 6, 11, None, (),
                 id="float32-2-4-4-16-14-8-6-11"),  # MHA + sliding window
    pytest.param(jnp.float32, 6, 8, 2, 32, NB_4P, 16, MB_4P, 0, EDGES_4P,
                 SHIELD, id="block-edges-MB4P-float32"),
    pytest.param(jnp.bfloat16, 6, 8, 2, 32, NB_4P, 16, MB_4P, 0, EDGES_4P,
                 SHIELD, id="block-edges-MB4P-bfloat16"),
    pytest.param(jnp.float32, 6, 8, 2, 32, NB_RAGGED, 16, MB_RAGGED, 0,
                 EDGES_RAGGED, SHIELD, id="block-edges-ragged"),
    pytest.param(jnp.float32, 6, 8, 2, 32, NB_4P, 16, MB_4P, 100, EDGES_4P,
                 (), id="block-edges-MB4P-window100"),
]))
def test_paged_decode_kernel_matches_ref(dtype, B, Hq, Hkv, D, NB, bs, MB, win,
                                         lens, shielded, layer):
    rng = np.random.RandomState(0)
    G = Hq // Hkv
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (B, Hq, D), dtype)
    kp = jax.random.normal(ks[1], (NB, bs, Hkv, D), dtype)
    vp = jax.random.normal(ks[2], (NB, bs, Hkv, D), dtype)
    lens = _lens(rng, lens, B, MB, bs)
    bt = _rand_tables(rng, B, MB, NB, bs, lens, shielded)
    qpos = jnp.asarray(lens, jnp.int32)
    out = ops.decode_attention_paged(
        q, _stack(kp, layer, ks[3]), _stack(vp, layer, ks[4]),
        block_tables=bt, q_position=qpos, layer=layer, sliding_window=win,
        interpret=True)
    want = ref.ref_decode_attention_paged(
        q.reshape(B, Hkv, G, D), kp, vp, bt, qpos,
        sliding_window=win).reshape(B, Hq, D)
    _close(out, want, dtype)
    for b in shielded:  # no live page: zeros, as the kernel always wrote
        assert not np.any(np.asarray(out[b], np.float32))


@pytest.mark.parametrize(
    "dtype,B,Hq,Hkv,D,NB,bs,MB,win,lens,shielded,layer", _each_layer([
    pytest.param(jnp.float32, 3, 4, 2, 64, 16, 16, 4, 0, None, (),
                 id="float32-3-4-2-64-16-16-4-0"),
    pytest.param(jnp.bfloat16, 2, 4, 1, 32, 12, 8, 4, 0, None, (),  # MQA
                 id="bfloat16-2-4-1-32-12-8-4-0"),
    pytest.param(jnp.float32, 2, 4, 4, 16, 14, 8, 6, 11, None, (),
                 id="float32-2-4-4-16-14-8-6-11"),  # MHA + sliding window
    pytest.param(jnp.bfloat16, 6, 8, 2, 32, NB_4P, 16, MB_4P, 0, EDGES_4P,
                 SHIELD, id="block-edges-MB4P-bfloat16"),
    pytest.param(jnp.float32, 6, 8, 2, 32, NB_RAGGED, 16, MB_RAGGED, 0,
                 EDGES_RAGGED, SHIELD, id="block-edges-ragged"),
]))
def test_paged_merged_kernel_matches_ref(dtype, B, Hq, Hkv, D, NB, bs, MB, win,
                                         lens, shielded, layer):
    rng = np.random.RandomState(1)
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    u = jax.random.normal(ks[0], (B, Hq * D), dtype)
    kp = jax.random.normal(ks[1], (NB, bs, Hkv, D), dtype)
    vp = jax.random.normal(ks[2], (NB, bs, Hkv, D), dtype)
    lens = _lens(rng, lens, B, MB, bs)
    bt = _rand_tables(rng, B, MB, NB, bs, lens, shielded)
    qpos = jnp.asarray(lens, jnp.int32)
    out = ops.decode_attention_paged_merged(
        u, _stack(kp, layer, ks[3]), _stack(vp, layer, ks[4]),
        block_tables=bt, q_position=qpos, layer=layer, n_kv_heads=Hkv,
        sliding_window=win, interpret=True)
    want = ref.ref_decode_attention_paged_merged(
        u, kp, vp, bt, qpos, n_kv_heads=Hkv, sliding_window=win)
    _close(out, want, dtype)


def _q8_pool(key, NB, bs, Hkv, D):
    kq, ks = jax.random.split(key)
    pool = jax.random.randint(kq, (NB, bs, Hkv, D), -127, 128,
                              jnp.int32).astype(jnp.int8)
    return pool, jax.random.uniform(ks, (NB, Hkv), jnp.float32, 0.01, 0.05)


def _ring_tables(rng, B, R, NB, bs, lens):
    """Ring-addressed tables: absolute block b of a slot lives at table
    slot b % R, the last R blocks up to the query's mapped."""
    bt = np.full((B, R), -1, np.int32)
    perm = rng.permutation(NB)
    ptr = 0
    for b, L in enumerate(lens):
        for blk in range(max(0, L // bs - R + 1), L // bs + 1):
            bt[b, blk % R] = perm[ptr]
            ptr += 1
    return jnp.asarray(bt)


@pytest.mark.parametrize("style", ["generic", "merged"])
@pytest.mark.parametrize("table,layer", _each_layer(
    [pytest.param(t, id=t) for t in ("block-edges", "ring")]))
def test_paged_q8_kernel_matches_ref(style, table, layer):
    """The int8 wrappers at the block edges (MB = 4P, one shielded slot)
    and on a ring-addressed table (window 400 < MB·bs: R = 26 slots, a
    full block and a ragged one), against the dequantize-then-attend
    oracle on the layer read."""
    rng = np.random.RandomState(3)
    B, Hq, Hkv, D, NB, bs = 6, 8, 2, 32, NB_4P, 16
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(k1, (B, Hq, D), jnp.float32)
    (kp, ksc), (vp, vsc) = _q8_pool(k2, NB, bs, Hkv, D), \
        _q8_pool(k3, NB, bs, Hkv, D)
    stacks = [_stack(a, layer, k) for a, k in
              zip((kp, vp, ksc, vsc), jax.random.split(k4, 4))]
    if table == "ring":
        win, lens = 400, [1, 255, 257, 420, 1500, 3000]
        R = paged_ring_active(win, bs, 26)
        assert R == 26 and R % P16
        bt = _ring_tables(rng, B, R, NB, bs, lens)
    else:
        win, lens, R = 0, list(EDGES_4P), 0
        bt = _rand_tables(rng, B, MB_4P, NB, bs, lens, SHIELD)
    qpos = jnp.asarray(lens, jnp.int32)
    k_st, v_st, ks_st, vs_st = stacks
    if style == "merged":
        out = ops.decode_attention_paged_q8_merged(
            q.reshape(B, Hq * D), k_st, v_st, k_scale=ks_st, v_scale=vs_st,
            block_tables=bt, q_position=qpos, layer=layer, n_kv_heads=Hkv,
            sliding_window=win, interpret=True).reshape(B, Hq, D)
    else:
        out = ops.decode_attention_paged_q8(
            q, k_st, v_st, k_scale=ks_st, v_scale=vs_st, block_tables=bt,
            q_position=qpos, layer=layer, sliding_window=win,
            interpret=True)
    want = ref.ref_decode_attention_paged_q8(
        q.reshape(B, Hkv, Hq // Hkv, D), kp, vp, ksc, vsc, bt, qpos,
        sliding_window=win, ring_blocks=R).reshape(B, Hq, D)
    _close(out, want, jnp.float32)


@pytest.mark.parametrize("pool,layer", _each_layer(
    [pytest.param(p, id=p) for p in ("bf16", "q8")]))
def test_paged_kernel_never_reads_unmapped_pages(pool, layer):
    """Every page of the layer read that no table maps holds NaN (its
    scales too, for an int8 pool), and so does every other layer of the
    stack (its scales, for an int8 pool): the output is finite and equals
    the oracle's on the clean layer, so neither a dead page nor another
    layer reaches the result."""
    rng = np.random.RandomState(4)
    B, Hq, Hkv, D, NB, bs, MB = 6, 8, 2, 32, NB_4P, 16, MB_4P
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(k1, (B, Hq, D), jnp.float32)
    bt = _rand_tables(rng, B, MB, NB, bs, EDGES_4P, SHIELD)
    qpos = jnp.asarray(EDGES_4P, jnp.int32)
    dead = np.ones(NB, bool)
    dead[np.asarray(bt)[np.asarray(bt) >= 0]] = False
    dead = jnp.asarray(dead)
    if pool == "q8":
        (kp, ksc), (vp, vsc) = _q8_pool(k2, NB, bs, Hkv, D), \
            _q8_pool(k3, NB, bs, Hkv, D)
        nan_s = lambda s: _nan_layers(  # noqa: E731
            jnp.where(dead[:, None], jnp.nan, s), layer)
        out = ops.decode_attention_paged_q8(
            q, _stack(kp, layer, k1), _stack(vp, layer, k1),
            k_scale=nan_s(ksc), v_scale=nan_s(vsc), block_tables=bt,
            q_position=qpos, layer=layer, interpret=True)
        want = ref.ref_decode_attention_paged_q8(
            q.reshape(B, Hkv, Hq // Hkv, D), kp, vp, ksc, vsc, bt, qpos)
    else:
        kp = jax.random.normal(k2, (NB, bs, Hkv, D), jnp.bfloat16)
        vp = jax.random.normal(k3, (NB, bs, Hkv, D), jnp.bfloat16)
        nan_p = lambda p: _nan_layers(jnp.where(  # noqa: E731
            dead[:, None, None, None], jnp.nan, p).astype(p.dtype), layer)
        out = ops.decode_attention_paged(
            q.astype(jnp.bfloat16), nan_p(kp), nan_p(vp), block_tables=bt,
            q_position=qpos, layer=layer, interpret=True)
        want = ref.ref_decode_attention_paged(
            q.astype(jnp.bfloat16).reshape(B, Hkv, Hq // Hkv, D), kp, vp,
            bt, qpos)
    assert np.all(np.isfinite(np.asarray(out, np.float32)))
    _close(out, want.reshape(B, Hq, D),
           jnp.bfloat16 if pool == "bf16" else jnp.float32)


def _nan_layers(pool, layer):
    """``pool`` as layer ``layer`` of an N_LAYERS stack of NaN."""
    return jnp.full((N_LAYERS, *pool.shape), jnp.nan,
                    pool.dtype).at[layer].set(pool)


def test_paged_ref_matches_dense_ref():
    """The paged oracle itself is just a gather in front of the dense
    oracle: densify manually and cross-check."""
    rng = np.random.RandomState(2)
    B, Hq, Hkv, D, NB, bs, MB = 2, 4, 2, 16, 10, 4, 4
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, Hkv, Hq // Hkv, D), jnp.float32)
    kp = jax.random.normal(ks[1], (NB, bs, Hkv, D), jnp.float32)
    vp = jax.random.normal(ks[2], (NB, bs, Hkv, D), jnp.float32)
    lens = [7, 13]
    bt = _rand_tables(rng, B, MB, NB, bs, lens)
    qpos = jnp.asarray(lens, jnp.int32)
    got = ref.ref_decode_attention_paged(q, kp, vp, bt, qpos)
    k = ref.ref_paged_gather(kp, bt).transpose(0, 2, 1, 3)
    v = ref.ref_paged_gather(vp, bt).transpose(0, 2, 1, 3)
    kv_pos = ref.ref_paged_positions(bt, bs)
    want = ref.ref_decode_attention(q, k, v, kv_pos, qpos[:, None])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


# ---------------------------------------------------------------------------
# the decode step writes each layer's row into the pool stack in place
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("pool", ["bf16", "q8"])
def test_forward_step_writes_each_layer_row_in_place(pool, impl):
    """One paged decode step over a three-layer stack of random pages:
    layer l's new K and V rows lie at [l, page, offset] of each slot's
    page and equal the rows a dense cache of the same contents writes;
    the shielded slot (table unmapped) writes nothing, and every other
    byte of the stack is the input's.  An int8 page written to may be
    rescaled whole (the monotone scale merge), so there the new row and
    the live rows before it are held to their values dequantized; every
    other page, and its scales, is the input's byte for byte."""
    q8 = pool == "q8"
    cfg = reduce_config(get_config("mistral-7b")).with_(
        n_layers=N_LAYERS, n_kv_heads=2, sliding_window=0,
        dtype="float32" if q8 else "bfloat16")
    params = init_params(jax.random.PRNGKey(0), cfg)
    B, bs, MB, NB = 3, 4, 4, 12
    Hkv, Dh = cfg.n_kv_heads, cfg.d_head
    lens = np.array([5, 8, 2], np.int32)  # offsets 1, 0 (a fresh page), 2
    rng = np.random.RandomState(5)
    bt = _rand_tables(rng, B, MB, NB, bs, lens, shielded=(2,))
    pages = np.asarray(bt)[[0, 1], lens[:2] // bs]
    offs = lens[:2] % bs
    k1, k2, k3, k4, k5 = jax.random.split(jax.random.PRNGKey(5), 5)
    shape = (N_LAYERS, NB, bs, Hkv, Dh)
    tok = jax.random.randint(k5, (B,), 0, cfg.vocab_size, jnp.int32)
    if q8:
        kp, vp = (jax.random.randint(k, shape, -127, 128, jnp.int32)
                  .astype(jnp.int8) for k in (k1, k2))
        ks, vs = (jax.random.uniform(k, shape[:2] + (Hkv,), jnp.float32,
                                     0.01, 0.05) for k in (k3, k4))
        cache = PagedQ8DecodeCache(kp, vp, ks, vs, bt, jnp.asarray(lens))
        kf = kp.astype(jnp.float32) * ks[:, :, None, :, None]
        vf = vp.astype(jnp.float32) * vs[:, :, None, :, None]
    else:
        kp, vp = (jax.random.normal(k, shape, jnp.bfloat16) for k in (k1, k2))
        cache = PagedDecodeCache(kp, vp, bt, jnp.asarray(lens))
        kf, vf = kp, vp
    _, new = forward_step(params, cfg, tok, cache, impl=impl)

    # the dense cache of the same contents, and the rows it writes
    S = MB * bs
    tbl = np.maximum(np.asarray(bt), 0)

    def dense_of(pf):
        return pf[:, tbl].reshape(N_LAYERS, B, S, Hkv, Dh)

    pos = np.arange(S)[None, :]
    kv_pos = np.where((pos < lens[:, None])
                      & np.repeat(np.asarray(bt) >= 0, bs, axis=1), pos, -1)
    dense = DecodeCache(dense_of(kf), dense_of(vf), jnp.asarray(kv_pos),
                        jnp.asarray(lens), None, None, None)
    _, want = forward_step(params, cfg, tok, dense, impl="xla")

    written = np.zeros((N_LAYERS, NB), bool)
    written[:, pages] = True
    row = np.zeros(shape[:3], bool)
    row[:, pages, offs] = True
    for got, old, old_f, rows, scale in (
            (new.k, kp, kf, want.k, getattr(new, "k_scale", None)),
            (new.v, vp, vf, want.v, getattr(new, "v_scale", None))):
        got, old, old_f = map(np.asarray, (got, old, old_f))
        rows = np.asarray(rows, np.float32)
        keep = np.broadcast_to(~written[:, :, None], row.shape) if q8 \
            else ~row
        np.testing.assert_array_equal(got[keep], old[keep])
        for b, (pg, off) in enumerate(zip(pages, offs)):
            want_row = rows[:, b, lens[b]]
            if not q8:
                _close(got[:, pg, off], want_row, jnp.bfloat16)
                continue
            # the page's live rows and the new one, dequantized: within
            # one quantization step of the old values and the dense row
            sc = np.asarray(scale)[:, pg][:, None, :, None]  # (L,1,Hkv,1)
            live = got[:, pg, :off + 1].astype(np.float32) * sc
            want_live = np.concatenate(
                [old_f[:, pg, :off], want_row[:, None]], axis=1)
            assert np.all(np.abs(live - want_live) <= sc), \
                (b, float(np.max(np.abs(live - want_live) / sc)))
    if q8:
        for got, old in ((new.k_scale, ks), (new.v_scale, vs)):
            np.testing.assert_array_equal(np.asarray(got)[~written],
                                          np.asarray(old)[~written])


# ---------------------------------------------------------------------------
# allocator / manager semantics
# ---------------------------------------------------------------------------

def test_block_allocator_refcounts_and_exhaustion():
    a = BlockAllocator(4)
    ids = a.alloc(3)
    assert ids is not None and a.n_free == 1
    assert a.alloc(2) is None, "over-allocation must fail, not wrap"
    a.fork(ids[:2])  # share two pages
    assert a.release(ids) == [ids[2]]  # shared pages stay resident
    assert a.n_free == 2
    assert sorted(a.release(ids[:2])) == sorted(ids[:2])
    assert a.n_free == 4


def test_manager_prefix_sharing_and_release():
    cfg = reduce_config(get_config("llama3.2-1b"))
    m = PagedCacheManager(cfg, n_slots=3, max_len=32, block_size=8,
                          n_blocks=8)
    toks = np.arange(20) % cfg.vocab_size  # 2 full pages + 1 partial
    assert m.admit(0, toks) == 0  # nothing to share yet
    assert m.allocator.n_used == 3
    assert m.admit(1, toks) == 3  # full chain + exact-prompt partial
    assert m.allocator.n_used == 3, "identical prompt must map 0 new pages"
    # both slots append -> each copy-on-writes the shared partial page
    assert m.ensure_appendable(0) and m.ensure_appendable(1)
    assert m.allocator.n_cow >= 1
    assert m.tables[0, 2] != m.tables[1, 2], "partial page must diverge"
    assert (m.tables[0, :2] == m.tables[1, :2]).all(), "full pages stay shared"
    m.release(0)
    m.release(1)
    # prefix retention: the registered chain (2 full pages + the prompt
    # tail) survives its last sharer, held by the radix tree alone
    assert m.allocator.n_used == 3 == len(m.tree.retained)
    assert m.admit(2, toks) == 3, "a later admit hits the retained chain"
    m.release(2)
    assert m.drop_prefix_cache() == 3
    assert m.allocator.n_used == 0, "all pages must return to the free list"
    assert m.tree.n_pages == 0 and m.tree.n_nodes == 0


def test_manager_admission_control():
    cfg = reduce_config(get_config("llama3.2-1b"))
    m = PagedCacheManager(cfg, n_slots=4, max_len=32, block_size=8,
                          n_blocks=4)
    assert m.admit(0, np.arange(17)) == 0  # 3 pages
    assert m.admit(1, np.arange(50, 60)) is None  # needs 2, 1 free: defer
    assert m.admit(2, np.arange(70, 75)) == 0  # 1 page fits
    with pytest.raises(ValueError):
        m.admit(3, np.arange(40))  # longer than max_len


# ---------------------------------------------------------------------------
# engine end-to-end: paged == dense == oracle
# ---------------------------------------------------------------------------

def _greedy_oracle(params, cfg, prompt, n):
    toks = list(prompt)
    out = []
    for _ in range(n):
        lg, _, _ = forward_seq(params, cfg, jnp.asarray(toks, jnp.int32)[None])
        t = int(jnp.argmax(lg[0, -1, :cfg.vocab_size]))
        out.append(t)
        toks.append(t)
    return out


def _mixed_prompts(vocab, n=5):
    rng = np.random.RandomState(3)
    return [rng.randint(0, vocab, size=(int(s),)).astype(np.int32)
            for s in rng.randint(3, 20, size=n)]


@pytest.mark.parametrize("n_kv,impl", [
    (4, "xla"), (2, "xla"),  # MHA and GQA
    (2, "pallas_interpret"),
])
def test_paged_engine_matches_dense_and_oracle(n_kv, impl):
    """Mixed-length workload through more requests than the paged pool can
    hold at once: every greedy stream must match both the dense engine and
    the full-sequence oracle."""
    cfg = reduce_config(get_config("mistral-7b")).with_(n_kv_heads=n_kv)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = _mixed_prompts(cfg.vocab_size)
    dense = Engine(cfg, params, ServeConfig(n_slots=3, max_len=64), impl=impl)
    paged = Engine(cfg, params,
                   ServeConfig(n_slots=4, max_len=64, cache_kind="paged",
                               block_size=8, n_blocks=16), impl=impl)
    out_d = dense.generate(prompts, max_new_tokens=6)
    out_p = paged.generate(prompts, max_new_tokens=6)
    assert out_p == out_d
    for p, o in zip(prompts, out_p):
        assert o == _greedy_oracle(params, cfg, p, 6), p[:3]
    assert paged.pm.allocator.n_used == len(paged.pm.tree.retained), \
        "drained engine holds only tree-retained prefix pages"
    paged.pm.drop_prefix_cache()
    assert paged.pm.allocator.n_used == 0, "drained engine must free pool"


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_paged_engine_merged_fast_path(impl):
    """QP-merged model through the paged engine: merged fast path + block
    tables must stay token-exact vs the merged full-sequence oracle."""
    from repro.core import merge_skipless
    cfg = reduce_config(get_config("mistral-7b")).with_(
        block_style="skipless", dtype="float32", param_dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    params["embed"]["table"] = params["embed"]["table"] * 50.0
    mparams, mcfg = merge_skipless(params, cfg, "qp")
    eng = Engine(mcfg, mparams,
                 ServeConfig(n_slots=3, max_len=64, cache_kind="paged",
                             block_size=8), impl=impl)
    assert eng.merged_fast_path
    prompts = _mixed_prompts(cfg.vocab_size, n=3)
    outs = eng.generate(prompts, max_new_tokens=6)
    for p, o in zip(prompts, outs):
        assert o == _greedy_oracle(mparams, mcfg, p, 6), p[:3]


def test_paged_prefix_sharing_cow_token_exact():
    """Two concurrent requests with the same prompt share its pages
    (including the partial tail page, diverging via copy-on-write when
    they decode) and still emit the oracle's exact stream."""
    cfg = reduce_config(get_config("llama3.2-1b"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    shared = np.arange(21) % cfg.vocab_size  # 2 full pages + 1 partial
    other = np.arange(7) + 3
    eng = Engine(cfg, params,
                 ServeConfig(n_slots=4, max_len=64, cache_kind="paged",
                             block_size=8))
    outs = eng.generate([shared, shared, other], max_new_tokens=6)
    w = _greedy_oracle(params, cfg, shared, 6)
    assert outs[0] == w and outs[1] == w
    assert outs[2] == _greedy_oracle(params, cfg, other, 6)
    assert eng.pm.allocator.n_shared_hits >= 3, "prompt pages must be shared"
    assert eng.pm.allocator.n_cow >= 1, "append into shared tail must CoW"


def test_paged_admission_and_preemption_token_exact():
    """Pool far smaller than the workload: requests defer (admission
    control) and get preempted mid-decode, then resume — streams must
    stay token-identical to the oracle throughout."""
    cfg = reduce_config(get_config("llama3.2-1b"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = [np.arange(8) + i for i in range(3)]
    eng = Engine(cfg, params,
                 ServeConfig(n_slots=3, max_len=64, cache_kind="paged",
                             block_size=8, n_blocks=7))
    outs = eng.generate(prompts, max_new_tokens=20)
    assert eng.stats["n_preempted"] > 0, "workload sized to force preemption"
    for p, o in zip(prompts, outs):
        assert o == _greedy_oracle(params, cfg, p, 20)


def test_paged_rejects_stateful_families():
    cfg = reduce_config(get_config("mamba2-2.7b"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(AssertionError):
        Engine(cfg, params, ServeConfig(n_slots=2, max_len=32,
                                        cache_kind="paged", block_size=8))


def test_submit_rejects_requests_that_cannot_finish():
    """prompt + max_new_tokens > max_len must fail fast at submit, not
    crash mid-decode when the request walks off its block table (which
    would discard every co-scheduled stream)."""
    cfg = reduce_config(get_config("llama3.2-1b"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    for kind in ("dense", "paged"):
        eng = Engine(cfg, params, ServeConfig(n_slots=2, max_len=32,
                                              cache_kind=kind, block_size=8))
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.generate([np.arange(30) % cfg.vocab_size], max_new_tokens=8)


def test_preemption_preserves_sampling_stream():
    """A preempted+resumed request must continue its PRNG stream where it
    stopped — replaying draws from the start would make sampled output
    depend on co-scheduled traffic (which preemption is a function of)."""
    cfg = reduce_config(get_config("llama3.2-1b"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = [np.arange(8), np.arange(8) + 50]  # target submits last
    roomy = Engine(cfg, params,
                   ServeConfig(n_slots=2, max_len=64, temperature=1.0,
                               seed=5, cache_kind="paged", block_size=8,
                               n_blocks=16))
    out_roomy = roomy.generate(prompts, max_new_tokens=20)
    assert roomy.stats["n_preempted"] == 0
    tight = Engine(cfg, params,
                   ServeConfig(n_slots=2, max_len=64, temperature=1.0,
                               seed=5, cache_kind="paged", block_size=8,
                               n_blocks=5))
    out_tight = tight.generate(prompts, max_new_tokens=20)
    assert tight.stats["n_preempted"] > 0, "pool sized to force preemption"
    assert out_tight == out_roomy
