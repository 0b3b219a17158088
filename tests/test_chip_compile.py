"""Every Pallas kernel, and a whole paged decode step, compiles for a TPU
v5e at real widths.

These compile for a described (not attached) ``v5e:2x2`` topology with
the TPU compiler that ships with jax, so they run on a machine without a
chip and guard the Mosaic block rules that interpret mode never checks.
A compile that passes is not a chip run: nothing executes here.  The
topology is described only inside the module fixture, never at import
(one process at a time may hold the TPU library), and the tests skip
where it cannot be described.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models import init_params
from repro.serving import Engine, ServeConfig

# Mistral-7B widths (d_model 4096, 32 q / 8 kv heads, d_head 128), a 16-token
# page; mamba2-2.7b widths for the SSD scan (80 heads of 64, state 128)
B, HQ, HKV, D, PAGE = 4, 32, 8, 128, 16
NB, MB, S_DENSE, S_PREFILL = 512, 64, 1024, 512
BF, I32, F32, I8 = jnp.bfloat16, jnp.int32, jnp.float32, jnp.int8


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    log_dir = os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / libtpu here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    if log_dir == "disabled":
        os.environ.pop("TPU_LOG_DIR", None)


def _compile(fn, args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel"
    return compiled


def _pool(dt=BF):
    return [(NB, PAGE, HKV, D), dt]


KERNEL_CASES = {
    "decode_attention": (
        lambda q, k, v, kp, qp: ops.decode_attention(
            q, k, v, kv_positions=kp, q_position=qp),
        [[(B, HQ, D)], [(B, S_DENSE, HKV, D)], [(B, S_DENSE, HKV, D)],
         [(B, S_DENSE), I32], [(B,), I32]]),
    "decode_attention_merged": (
        lambda u, k, v, kp, qp: ops.decode_attention_merged(
            u, k, v, kv_positions=kp, q_position=qp, n_kv_heads=HKV),
        [[(B, HQ * D)], [(B, S_DENSE, HKV, D)], [(B, S_DENSE, HKV, D)],
         [(B, S_DENSE), I32], [(B,), I32]]),
    "decode_attention_paged": (
        lambda q, k, v, bt, qp: ops.decode_attention_paged(
            q, k, v, block_tables=bt, q_position=qp),
        [[(B, HQ, D)], _pool(), _pool(), [(B, MB), I32], [(B,), I32]]),
    "decode_attention_paged_merged": (
        lambda u, k, v, bt, qp: ops.decode_attention_paged_merged(
            u, k, v, block_tables=bt, q_position=qp, n_kv_heads=HKV),
        [[(B, HQ * D)], _pool(), _pool(), [(B, MB), I32], [(B,), I32]]),
    "decode_attention_paged_q8": (
        lambda q, k, v, ks, vs, bt, qp: ops.decode_attention_paged_q8(
            q, k, v, k_scale=ks, v_scale=vs, block_tables=bt, q_position=qp),
        [[(B, HQ, D)], _pool(I8), _pool(I8), [(NB, HKV), F32],
         [(NB, HKV), F32], [(B, MB), I32], [(B,), I32]]),
    "decode_attention_paged_q8_merged": (
        lambda u, k, v, ks, vs, bt, qp: ops.decode_attention_paged_q8_merged(
            u, k, v, k_scale=ks, v_scale=vs, block_tables=bt, q_position=qp,
            n_kv_heads=HKV),
        [[(B, HQ * D)], _pool(I8), _pool(I8), [(NB, HKV), F32],
         [(NB, HKV), F32], [(B, MB), I32], [(B,), I32]]),
    "flash_attention": (
        lambda q, k, v: ops.flash_attention(q, k, v, sliding_window=4096),
        [[(1, S_PREFILL, HQ, D)], [(1, S_PREFILL, HKV, D)],
         [(1, S_PREFILL, HKV, D)]]),
    "flash_attention_merged": (
        lambda u, k, v: ops.flash_attention_merged(
            u, k, v, n_kv_heads=HKV, sliding_window=4096),
        [[(1, S_PREFILL, HQ * D)], [(1, S_PREFILL, HKV, D)],
         [(1, S_PREFILL, HKV, D)]]),
    "flash_attention_merged_q8": (
        lambda u, k, v, ks, vs: ops.flash_attention_merged_q8(
            u, k, v, k_scale=ks, v_scale=vs, n_kv_heads=HKV,
            sliding_window=4096),
        [[(1, S_PREFILL, HQ * D)], [(1, S_PREFILL, HKV, D), I8],
         [(1, S_PREFILL, HKV, D), I8], [(1, S_PREFILL // PAGE, HKV), F32],
         [(1, S_PREFILL // PAGE, HKV), F32]]),
    "ssd_scan": (
        lambda x, dt, a, bm, cm: ops.ssd_scan(x, dt, a, bm, cm, chunk=256),
        [[(1, 1024, 80, 64)], [(1, 1024, 80), F32], [(80,), F32],
         [(1, 1024, 80, 128)], [(1, 1024, 80, 128)]]),
}


def test_cases_cover_every_attention_kernel():
    wrappers = {fn.__name__ for fn in ops.ATTENTION_KERNELS.values()}
    assert wrappers <= set(KERNEL_CASES), wrappers - set(KERNEL_CASES)


@pytest.mark.parametrize("name", sorted(KERNEL_CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, specs = KERNEL_CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dt[0] if dt else BF, sharding=one_chip)
            for shape, *dt in specs]
    _compile(fn, args)


@pytest.mark.parametrize("style", ["standard", "skipless_merged"])
def test_paged_pallas_decode_step_compiles_for_v5e(one_chip, style):
    """The engine's whole serve_step (2 layers at Mistral-7B widths, bf16
    weights, paged pool) with the Pallas decode kernel inside."""
    cfg = get_config("mistral-7b").with_(n_layers=2, param_dtype="bfloat16",
                                         block_style=style)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    eng = Engine(cfg, params, ServeConfig(n_slots=B, max_len=512),
                 impl="pallas", cache="paged")
    assert eng.merged_fast_path == (style == "skipless_merged")
    tokens = jax.ShapeDtypeStruct((B,), I32, sharding=one_chip)
    compiled = eng._decode.lower(params, tokens, on_chip(eng.kv.spec()))
    assert "tpu_custom_call" in compiled.compile().as_text()
