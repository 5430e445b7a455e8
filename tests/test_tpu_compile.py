"""Ahead-of-time compiles of the paged-attention kernels for a v5e chip.

Interpret mode (every other kernel test) executes the kernel body on the
CPU and checks no TPU tiling rule; these tests hand the real shapes to the
TPU compiler, for a chip that is described and not attached, and assert
that the kernel lowered to a Mosaic custom call.  Shapes are granite-3-2b's
(32 query heads, 8 kv heads, head_dim 64) in bf16 with 16-token pages, at
one chip and at the per-shard width of a 4-way tensor-parallel cluster
(2 kv heads per shard); and the benchmark cells' own shapes at their
deepest walk, where the block size the kernel derives from the shapes
has to lower and fit VMEM.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and the test runner imports this file in
every worker.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention import kernel as K
from repro.kernels.paged_attention import ops as paged_ops

B, S, H, HKV, D, BS, NB, MB = 8, 1, 32, 8, 64, 16, 1024, 64
LIVE = 8                   # live blocks walked: 128 tokens of context
CHUNK = 16                 # unified view: widest segment of a tick
L = 40                     # layers of a stacked pool (copy_page)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - depends on the install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile written to the persistent cache cannot be read back
    # without a chip; keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _shapes(sharding, hkv, *, quant=False, rows=B, seg=S):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,  # noqa: E731
                                                 sharding=sharding)
    pool_dt = jnp.int8 if quant else jnp.bfloat16
    h = H * hkv // HKV
    args = dict(
        q=sds((rows, seg, h, D), jnp.bfloat16),
        k_new=sds((rows, seg, hkv, D), jnp.bfloat16),
        v_new=sds((rows, seg, hkv, D), jnp.bfloat16),
        k_pool=sds((NB, hkv, BS, D), pool_dt),
        v_pool=sds((NB, hkv, BS, D), pool_dt),
        block_tables=sds((rows, MB), jnp.int32),
        positions=sds((rows, seg), jnp.int32),
        window=sds((), jnp.int32))
    if quant:
        args["k_scale"] = sds((NB, hkv, BS), jnp.float32)
        args["v_scale"] = sds((NB, hkv, BS), jnp.float32)
    return args


def _assert_kernel(fn, args):
    hlo = jax.jit(fn).lower(**args).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("hkv", [HKV, HKV // 4], ids=["chip", "tp4_shard"])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_fused_update_compiles(one_chip, hkv, quant):
    args = _shapes(one_chip, hkv, quant=quant)
    _assert_kernel(lambda **a: K.paged_attention_update_pallas(
        **a, softcap=0.0, max_live_blocks=LIVE), args)


@pytest.mark.parametrize("hkv", [HKV, HKV // 4], ids=["chip", "tp4_shard"])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_readonly_walk_compiles(one_chip, hkv, quant):
    args = _shapes(one_chip, hkv, quant=quant)
    del args["k_new"], args["v_new"]
    _assert_kernel(lambda **a: K.paged_attention_pallas(
        **a, softcap=0.0, max_live_blocks=LIVE), args)


@pytest.mark.parametrize("hkv", [HKV, HKV // 4], ids=["chip", "tp4_shard"])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_unified_view_compiles(one_chip, hkv, quant):
    """The unified tick's ragged view: T flat one-token rows walked per
    request through ``row_map`` — prefill chunks and decodes in one
    launch."""
    T, R = B * CHUNK, B
    args = _shapes(one_chip, hkv, quant=quant, rows=T, seg=1)
    del args["block_tables"]              # per request, not per flat row
    args["req_tables"] = jax.ShapeDtypeStruct((R, MB), jnp.int32,
                                              sharding=one_chip)
    args["row_map"] = jax.ShapeDtypeStruct((R, CHUNK), jnp.int32,
                                           sharding=one_chip)
    _assert_kernel(lambda **a: paged_ops.paged_attention_unified(
        **a, softcap=0.0, max_live_blocks=LIVE, max_seg_len=CHUNK,
        use_pallas=True, interpret=False), args)


@pytest.mark.parametrize("hkv", [HKV, HKV // 4], ids=["chip", "tp4_shard"])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_copy_page_compiles(one_chip, hkv, quant):
    pool_dt = jnp.int8 if quant else jnp.bfloat16
    for shape, dt in [((L, NB, hkv, BS, D), pool_dt),
                      ((L, NB, hkv, BS), jnp.float32)][:1 + quant]:
        args = dict(pool=jax.ShapeDtypeStruct(shape, dt, sharding=one_chip),
                    src=jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
                    dst=jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))
        _assert_kernel(lambda **a: K.copy_page_pallas(**a), args)


# The benchmark cells' ticks at their deepest walk (136 live pages of 16
# tokens; the pools flat over the layers, as the layer scan carries them):
# granite-3-2b's decode tick, 20 kernel rows (16 decoding, 4 padding) of
# one token at 4 query heads per 64-wide kv head, and glm4-9b's chunk
# tick, 8 requests of 128-token chunks at 16 query heads per 128-wide kv
# head (2048 query rows against each kv head).
CELLS = {"granite_decode": dict(h=32, hkv=8, d=64, rows=20, seg=1,
                                pages=40 * 2600),
         "glm4_chunk": dict(h=32, hkv=2, d=128, rows=8, seg=128,
                            pages=20 * 8400)}
DEEP = 136


@pytest.mark.parametrize("view", ["fused", "unified"])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_shapes_compile(one_chip, cell, view):
    c = CELLS[cell]
    sds = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    R, seg = c["rows"], c["seg"]
    T = R * seg if view == "unified" else R
    s = 1 if view == "unified" else seg
    args = dict(q=sds((T, s, c["h"], c["d"])),
                k_new=sds((T, s, c["hkv"], c["d"])),
                v_new=sds((T, s, c["hkv"], c["d"])),
                k_pool=sds((c["pages"], c["hkv"], BS, c["d"])),
                v_pool=sds((c["pages"], c["hkv"], BS, c["d"])),
                positions=sds((T, s), jnp.int32),
                window=sds((), jnp.int32))
    if view == "fused":
        args["block_tables"] = sds((R, DEEP), jnp.int32)
        _assert_kernel(lambda **a: K.paged_attention_update_pallas(
            **a, softcap=0.0, max_live_blocks=DEEP), args)
        return
    args["req_tables"] = sds((R, DEEP), jnp.int32)
    args["row_map"] = sds((R, seg), jnp.int32)
    _assert_kernel(lambda **a: paged_ops.paged_attention_unified(
        **a, softcap=0.0, max_live_blocks=DEEP, max_seg_len=seg,
        use_pallas=True, interpret=False), args)
