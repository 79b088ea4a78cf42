"""The main path's Pallas kernels compile for a TPU v5e at real widths.

No chip is needed: the TPU compiler compiles for a described (not
attached) ``v5e:2x2`` topology, and refuses what the chip would refuse —
a block that breaks the (8, 128) tiling rule, a kernel that needs more
scoped VMEM than it may use.  Interpret mode cannot see either.  Every
case asserts that the compiled program holds the kernel
(``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and a test worker that
holds it keeps it until it exits.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.decode_attention.kernel import (
    paged_decode_attention_kernel,
    quantized_paged_decode_attention_kernel,
)
from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.moe_dropless.kernel import ragged_ffn_kernel
from repro.kernels.moe_dropless.ops import kernel_vmem_bytes, pick_block_i
from repro.quant import get_kv_quant

# (q heads, kv heads, head_dim): M6-base (configs/m6.py) and granite's GQA
ATTN_WIDTHS = {"m6-base": (16, 16, 64), "granite": (24, 8, 64)}
# (experts, d_model, d_ff, gated activation)
FFN_WIDTHS = {"m6-base": (32, 1024, 4096, False),
              "granite": (40, 1536, 512, True)}
# a serving step's pool and rows: 8 slots x 256 positions in 16-token
# blocks (+1 garbage block), 8 decode rows + a 32-token prefill chunk
POOL_BLOCKS, BLOCK, ROWS = 8 * 16 + 1, 16, 8 + 32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile_text(fn, one_chip, *shapes):
    args = [None if s is None else
            jax.ShapeDtypeStruct(s[0], s[1], sharding=one_chip)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(text):
    assert 'custom_call_target="tpu_custom_call"' in text


def _paged_shapes(widths, pool_dtype):
    hq, hkv, d = ATTN_WIDTHS[widths]
    return [((ROWS, hkv, hq // hkv, d), jnp.bfloat16),
            ((POOL_BLOCKS, hkv, BLOCK, d), pool_dtype),
            ((POOL_BLOCKS, hkv, BLOCK, d), pool_dtype),
            ((ROWS, 256 // BLOCK), jnp.int32), ((ROWS,), jnp.int32)], hkv


@pytest.mark.parametrize("widths", sorted(ATTN_WIDTHS))
def test_paged_attention_compiles(one_chip, widths):
    shapes, _ = _paged_shapes(widths, jnp.bfloat16)
    d = ATTN_WIDTHS[widths][2]
    _assert_kernel(_compile_text(
        functools.partial(paged_decode_attention_kernel, scale=d ** -0.5),
        one_chip, *shapes))


def test_latent_paged_attention_compiles(one_chip):
    """Moonlight's absorbed decode: 16 query heads over one latent KV
    head of 512 + 64 lanes (zero-padded to 640) in blocks of 256, the
    values the leading 512, read in place from layer ``layer`` of a
    4-layer group's pool."""
    n, blocks, bs = 384, 64, 256

    def fn(q, k, tbl, lens, layer):
        return paged_decode_attention_kernel(q, k, None, tbl, lens,
                                             scale=192 ** -0.5, v_dim=512,
                                             layer=layer)

    _assert_kernel(_compile_text(
        fn, one_chip, ((n, 1, 16, 640), jnp.bfloat16),
        ((4, blocks, 1, bs, 640), jnp.bfloat16), ((n, 8192 // bs), jnp.int32),
        ((n,), jnp.int32), ((), jnp.int32)))


@pytest.mark.parametrize("kv_quant", ["int8", "fp8"])
def test_quantized_paged_attention_compiles(one_chip, kv_quant):
    (q, k, v, tbl, lens), hkv = _paged_shapes("m6-base", jnp.int8)
    scales = ((POOL_BLOCKS, hkv), jnp.float32)
    decode = get_kv_quant(kv_quant).decode

    def fn(q, k, v, ks, vs, tbl, lens):
        return quantized_paged_decode_attention_kernel(
            q, k, v, ks, vs, tbl, lens, decode=decode,
            scale=q.shape[-1] ** -0.5)

    _assert_kernel(_compile_text(fn, one_chip, q, k, v, scales, scales,
                                 tbl, lens))


def _ffn_text(one_chip, widths, dtype, block_x, block_i):
    E, M, I, gated = FFN_WIDTHS[widths]
    N = block_x * 64
    act = "swiglu" if gated else "gelu"

    def fn(x, be, up, gate, down):
        return ragged_ffn_kernel(x, be, up, gate, down, act,
                                 block_x=block_x, block_i=block_i)

    w_in = ((E, M, I), dtype)
    return _compile_text(fn, one_chip, ((N, M), dtype),
                         ((N // block_x,), jnp.int32), w_in,
                         w_in if gated else None, ((E, I, M), dtype))


@pytest.mark.parametrize("block_x", [8, 128])
@pytest.mark.parametrize("widths", sorted(FFN_WIDTHS))
def test_ragged_ffn_compiles(one_chip, widths, block_x):
    E, M, I, gated = FFN_WIDTHS[widths]
    bi = pick_block_i(block_x, M, I, 3 if gated else 2, 2, 2)
    _assert_kernel(_ffn_text(one_chip, widths, jnp.bfloat16, block_x, bi))


def test_ragged_ffn_f32_block_i_fits_vmem(one_chip):
    """At f32 and granite widths the default 512-wide intermediate tile
    needs more than the 16 MiB of scoped VMEM; the wrapper's pick fits."""
    E, M, I, gated = FFN_WIDTHS["granite"]
    bi = pick_block_i(128, M, I, 3, 4, 4)
    assert bi < 512
    assert kernel_vmem_bytes(128, 512, M, 3, 4, 4) > 16 * 2**20
    _assert_kernel(_ffn_text(one_chip, "granite", jnp.float32, 128, bi))


def test_flash_attention_compiles(one_chip):
    hq, hkv, d = ATTN_WIDTHS["m6-base"]
    q = ((1, hq, 256, d), jnp.bfloat16)
    kv = ((1, hkv, 256, d), jnp.bfloat16)
    _assert_kernel(_compile_text(flash_attention_kernel, one_chip, q, kv, kv))
