"""The main path's Pallas kernels compile for a TPU v5e at olmo-1b widths.

Interpret mode (tests/test_kernels.py, test_kv_quant.py) cannot see what
the chip's compiler refuses: 8-bit vector shifts and compares, blocks
whose last two dims break the (8, 128) tiling rule.  These tests compile
each kernel for a described, unattached ``v5e:2x2`` topology and check
that the compiled program calls it (``tpu_custom_call``).  Nothing runs.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and test
workers import every test file.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import entropy_hist, flash_attention, quant_matmul

M = 8                                       # decode batch (serving slots)
B, H, D, S, PAGE = 8, 16, 128, 2048, 16     # olmo-1b decode attention


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("k,n", [(2048, 2048), (2048, 8192), (8192, 2048)])
@pytest.mark.parametrize("bits", [4, 2])
def test_quant_matmul_compiles(one_chip, bits, k, n):
    def fn(x, wp, scale):
        return quant_matmul.quant_matmul(x, wp, scale, bits=bits)

    _compile(fn, one_chip, ((M, k), jnp.bfloat16),
             ((k // (8 // bits), n), jnp.uint8), ((n,), jnp.float32))


def _cache_shapes(rows, bits):
    dp = D if bits == 8 else D // 2
    code = jnp.int8 if bits == 8 else jnp.uint8
    return ((rows + (H, dp), code), ((B, H, D), jnp.float32),
            (rows + (H, dp), code), (rows + (H,), jnp.float32))


@pytest.mark.parametrize("bits", [8, 4])
def test_kv_decode_attention_compiles(one_chip, bits):
    kq, ks, vq, vs = _cache_shapes((B, S), bits)

    def fn(q, kq, ks, vq, vs, pos):
        return flash_attention.kv_decode_attention(q, kq, ks, vq, vs, pos,
                                                   bits=bits)

    _compile(fn, one_chip, ((B, H, D), jnp.float32), kq, ks, vq, vs,
             ((B,), jnp.int32))


@pytest.mark.parametrize("bits", [8, 4])
def test_paged_kv_decode_attention_compiles(one_chip, bits):
    n_pages = S // PAGE
    kq, ks, vq, vs = _cache_shapes((B * n_pages, PAGE), bits)

    def fn(q, kq, ks, vq, vs, tbl, pos):
        return flash_attention.paged_kv_decode_attention(
            q, kq, ks, vq, vs, tbl, pos, bits=bits)

    _compile(fn, one_chip, ((B, H, D), jnp.float32), kq, ks, vq, vs,
             ((B, n_pages), jnp.int32), ((B,), jnp.int32))


def test_entropy_histogram_compiles(one_chip):
    def fn(codes):
        return entropy_hist.histogram(codes, 16)

    _compile(fn, one_chip, ((2048 * 2048,), jnp.int32))
