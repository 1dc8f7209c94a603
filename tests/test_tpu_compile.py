"""The main path's Pallas kernels compile for a TPU v5e at Qwen2.5-3B widths.

Interpret mode (what every other kernel test runs on the CPU) accepts block
shapes, reshapes and casts that Mosaic refuses.  Here each kernel is lowered
and compiled for a described v5e chip, which needs the TPU compiler but no
chip: nothing runs, so these tests pin only that Mosaic accepts the kernel
and that it stays a kernel (a ``tpu_custom_call`` in the compiled module).

Shapes are those of Qwen2.5-3B (d_model 2048, d_ff 11008, 16 heads over 2 KV
heads of dim 128) serving 8 slots: a decode-tick projection for the fabric
kernels, a paged decode over 1024-token slots, and a 512-token prefill for
flash attention.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and every test worker imports
this module.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bitplane_mac.ops import bitplane_mac, bitplane_mac_noisy
from repro.kernels.flash_attn.ops import flash_attention
from repro.kernels.imc_mac.ops import imc_mac
from repro.kernels.paged_attn.ops import paged_attention

SLOTS, D, FF, H, KV, HD = 8, 2048, 11008, 16, 2, 128
BLOCK, MAX_BLOCKS = 16, 64  # 1024-token slots
NUM_BLOCKS = SLOTS * MAX_BLOCKS


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def test_bitplane_mac_8x8_compiles(one_chip, no_compile_cache):
    _compile(lambda a, w: bitplane_mac(a, w, bits_a=8, bits_w=8,
                                       interpret=False),
             one_chip, ((SLOTS, D), jnp.int32), ((D, FF), jnp.int32))


def test_bitplane_mac_noisy_compiles(one_chip, no_compile_cache):
    from repro.core.fabric import NoiseSpec

    sigma = NoiseSpec.calibrated().mismatch_sigma
    _compile(lambda a, w, key: bitplane_mac_noisy(
        a, w, key, bits_a=8, bits_w=8, mismatch_sigma=sigma,
        interpret=False),
        one_chip, ((SLOTS, FF), jnp.int32), ((FF, D), jnp.int32),
        ((2,), jnp.uint32))


def test_imc_mac_compiles(one_chip, no_compile_cache):
    _compile(lambda a, w: imc_mac(a, w, interpret=False),
             one_chip, ((SLOTS, D), jnp.int8), ((D, FF), jnp.int8))


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_attn_compiles(one_chip, no_compile_cache, kv_dtype):
    pool_dt = jnp.int8 if kv_dtype == "int8" else jnp.bfloat16
    pool = ((NUM_BLOCKS, BLOCK, KV, HD), pool_dt)
    shapes = [((SLOTS, 1, H, HD), jnp.bfloat16), pool, pool,
              ((SLOTS, MAX_BLOCKS), jnp.int32), ((SLOTS,), jnp.int32)]
    if kv_dtype == "int8":
        shapes += [((NUM_BLOCKS, BLOCK, KV), jnp.float16)] * 2

    def fn(q, k, v, tbl, pos, *scales):
        ks, vs = scales or (None, None)
        return paged_attention(q, k, v, tbl, pos, k_scale=ks, v_scale=vs,
                               impl="pallas", interpret=False)

    _compile(fn, one_chip, *shapes)


def test_flash_attn_compiles(one_chip, no_compile_cache):
    s = 512
    _compile(lambda q, k, v: flash_attention(q, k, v, interpret=False),
             one_chip, ((1, s, H, HD), jnp.bfloat16),
             ((1, s, KV, HD), jnp.bfloat16), ((1, s, KV, HD), jnp.bfloat16))

