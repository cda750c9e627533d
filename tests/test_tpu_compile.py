"""Mosaic compiles of the four Pallas kernels for a described TPU v5e.

Interpret mode (tests/test_kernels.py) checks the numbers; only the TPU
compiler checks that a kernel's block layout and VMEM use are legal on
the chip.  The TPU compiler is installed with jaxlib and compiles for a
``v5e:2x2`` topology that is described, not attached, so these tests run
on a CPU-only machine.  Widths are llama3.2-1b's (32 q heads, 8 kv heads,
head dim 64) at the serving block sizes and page size 64.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and every pytest worker imports this
file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.chunked_prefill import (chunked_prefill_attention,
                                           paged_prefill_attention)
from repro.kernels.gqa_decode import (gqa_decode_attention,
                                      paged_gqa_decode_attention)

H, HKV, HD = 32, 8, 64           # llama3.2-1b attention widths
B, S, L = 2, 512, 1024           # rows, prefill length, dense cache slots
PAGES, PAGE, P = 64, 64, 8       # pool pages, page size, page-table width


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


F32, I32 = jnp.float32, jnp.int32
KERNELS = {
    "chunked_prefill": (
        lambda q, k, v, seg: chunked_prefill_attention(
            q, k, v, seg, block_q=128, block_k=128, interpret=False),
        [((B, S, H, HD), F32), ((B, S, HKV, HD), F32),
         ((B, S, HKV, HD), F32), ((B, S), I32)]),
    "gqa_decode": (
        lambda q, k, v, valid, start: gqa_decode_attention(
            q, k, v, valid, start, block_k=256, interpret=False),
        [((B, H, HD), F32), ((B, L, HKV, HD), F32), ((B, L, HKV, HD), F32),
         ((B,), I32), ((B,), I32)]),
    "paged_prefill": (
        lambda q, k, v, pt, pos: paged_prefill_attention(
            q, k, v, pt, pos, block_q=128, interpret=False),
        [((B, S, H, HD), F32), ((PAGES, PAGE, HKV, HD), F32),
         ((PAGES, PAGE, HKV, HD), F32), ((B, P), I32), ((B, S), I32)]),
    "paged_gqa_decode": (
        lambda q, k, v, pt, valid: paged_gqa_decode_attention(
            q, k, v, pt, valid, interpret=False),
        [((B, H, HD), F32), ((PAGES, PAGE, HKV, HD), F32),
         ((PAGES, PAGE, HKV, HD), F32), ((B, P), I32), ((B,), I32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, shapes = KERNELS[name]
    assert "tpu_custom_call" in _compile(fn, shapes, one_chip)
