"""The serving path's Pallas kernels compile for a TPU v5e at Llama2-7B's
widths (32 heads of 128, 16-token pages, 4 MiB of sealed bytes per
ChaCha20 call) — compiled for a described chip, none attached.

What interpret mode cannot show, this does: the TPU compiler refuses block
shapes off its tiling, lowering it does not support, and more fast memory
than a kernel may use. Nothing runs, so results and times are not checked.
The topology is described inside a fixture (never at import), and these
tests stay in this one file: one process at a time may load the TPU
library, and it keeps it until it exits.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.chacha20 import BLOCKS_PER_TILE, chacha20_xor_blocked
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import (paged_attention,
                                           paged_attention_unseal)

# llama2-7b decode: 4 slots, 32 heads (= kv heads) of 128, 16-token pages,
# a pool of 80 pages + the null page, 20 pages per sequence
SLOTS, HEADS, HEAD_DIM, PAGE, PAGES, MAX_PAGES = 4, 32, 128, 16, 80, 20


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with JAX's persistent cache off
    (what a described chip compiles cannot be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_chacha20_xor_compiles(one_chip):
    hlo = _compiled_text(
        one_chip,
        lambda k, n, d, b: chacha20_xor_blocked(k, n, d, counter_base=b,
                                                interpret=False),
        ((8,), jnp.uint32), ((3,), jnp.uint32),
        ((16, 64 * BLOCKS_PER_TILE), jnp.uint32), ((), jnp.uint32))
    assert "tpu_custom_call" in hlo


def test_flash_attention_compiles(one_chip):
    s = (HEADS, 256, HEAD_DIM)
    hlo = _compiled_text(
        one_chip,
        lambda q, k, v: flash_attention(q, k, v, interpret=False),
        (s, jnp.bfloat16), (s, jnp.bfloat16), (s, jnp.bfloat16))
    assert "tpu_custom_call" in hlo


def _paged_shapes(dtype):
    pool = ((PAGES + 1, PAGE, HEADS, HEAD_DIM), dtype)
    return [((SLOTS, HEADS, HEAD_DIM), dtype), pool, pool,
            ((SLOTS, MAX_PAGES), jnp.int32), ((SLOTS,), jnp.int32)]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_paged_attention_compiles(one_chip, dtype):
    hlo = _compiled_text(
        one_chip,
        lambda *a: paged_attention(*a, interpret=False, emulate=False),
        *_paged_shapes(dtype))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_paged_attention_unseal_compiles(one_chip, dtype):
    bpp = PAGE * HEADS * HEAD_DIM * jnp.dtype(dtype).itemsize // 64
    crypt = ((PAGES + 1, 4), jnp.uint32)
    hlo = _compiled_text(
        one_chip,
        lambda *a: paged_attention_unseal(*a, blocks_per_page=bpp,
                                          interpret=False, emulate=False),
        *_paged_shapes(dtype), ((), jnp.int32), ((8,), jnp.uint32),
        crypt, crypt)
    assert "tpu_custom_call" in hlo
