"""The model-reachable Pallas kernels compile for a TPU v5e chip.

Interpret mode (the CPU path of every other kernel test) accepts block
shapes the TPU compiler refuses, so these tests compile each kernel for a
described, not attached, v5e chip at llama3.2-3b widths: 24 query heads over
8 KV heads, head_dim 128; the paged decode kernel also at the Qwen3-8B chat
cell's shapes (32 slots of 4 query heads per KV head, a 160-page table over
a 1,537-row pool). Both paged kernels compile in both forms: on one layer's
pool, and on the whole layer stack's pools read at a traced layer index, as
the model's layer scan calls them. Nothing runs; a compile that the chip's compiler
would refuse fails here. The topology is described inside a fixture, never
while the module is imported (only one process at a time may load the TPU
library).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_attention as dec
from repro.kernels import flash_attention as fa

B, H, KV, D = 8, 24, 8, 128
G = H // KV
PAGE, POOL_PAGES, MAX_PAGES = 16, 256, 32  # a 512-token table per slot
CHUNK = 128
SEQ = 2048


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_was = jax.config.jax_enable_compilation_cache
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip.
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _shape(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


LAYERS = 18  # the stacked form: Qwen3-8B chat's 18 layers
FORMS = ["pool", "stacked"]


def _pools(one_chip, form, rows):
    """The kernel's pool operands and its layer index (None for one pool)."""
    lead = (LAYERS,) if form == "stacked" else ()
    pool = _shape(one_chip, lead + (rows, PAGE, KV, D))
    layer = _shape(one_chip, (), jnp.int32) if form == "stacked" else None
    return pool, layer


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize(
    "b, g, max_pages, pool_rows",
    [
        (B, G, MAX_PAGES, POOL_PAGES + 1),
        (32, 4, 160, 1537),  # Qwen3-8B chat: 32 slots, a 2,560-token table
    ],
    ids=["llama3.2-3b", "qwen3-8b-chat"],
)
def test_paged_decode_compiles(one_chip, b, g, max_pages, pool_rows, form):
    pool, layer = _pools(one_chip, form, pool_rows)
    compiled = _compile(
        lambda q, k, v, pt, pos, ly: dec.paged_decode_attention(
            q, k, v, pt, pos, ly, n_lp=max_pages, interpret=False
        ),
        _shape(one_chip, (b, KV, g, D)), pool, pool,
        _shape(one_chip, (b, max_pages), jnp.int32),
        _shape(one_chip, (b,), jnp.int32), layer,
    )
    # the kernel's op carries its own name into the compiled program
    assert "%paged_decode_attention" in compiled.as_text()


@pytest.mark.parametrize("form", FORMS)
def test_paged_chunk_compiles(one_chip, form):
    pool, layer = _pools(one_chip, form, POOL_PAGES + 1)
    compiled = _compile(
        lambda q, k, v, pt, start, ly: dec.paged_chunk_attention(
            q, k, v, pt, start, ly, n_lp=MAX_PAGES, group=G, interpret=False
        ),
        _shape(one_chip, (1, KV, CHUNK * G, D)), pool, pool,
        _shape(one_chip, (1, MAX_PAGES), jnp.int32),
        _shape(one_chip, (1,), jnp.int32), layer,
    )
    assert "%paged_chunk_attention" in compiled.as_text()


def _qkv(one_chip):
    return [_shape(one_chip, (2 * H, SEQ, D)) for _ in range(3)]


def test_flash_fwd_compiles(one_chip):
    _compile(
        lambda q, k, v: fa.flash_attention_fwd(q, k, v, interpret=False),
        *_qkv(one_chip),
    )


def test_flash_bwd_compiles(one_chip):
    q, k, v = _qkv(one_chip)
    lse = jax.eval_shape(
        lambda q, k, v: fa.flash_attention_fwd(q, k, v, interpret=True)[1], q, k, v
    )
    _compile(
        lambda q, k, v, o, lse, do: fa.flash_attention_bwd(
            q, k, v, o, lse, do, interpret=False
        ),
        q, k, v, q, _shape(one_chip, lse.shape, lse.dtype), q,
    )
