"""Flash-decoding split-K attention kernel (one new token vs a long cache).

GPU flash-decoding [arXiv:2311.01282] splits the KV length across SMs and
combines partials in a second pass. The TPU adaptation runs the KV blocks as
the sequential innermost grid dimension with the running (m, l, acc) state
in VMEM scratch — the combine is the carry, no second pass needed; split-K
ACROSS chips comes from sharding the cache seq dim over the mesh (the
decode_default profile), whose partial-softmax combine XLA handles.

Layout: q (BKV, G, D) — one program per (batch, kv-head); G = query heads
per kv head ride the sublane dim. k/v: (BKV, T, D). Validity is positional:
slots with k_pos > cur_pos (or outside the window ring) are masked, so the
same kernel serves dense caches and ring buffers.

``paged_decode_attention`` is the paged-serving variant: KV lives in a
shared page pool (P+1, page, KV, D), row P the trash page, and slot b's
logical page j is physical page ``page_table[b, j]``. The pool may also be
the whole layer stack's, (L, P+1, page, KV, D), read at a ``layer`` index
that rides in as a scalar-prefetch operand: the model's layer scan carries
the stacked pool and updates it in place, and no layer's pool is ever
sliced out of it. Its cost follows each slot's live context, not the
table width. The grid is one step per slot;
the pools stay in HBM and the kernel copies pages itself. A slot reads its
live pages only — up to the page holding ``cur_pos[b]``, capped at ``n_lp``
(the table width for dense layers, ``ceil(window / page)`` for ring-folded
windowed ones) — and none when its row starts at the trash page (a retired
or empty slot, whose frozen position may be large). It walks them in blocks
of several pages (``pages_per_block``, from the shapes), double-buffered in
VMEM: the slot's next block is copied while the current one computes, and
a block past the bound starts no copy. The online softmax keeps f32
running max, sum and accumulator, and positional validity (dense, ring
window, tail page) is computed in-kernel from the logical slot index, so
the walk needs no inputs beyond the page table and positions, and no
gathered copy of the cache is ever materialised.
``paged_chunk_attention`` (many query rows, one prompt chunk) keeps a grid
of (slot, logical page) with BlockSpec-fetched pages.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
F32 = jnp.float32
# The paged decode walk's block: about this many tokens a step, with its four
# K/V buffers (two in flight, two computing) held to this much VMEM.
_BLOCK_TOKENS = 256
_KV_BUFFER_BYTES = 4 * 2**20


def _decode_kernel(
    q_ref, k_ref, v_ref, kpos_ref, curpos_ref, o_ref,
    acc_ref, m_ref, l_ref,
    *, sm_scale, window, blk_k, n_k,
):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(F32)  # (G, D)
    k = k_ref[0].astype(F32)  # (blk_k, D)
    v = v_ref[0].astype(F32)
    k_pos = kpos_ref[...]  # (blk_k,)
    cur = curpos_ref[0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=F32) * sm_scale
    valid = (k_pos <= cur) & (k_pos >= 0)
    if window:
        valid = valid & (k_pos > cur - window)
    s = jnp.where(valid[None, :], s, NEG_INF)  # (G, blk_k)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=F32
    )
    m_ref[...] = m_cur

    @pl.when(ki == n_k - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention(
    q: jax.Array,  # (BKV, G, D)
    k: jax.Array,  # (BKV, T, D)
    v: jax.Array,  # (BKV, T, D)
    k_pos: jax.Array,  # (T,) int32 positions held by each slot
    cur_pos: jax.Array,  # scalar int32
    *, window: int = 0, sm_scale: float | None = None, blk_k: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    if interpret is None:
        from repro.kernels.ops import default_interpret

        interpret = default_interpret()
    BKV, G, D = q.shape
    T = k.shape[1]
    blk_k = min(blk_k, T)
    assert T % blk_k == 0, (T, blk_k)
    n_k = T // blk_k
    sm = sm_scale if sm_scale is not None else D ** -0.5

    return pl.pallas_call(
        functools.partial(
            _decode_kernel, sm_scale=sm, window=window, blk_k=blk_k, n_k=n_k
        ),
        grid=(BKV, n_k),
        in_specs=[
            pl.BlockSpec((1, G, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, blk_k, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, blk_k, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((blk_k,), lambda b, j: (j,)),
            pl.BlockSpec((1,), lambda b, j: (0,)),
        ],
        out_specs=pl.BlockSpec((1, G, D), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BKV, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, D), F32),
            pltpu.VMEM((G,), F32),
            pltpu.VMEM((G,), F32),
        ],
        interpret=interpret,
    )(q, k, v, k_pos, cur_pos[None].astype(jnp.int32))


def _paged_init(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _paged_head_update(h, q, k, v, valid, acc_ref, m_ref, l_ref, sm_scale):
    """One online-softmax step of kv-head ``h`` against ``k``/``v``, that
    head's ``(tokens, D)`` slice of the current page or block.

    The TPU compiler needs a block's last two dims tiled or whole, so the
    pools and buffers keep every kv head of a page, ``(.., page, KV, D)``,
    and the callers pick the head. Row state is 2-D ``(rows, 1)`` per head
    for the same reason."""
    k = k.astype(F32)
    v = v.astype(F32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=F32) * sm_scale
    s = jnp.where(valid, s, NEG_INF)  # (rows, tokens)
    m_prev = m_ref[h]  # (rows, 1)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur)
    l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=F32
    )
    m_ref[h] = m_cur


def _paged_flush(o_ref, acc_ref, l_ref):
    for h in range(o_ref.shape[1]):
        l = jnp.maximum(l_ref[h], 1e-30)
        o_ref[0, h] = (acc_ref[h] / l).astype(o_ref.dtype)


def _decode_k_pos(s_idx, cur, window):
    """Position held by logical slot ``s_idx``: dense slots hold position
    s; ring slots s < window hold the latest p <= cur with p % window == s
    (negative: never written)."""
    if not window:
        return s_idx
    k_pos = cur - ((cur - s_idx) % window)
    return jnp.where(s_idx < window, k_pos, -1)


def _stacked_pools(k_pool, v_pool, layer):
    """The pools as a layer stack (L, P+1, page, KV, D) and the layer read,
    as a (1,) int32 scalar-prefetch operand. A single layer's pool
    (``layer`` None) is a stack of one."""
    if layer is None:
        return k_pool[None], v_pool[None], jnp.zeros((1,), jnp.int32)
    return k_pool, v_pool, jnp.reshape(layer, (1,)).astype(jnp.int32)


def _paged_decode_kernel(
    # scalar prefetch: (B, MP) page table, (B,) cur positions, (1,) layer
    pt_ref, cp_ref, ly_ref,
    q_ref, k_hbm, v_hbm, o_ref,
    k_buf, v_buf, sem, acc_ref, m_ref, l_ref,
    *, sm_scale, window, page, n_lp, ppb,
):
    """Grid ``(B,)``, one step per slot. Slot ``b`` walks its live pages
    (:func:`live_pages`) in blocks of ``ppb`` pages: a loop with a dynamic
    trip count, each block copied page by page from the pools (left in
    HBM) into one of two VMEM buffers while the block in the other
    computes."""
    b = pl.program_id(0)
    layer = ly_ref[0]
    n_live = live_pages(
        pt_ref[b, 0], cp_ref[b], page=page, n_lp=n_lp, trash=k_hbm.shape[1] - 1
    )
    n_blk = (n_live + ppb - 1) // ppb

    def block_copies(i, buf, start):
        # Only the block's live pages are copied; the rest of the buffer
        # keeps older finite pages, masked out by position.
        for c in range(ppb):
            j = i * ppb + c

            @pl.when(j < n_live)
            def _page():
                pid = pt_ref[b, j]
                for src, dst, t in ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1)):
                    cp = pltpu.make_async_copy(
                        src.at[layer, pid], dst.at[buf, pl.ds(c * page, page)],
                        sem.at[t, buf],
                    )
                    cp.start() if start else cp.wait()

    @pl.when(b == 0)
    def _call_init():
        # Buffer pages past a slot's last live page are read and masked:
        # they must never hold non-finite VMEM garbage.
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)

    _paged_init(acc_ref, m_ref, l_ref)
    cur = cp_ref[b]

    @pl.when(n_blk > 0)
    def _walk():
        block_copies(0, 0, True)

        def body(i, buf):
            @pl.when(i + 1 < n_blk)
            def _prefetch():
                block_copies(i + 1, 1 - buf, True)

            block_copies(i, buf, False)
            s_idx = i * (ppb * page) + jax.lax.broadcasted_iota(jnp.int32, (1, ppb * page), 1)
            k_pos = _decode_k_pos(s_idx, cur, window)
            valid = (k_pos >= 0) & (k_pos <= cur)  # (1, ppb * page)
            for h in range(q_ref.shape[1]):
                q = q_ref[0, h].astype(F32)  # (G, D)
                _paged_head_update(
                    h, q, k_buf[buf, :, h, :], v_buf[buf, :, h, :], valid,
                    acc_ref, m_ref, l_ref, sm_scale,
                )
            return 1 - buf

        jax.lax.fori_loop(0, n_blk, body, 0)

    _paged_flush(o_ref, acc_ref, l_ref)


def _paged_chunk_kernel(
    # scalar prefetch: (B, MP) page table, (B,) chunk starts, (1,) layer
    pt_ref, start_ref, ly_ref,
    q_ref, k_ref, v_ref, o_ref,
    acc_ref, m_ref, l_ref,
    *, sm_scale, page, n_lp, G,
):
    b = pl.program_id(0)
    j = pl.program_id(1)  # logical page (innermost: sequential accumulation)

    @pl.when(j == 0)
    def _init():
        _paged_init(acc_ref, m_ref, l_ref)

    start = start_ref[b]
    CG = q_ref.shape[2]
    # Dense chunked prefill: logical slot s holds position s; query row r is
    # chunk token c = r // G at absolute position start + c. Trash-backed
    # table entries and the chunk's own padded tail sit at k_pos > q_pos and
    # mask out — the kernel needs no extra validity inputs.
    k_pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (CG, page), 1)
    q_pos = start + jax.lax.broadcasted_iota(jnp.int32, (CG, page), 0) // G
    valid = k_pos <= q_pos  # (CG, page)

    for h in range(q_ref.shape[1]):
        q = q_ref[0, h].astype(F32)  # (C*G, D): query row r = c*G + g
        _paged_head_update(
            h, q, k_ref[0, :, h, :], v_ref[0, :, h, :], valid,
            acc_ref, m_ref, l_ref, sm_scale,
        )

    @pl.when(j == n_lp - 1)
    def _flush():
        _paged_flush(o_ref, acc_ref, l_ref)


def paged_chunk_attention(
    q: jax.Array,  # (B, KV, C*G, D) chunk queries, row r = c*G + g
    k_pool: jax.Array,  # (P+1, page, KV, D) incl. trash page, or (L, P+1, ...)
    v_pool: jax.Array,
    page_table: jax.Array,  # (B, max_pages) int32
    start: jax.Array,  # (B,) int32: tokens cached before the chunk
    layer: jax.Array | None = None,  # int32 scalar: the stacked pools' layer
    *, n_lp: int, group: int, sm_scale: float | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Chunked-prefill flash attention over page-table-gathered KV blocks.

    The dense-layer companion of :func:`paged_decode_attention` for C > 1
    query tokens: the chunk's K/V are scattered into the pool *before* the
    call, then every chunk token attends to the already-paged prefix plus
    its chunk predecessors through the same scalar-prefetched page table —
    per-(token, slot) causal validity is computed in-kernel from the page
    index and the chunk start, so the kernel never materialises a gathered
    cache copy or a mask input.
    """
    if interpret is None:
        from repro.kernels.ops import default_interpret

        interpret = default_interpret()
    B, KV, R, D = q.shape
    assert R % group == 0, (q.shape, group)
    assert n_lp <= page_table.shape[1], (n_lp, page_table.shape)
    sm = sm_scale if sm_scale is not None else D ** -0.5
    k_pool, v_pool, layer = _stacked_pools(k_pool, v_pool, layer)
    page = k_pool.shape[2]
    kernel = functools.partial(
        _paged_chunk_kernel, sm_scale=sm, page=page, n_lp=n_lp, G=group
    )
    # Grid (B, n_lp): one program per (slot, logical page), all kv heads per
    # program; the k/v BlockSpecs fetch physical page page_table[b, j] of
    # the layer read.
    kv_spec = pl.BlockSpec(
        (pl.squeezed, 1, page, KV, D),
        lambda b, j, pt, st, ly: (ly[0], pt[b, j], 0, 0, 0),
    )
    q_spec = pl.BlockSpec((1, KV, R, D), lambda b, j, pt, st, ly: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, n_lp),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=_row_state(KV, R, D),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, R, D), q.dtype),
        interpret=interpret,
        name="paged_chunk_attention",
    )(page_table.astype(jnp.int32), start.astype(jnp.int32), layer, q, k_pool, v_pool)


def _row_state(KV, R, D):
    """VMEM scratch of the online softmax: accumulator, running max, sum."""
    return [
        pltpu.VMEM((KV, R, D), F32),
        pltpu.VMEM((KV, R, 1), F32),
        pltpu.VMEM((KV, R, 1), F32),
    ]


def live_pages(first_page, cur, *, page, n_lp, trash, xp=jnp):
    """Pages of a slot's table row that the decode walk reads: those up to
    the one holding ``cur`` (all ``n_lp`` of a full ring), and none for a
    row that starts at the trash page (a retired or empty slot). ``xp`` is
    ``jnp`` in the kernel and ``numpy`` for the host's count of the same."""
    return xp.where(first_page == trash, 0, xp.minimum(n_lp, cur // page + 1))


def pages_per_block(page: int, kv: int, d: int, itemsize: int, n_lp: int) -> int:
    """Pages the decode walk copies and computes per block: about
    ``_BLOCK_TOKENS`` tokens, no more than fit the VMEM budget of the four
    K/V buffers (kv heads padded to the dtype's sublane tile), and no more
    than the layer attends."""
    sublanes = 8 * max(1, 4 // itemsize)
    page_bytes = page * (-(-kv // sublanes) * sublanes) * d * itemsize
    by_vmem = _KV_BUFFER_BYTES // (4 * page_bytes)
    return max(1, min(n_lp, _BLOCK_TOKENS // page, by_vmem))


def paged_decode_attention(
    q: jax.Array,  # (B, KV, G, D)
    k_pool: jax.Array,  # (P+1, page, KV, D) incl. trash page, or (L, P+1, ...)
    v_pool: jax.Array,
    page_table: jax.Array,  # (B, max_pages) int32 physical page per logical page
    cur_pos: jax.Array,  # (B,) int32 position of each slot's query token
    layer: jax.Array | None = None,  # int32 scalar: the stacked pools' layer
    *, n_lp: int, window: int = 0, sm_scale: float | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Flash decode over each slot's live pages of the shared pool.

    ``n_lp`` bounds the logical pages attended — ``ceil(window / page)`` for
    ring-folded windowed layers (a bounded working set), the full table
    width for dense. Within it a slot reads only its live pages
    (:func:`live_pages`), fetched ``pages_per_block`` pages at a time.
    """
    if interpret is None:
        from repro.kernels.ops import default_interpret

        interpret = default_interpret()
    B, KV, G, D = q.shape
    k_pool, v_pool, layer = _stacked_pools(k_pool, v_pool, layer)
    page = k_pool.shape[2]
    assert n_lp <= page_table.shape[1], (n_lp, page_table.shape)
    ppb = pages_per_block(page, KV, D, k_pool.dtype.itemsize, n_lp)
    sm = sm_scale if sm_scale is not None else D ** -0.5
    kernel = functools.partial(
        _paged_decode_kernel, sm_scale=sm, window=window, page=page, n_lp=n_lp,
        ppb=ppb,
    )
    q_spec = pl.BlockSpec((1, KV, G, D), lambda b, pt, cp, ly: (b, 0, 0, 0))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    kv_buf = pltpu.VMEM((2, ppb * page, KV, D), k_pool.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[q_spec, pool_spec, pool_spec],
        out_specs=q_spec,
        scratch_shapes=[
            kv_buf, kv_buf,
            pltpu.SemaphoreType.DMA((2, 2)),  # (k or v, buffer)
            *_row_state(KV, G, D),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        # Slots run in order on one core: the first zeroes the buffers.
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(page_table.astype(jnp.int32), cur_pos.astype(jnp.int32), layer, q, k_pool, v_pool)
