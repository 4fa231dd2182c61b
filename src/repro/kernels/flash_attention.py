"""Flash attention (fwd + bwd) as Pallas TPU kernels.

TPU adaptation of the FlashAttention-2 schedule [arXiv:2307.08691]:
  * no warps/shared-memory — tiles are BlockSpec VMEM blocks, the MXU sees
    (blk_q x d) @ (d x blk_k) contractions, and the online-softmax running
    (m, l, acc) state lives in VMEM scratch carried across the sequential
    innermost grid dimension (TPU grids execute minor-to-major in order,
    which replaces the GPU's explicit k-loop inside one program).
  * Q/K/V layout: (B*H, S, D) — heads are folded into the grid's major dim,
    so one program instance owns one (batch, head) pair.
  * causal/window masking is positional (jnp.where), with whole-block skips
    expressed via ``pl.when`` on the block indices.
  * blk_q/blk_k default to 128 (MXU-aligned); D is the full head dim.

Backward follows FA-2: LSE saved from fwd; one kernel computes dQ (k-inner
loop), a second computes dK/dV (q-inner loop). delta = rowsum(dO * O) is
computed outside in jnp (cheap, bandwidth-bound).

Per-row values (the running max and sum, LSE, delta) are 2-D ``(rows, 1)``
columns: the TPU compiler needs a block's last two dims tiled by (8, 128)
or equal to the array's, which a 1-D row vector cannot satisfy.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
F32 = jnp.float32


def _mask(qi, ki, blk_q, blk_k, causal, window, q_offset):
    """(blk_q, blk_k) boolean validity for this tile."""
    q_pos = q_offset + qi * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
    k_pos = ki * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
    m = jnp.ones((blk_q, blk_k), jnp.bool_)
    if causal:
        m = m & (k_pos <= q_pos)
    if window:
        m = m & (k_pos > q_pos - window)
    return m


# ==========================================================================
# Forward
# ==========================================================================
def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, sm_scale, causal, window, blk_q, blk_k, n_k, q_offset,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Whole-tile skip for causal/window structure.
    q_hi = q_offset + (qi + 1) * blk_q - 1  # highest query position in tile
    k_lo = ki * blk_k
    run = k_lo <= q_hi if causal else True
    if window:
        k_hi = (ki + 1) * blk_k - 1
        q_lo = q_offset + qi * blk_q
        run = jnp.logical_and(run, k_hi > q_lo - window)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(F32)  # (blk_q, D)
        k = k_ref[0].astype(F32)  # (blk_k, D)
        v = v_ref[0].astype(F32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=F32
        ) * sm_scale  # (blk_q, blk_k)
        msk = _mask(qi, ki, blk_q, blk_k, causal, window, q_offset)
        s = jnp.where(msk, s, NEG_INF)

        m_prev = m_ref[...]  # (blk_q, 1)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=F32
        )
        m_ref[...] = m_cur

    @pl.when(ki == n_k - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0] = (m_ref[...] + jnp.log(l)).astype(lse_ref.dtype)


def flash_attention_fwd(
    q: jax.Array,  # (BH, Sq, D)
    k: jax.Array,  # (BH, Sk, D)
    v: jax.Array,  # (BH, Sk, D)
    *, causal: bool = True, window: int = 0, sm_scale: float | None = None,
    blk_q: int = 128, blk_k: int = 128, q_offset: int = 0,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    if interpret is None:
        from repro.kernels.ops import default_interpret

        interpret = default_interpret()
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    blk_q = min(blk_q, Sq)
    blk_k = min(blk_k, Sk)
    assert Sq % blk_q == 0 and Sk % blk_k == 0, (Sq, blk_q, Sk, blk_k)
    sm = sm_scale if sm_scale is not None else D ** -0.5
    n_q, n_k = Sq // blk_q, Sk // blk_k

    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm, causal=causal, window=window,
        blk_q=blk_q, blk_k=blk_k, n_k=n_k, q_offset=q_offset,
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=(BH, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, blk_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, blk_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((BH, Sq, 1), F32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_q, D), F32),
            pltpu.VMEM((blk_q, 1), F32),
            pltpu.VMEM((blk_q, 1), F32),
        ],
        interpret=interpret,
    )(q, k, v)
    return o, lse


# ==========================================================================
# Backward: dQ kernel (loop over K blocks), dK/dV kernel (loop over Q blocks)
# ==========================================================================
def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref,
    *, sm_scale, causal, window, blk_q, blk_k, n_k, q_offset,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_hi = q_offset + (qi + 1) * blk_q - 1
    run = ki * blk_k <= q_hi if causal else True
    if window:
        run = jnp.logical_and(run, (ki + 1) * blk_k - 1 > q_offset + qi * blk_q - window)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(F32)
        k = k_ref[0].astype(F32)
        v = v_ref[0].astype(F32)
        do = do_ref[0].astype(F32)
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=F32) * sm_scale
        msk = _mask(qi, ki, blk_q, blk_k, causal, window, q_offset)
        p = jnp.where(msk, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=F32)
        ds = p * (dp - delta) * sm_scale
        acc_ref[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())), preferred_element_type=F32)

    @pl.when(ki == n_k - 1)
    def _flush():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, sm_scale, causal, window, blk_q, blk_k, n_q, q_offset,
):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_hi = q_offset + (qi + 1) * blk_q - 1
    run = ki * blk_k <= q_hi if causal else True
    if window:
        run = jnp.logical_and(run, (ki + 1) * blk_k - 1 > q_offset + qi * blk_q - window)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(F32)
        k = k_ref[0].astype(F32)
        v = v_ref[0].astype(F32)
        do = do_ref[0].astype(F32)
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=F32) * sm_scale
        msk = _mask(qi, ki, blk_q, blk_k, causal, window, q_offset)
        p = jnp.where(msk, jnp.exp(s - lse), 0.0)  # (blk_q, blk_k)
        dv_acc[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())), preferred_element_type=F32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=F32)
        ds = p * (dp - delta) * sm_scale
        dk_acc[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())), preferred_element_type=F32)

    @pl.when(qi == n_q - 1)
    def _flush():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd(
    q, k, v, o, lse, do,
    *, causal=True, window=0, sm_scale=None, blk_q=128, blk_k=128,
    q_offset=0, interpret=None,
):
    if interpret is None:
        from repro.kernels.ops import default_interpret

        interpret = default_interpret()
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    blk_q = min(blk_q, Sq)
    blk_k = min(blk_k, Sk)
    sm = sm_scale if sm_scale is not None else D ** -0.5
    n_q, n_k = Sq // blk_q, Sk // blk_k
    delta = jnp.sum(do.astype(F32) * o.astype(F32), axis=-1, keepdims=True)  # (BH, Sq, 1)

    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, sm_scale=sm, causal=causal, window=window,
            blk_q=blk_q, blk_k=blk_k, n_k=n_k, q_offset=q_offset,
        ),
        grid=(BH, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, blk_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, blk_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, blk_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((blk_q, D), F32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, sm_scale=sm, causal=causal, window=window,
            blk_q=blk_q, blk_k=blk_k, n_q=n_q, q_offset=q_offset,
        ),
        grid=(BH, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, blk_q, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, blk_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, blk_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, blk_q, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, blk_q, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, blk_q, 1), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, blk_k, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sk, D), k.dtype),
            jax.ShapeDtypeStruct((BH, Sk, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((blk_k, D), F32), pltpu.VMEM((blk_k, D), F32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv
