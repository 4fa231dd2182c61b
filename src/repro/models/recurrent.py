"""Recurrent blocks: RG-LRU (Griffin / RecurrentGemma) and xLSTM (mLSTM, sLSTM).

Training-time formulations are TPU-adapted:
  * RG-LRU uses ``jax.lax.associative_scan`` (log-depth parallel scan) — the
    Pallas ``rglru`` kernel is the blocked TPU hot path.
  * mLSTM uses the *chunkwise* parallel form: intra-chunk attention-like
    matmuls (MXU-friendly) + an inter-chunk state recurrence, numerically
    stabilised in log space. ``mlstm_sequential`` is the slow oracle used in
    tests; the Pallas ``mlstm`` kernel mirrors the chunkwise form.
  * sLSTM is inherently sequential (recurrent weights on h_{t-1}); it runs as
    a ``lax.scan`` of elementwise ops + per-head (dh x dh) matmuls.

All recurrence states are fp32.
"""
from __future__ import annotations

import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import (
    F32,
    causal_conv1d_step,
    causal_conv1d_train,
    cdt,
    groupnorm_heads,
)
from repro.models.schema import ParamSpec
from repro.sharding.rules import ShardingCtx, constrain

RGLRU_C = 8.0


# ==========================================================================
# RG-LRU
# ==========================================================================
class RGLRUState(NamedTuple):
    h: jax.Array  # (B, d_rnn) fp32
    conv: jax.Array  # (B, K-1, d_rnn)


def rglru_schema(cfg: ModelConfig) -> dict[str, Any]:
    d, dr = cfg.d_model, cfg.d_rnn
    K = cfg.conv_width
    return {
        "w_in_rec": ParamSpec((d, dr), ("embed", "rnn")),
        "w_in_gate": ParamSpec((d, dr), ("embed", "rnn")),
        "conv_w": ParamSpec((K, dr), ("conv", "rnn"), scale=1.0 / math.sqrt(K)),
        "conv_b": ParamSpec((dr,), ("rnn",), init="zeros"),
        "w_rec_gate": ParamSpec((dr, dr), ("rnn", None)),
        "b_rec_gate": ParamSpec((dr,), ("rnn",), init="zeros"),
        "w_inp_gate": ParamSpec((dr, dr), ("rnn", None)),
        "b_inp_gate": ParamSpec((dr,), ("rnn",), init="zeros"),
        "log_lambda": ParamSpec((dr,), ("rnn",), init="normal", scale=0.5),
        "w_out": ParamSpec((dr, d), ("rnn", "embed")),
    }


def _rglru_coeffs(p: dict[str, Any], u: jax.Array) -> tuple[jax.Array, jax.Array]:
    """u: (..., d_rnn) conv output. Returns (a, gated_input) in fp32."""
    uf = u.astype(F32)
    r = jax.nn.sigmoid(uf @ p["w_rec_gate"].astype(F32) + p["b_rec_gate"].astype(F32))
    i = jax.nn.sigmoid(uf @ p["w_inp_gate"].astype(F32) + p["b_inp_gate"].astype(F32))
    log_a = -RGLRU_C * r * jax.nn.softplus(p["log_lambda"].astype(F32))
    a = jnp.exp(log_a)
    beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-9))
    return a, beta * (i * uf)


def rglru_scan(a: jax.Array, b: jax.Array) -> jax.Array:
    """Parallel scan of h_t = a_t h_{t-1} + b_t over axis=1. (B,S,D) fp32."""

    def combine(x, y):
        a1, b1 = x
        a2, b2 = y
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    return h


def rglru_block(
    p: dict[str, Any],
    cfg: ModelConfig,
    x: jax.Array,  # (B, S, d)
    *,
    mode: str,
    state: RGLRUState | None = None,
    chunk_len: jax.Array | None = None,  # valid tokens (chunk mode)
    sctx: ShardingCtx,
) -> tuple[jax.Array, RGLRUState | None]:
    dt = cdt(cfg)
    u = jnp.einsum("bsd,dr->bsr", x, p["w_in_rec"].astype(dt), preferred_element_type=F32).astype(dt)
    g = jax.nn.gelu(
        jnp.einsum("bsd,dr->bsr", x, p["w_in_gate"].astype(dt), preferred_element_type=F32)
    ).astype(dt)
    u = constrain(u, ("batch", "seq", "rnn"), sctx)
    K = cfg.conv_width

    new_state: RGLRUState | None = None
    if mode == "decode":
        assert state is not None
        u_t, conv_state = causal_conv1d_step(u[:, 0], state.conv, p["conv_w"], p["conv_b"])
        a, gated = _rglru_coeffs(p, u_t)
        h = a * state.h + gated  # (B, dr) fp32
        new_state = RGLRUState(h=h, conv=conv_state)
        h = h[:, None, :]
    elif mode == "chunk":
        # Chunked prefill: carry the recurrence across chunks. The conv sees
        # the previous chunk's tap state as left context; the scan starts
        # from the carried h. Padded tail positions run but the new state is
        # read at chunk_len - 1, so they influence nothing downstream.
        assert state is not None and chunk_len is not None
        u_ext = jnp.concatenate([state.conv.astype(u.dtype), u], axis=1)
        u_c = causal_conv1d_train(u_ext, p["conv_w"], p["conv_b"])[:, K - 1 :]
        a, gated = _rglru_coeffs(p, u_c)
        gated = gated.at[:, 0].add(a[:, 0] * state.h)
        h = rglru_scan(a, gated)  # (B, S, dr) fp32
        h_last = jax.lax.dynamic_slice_in_dim(h, chunk_len - 1, 1, axis=1)[:, 0]
        conv_new = jax.lax.dynamic_slice_in_dim(u_ext, chunk_len, K - 1, axis=1)
        new_state = RGLRUState(h=h_last, conv=conv_new.astype(F32))
    else:
        u_c = causal_conv1d_train(u, p["conv_w"], p["conv_b"])
        a, gated = _rglru_coeffs(p, u_c)
        h = rglru_scan(a, gated)  # (B, S, dr) fp32
        if mode == "prefill":
            new_state = RGLRUState(
                h=h[:, -1], conv=u[:, -(K - 1) :].astype(F32)
            )
    y = h.astype(dt) * g
    out = jnp.einsum("bsr,rd->bsd", y, p["w_out"].astype(dt), preferred_element_type=F32)
    return constrain(out.astype(dt), ("batch", "seq", "embed_act"), sctx), new_state


def init_rglru_state(cfg: ModelConfig, batch: int) -> dict[str, ParamSpec]:
    return {
        "h": ParamSpec((batch, cfg.d_rnn), ("batch", "rnn"), dtype=F32, init="zeros"),
        "conv": ParamSpec(
            (batch, cfg.conv_width - 1, cfg.d_rnn), ("batch", None, "rnn"), dtype=F32, init="zeros"
        ),
    }


# ==========================================================================
# mLSTM (xLSTM matrix-memory block)
# ==========================================================================
class MLSTMState(NamedTuple):
    C: jax.Array  # (B, nh, dh, dh) fp32
    n: jax.Array  # (B, nh, dh)
    m: jax.Array  # (B, nh)
    conv: jax.Array  # (B, K-1, dp) conv tap state (dp = proj dim)


def mlstm_schema(cfg: ModelConfig) -> dict[str, Any]:
    d = cfg.d_model
    dp = int(cfg.mlstm_proj_factor * d)
    nh = cfg.n_heads
    dh = dp // nh
    K = cfg.conv_width
    # All axes model-replicated: the (B,S,dp)->(B,S,nh,dh) head reshape does
    # not commute with a 16-way dp sharding (measured: XLA "involuntary full
    # rematerialization" per chunk). xLSTM at 1.3B parallelises with wide DP
    # (dp_wide profile: batch over data x model); masters/moments still ZeRO-
    # shard over both axes.
    return {
        "w_up_main": ParamSpec((d, dp), ("embed", None)),
        "w_up_gate": ParamSpec((d, dp), ("embed", None)),
        "conv_w": ParamSpec((K, dp), ("conv", None), scale=1.0 / math.sqrt(K)),
        "conv_b": ParamSpec((dp,), (None,), init="zeros"),
        # Per-head (block-diagonal) q/k/v projections.
        "wq": ParamSpec((nh, dh, dh), (None, None, None)),
        "wk": ParamSpec((nh, dh, dh), (None, None, None)),
        "wv": ParamSpec((nh, dh, dh), (None, None, None)),
        "w_igate": ParamSpec((dp, nh), (None, None), init="small"),
        "b_igate": ParamSpec((nh,), (None,), init="zeros"),
        "w_fgate": ParamSpec((dp, nh), (None, None), init="small"),
        "b_fgate": ParamSpec((nh,), (None,), init="ones", scale=3.0),
        "learnable_skip": ParamSpec((dp,), (None,), init="ones"),
        "w_down": ParamSpec((dp, d), (None, "embed")),
    }


def mlstm_chunked(
    q: jax.Array,  # (B, S, nh, dh)  (already scaled by dh^-0.5)
    k: jax.Array,
    v: jax.Array,
    i_pre: jax.Array,  # (B, S, nh) input-gate pre-activation (log-space gate)
    f_pre: jax.Array,  # (B, S, nh) forget-gate pre-activation
    state: tuple[jax.Array, jax.Array, jax.Array] | None = None,
    chunk: int = 64,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array, jax.Array]]:
    """Chunkwise-parallel stabilised mLSTM. Returns (h (B,S,nh,dh), final state)."""
    B, S, nh, dh = q.shape
    L = max(1, min(chunk, S))
    assert S % L == 0, f"seq {S} must divide chunk {L}"
    N = S // L
    f32 = F32

    qf = q.astype(f32)
    kf = k.astype(f32)
    vf = v.astype(f32)
    log_f = -jax.nn.softplus(-f_pre.astype(f32))  # log sigmoid(f_pre)
    a = i_pre.astype(f32)  # log input gate (exponential gating)

    def reshape_chunks(x):
        return x.reshape(B, N, L, *x.shape[2:]).swapaxes(0, 1)  # (N, B, L, ...)

    qs, ks, vs = map(reshape_chunks, (qf, kf, vf))
    a_s = reshape_chunks(a)  # (N, B, L, nh)
    g_s = reshape_chunks(log_f)

    if state is None:
        C0 = jnp.zeros((B, nh, dh, dh), f32)
        n0 = jnp.zeros((B, nh, dh), f32)
        m0 = jnp.full((B, nh), -1e30, f32)
    else:
        C0, n0, m0 = state

    def body(carry, inp):
        C, n, m = carry
        qc, kc, vc, ac, gc = inp  # (B, L, ...)
        b = jnp.cumsum(gc, axis=1)  # (B, L, nh) within-chunk decay cumsum
        btot = b[:, -1]  # (B, nh)

        # Per-position output stabiliser: max(state path, best intra path).
        intra_carry = ac - b  # (B, L, nh): a_s - b_s (add b_t later)
        run_max = jax.lax.cummax(intra_carry, axis=1)
        m_state = b + m[:, None, :]  # (B, L, nh)
        m_out = jnp.maximum(m_state, b + run_max)

        # Intra-chunk weights D[t, s] = exp(a_s + b_t - b_s - m_out_t), s <= t.
        scores = jnp.einsum("blhd,bshd->bhls", qc, kc)  # (B, nh, L, L)
        ldec = b[:, :, None, :].swapaxes(1, 3)  # -> we build explicitly below
        a_sb = (ac - b)  # (B, L, nh)
        logD = (
            b.transpose(0, 2, 1)[:, :, :, None]  # b_t: (B, nh, L, 1)
            + a_sb.transpose(0, 2, 1)[:, :, None, :]  # a_s - b_s: (B, nh, 1, L)
            - m_out.transpose(0, 2, 1)[:, :, :, None]  # m_out_t
        )
        causal = jnp.tril(jnp.ones((L, L), bool))
        D = jnp.where(causal[None, None], jnp.exp(logD), 0.0)
        intra_num = jnp.einsum("bhls,bshd->blhd", scores * D, vc)
        intra_den = jnp.einsum("bhls,bshd,bshd->blh", D, qc, kc) if False else jnp.einsum(
            "bhls,bhs->blh", scores * D, jnp.ones((B, nh, L), f32)
        )
        # NOTE: denominator uses sum_s D[t,s] * (q_t . k_s) == rowsum of scores*D
        # (matches n_t . q_t for the stabilised recurrence).

        # Inter-chunk (state) contribution.
        sdec = jnp.exp(m_state - m_out)  # (B, L, nh)
        inter_num = jnp.einsum("blhd,bhde->blhe", qc, C) * sdec[..., None]
        inter_den = jnp.einsum("blhd,bhd->blh", qc, n) * sdec

        num = intra_num + inter_num
        den = inter_den + intra_den
        denom = jnp.maximum(jnp.abs(den), jnp.exp(-m_out))
        h = num / denom[..., None]  # (B, L, nh, dh)

        # State update to chunk end.
        m_a = jnp.max(ac + btot[:, None, :] - b, axis=1)  # (B, nh)
        m_new = jnp.maximum(m + btot, m_a)
        state_scale = jnp.exp(m + btot - m_new)  # (B, nh)
        in_w = jnp.exp(ac + btot[:, None, :] - b - m_new[:, None, :])  # (B, L, nh)
        C_new = C * state_scale[..., None, None] + jnp.einsum(
            "bshd,bshe,bsh->bhde", kc, vc, in_w
        )
        n_new = n * state_scale[..., None] + jnp.einsum("bshd,bsh->bhd", kc, in_w)
        return (C_new, n_new, m_new), h

    (Cf, nf, mf), hs = jax.lax.scan(body, (C0, n0, m0), (qs, ks, vs, a_s, g_s))
    h = hs.swapaxes(0, 1).reshape(B, S, nh, dh)
    return h, (Cf, nf, mf)


def mlstm_sequential(
    q: jax.Array, k: jax.Array, v: jax.Array, i_pre: jax.Array, f_pre: jax.Array,
    state: tuple[jax.Array, jax.Array, jax.Array] | None = None,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array, jax.Array]]:
    """Step-by-step oracle (tests only)."""
    B, S, nh, dh = q.shape
    if state is None:
        C = jnp.zeros((B, nh, dh, dh), F32)
        n = jnp.zeros((B, nh, dh), F32)
        m = jnp.full((B, nh), -1e30, F32)
    else:
        C, n, m = state

    def step(carry, t):
        C, n, m = carry
        qt, kt, vt = q[:, t].astype(F32), k[:, t].astype(F32), v[:, t].astype(F32)
        at = i_pre[:, t].astype(F32)
        lf = -jax.nn.softplus(-f_pre[:, t].astype(F32))
        m_new = jnp.maximum(lf + m, at)
        fp = jnp.exp(lf + m - m_new)
        ip = jnp.exp(at - m_new)
        C = C * fp[..., None, None] + ip[..., None, None] * jnp.einsum("bhd,bhe->bhde", kt, vt)
        n = n * fp[..., None] + ip[..., None] * kt
        num = jnp.einsum("bhd,bhde->bhe", qt, C)
        den = jnp.einsum("bhd,bhd->bh", qt, n)
        denom = jnp.maximum(jnp.abs(den), jnp.exp(-m_new))
        h = num / denom[..., None]
        return (C, n, m_new), h

    (C, n, m), hs = jax.lax.scan(step, (C, n, m), jnp.arange(S))
    return hs.swapaxes(0, 1).reshape(B, S, nh, dh), (C, n, m)


def mlstm_step(
    q, k, v, i_pre, f_pre, state
):
    """One decode step. q/k/v: (B, nh, dh); gates: (B, nh)."""
    C, n, m = state
    at = i_pre.astype(F32)
    lf = -jax.nn.softplus(-f_pre.astype(F32))
    m_new = jnp.maximum(lf + m, at)
    fp = jnp.exp(lf + m - m_new)
    ip = jnp.exp(at - m_new)
    C = C * fp[..., None, None] + ip[..., None, None] * jnp.einsum("bhd,bhe->bhde", k.astype(F32), v.astype(F32))
    n = n * fp[..., None] + ip[..., None] * k.astype(F32)
    num = jnp.einsum("bhd,bhde->bhe", q.astype(F32), C)
    den = jnp.einsum("bhd,bhd->bh", q.astype(F32), n)
    denom = jnp.maximum(jnp.abs(den), jnp.exp(-m_new))
    return num / denom[..., None], (C, n, m_new)


def mlstm_block(
    p: dict[str, Any],
    cfg: ModelConfig,
    x: jax.Array,  # (B, S, d)
    *,
    mode: str,
    state: MLSTMState | None = None,
    chunk_len: jax.Array | None = None,  # valid tokens (chunk mode)
    sctx: ShardingCtx,
) -> tuple[jax.Array, MLSTMState | None]:
    dt = cdt(cfg)
    B, S, d = x.shape
    dp = int(cfg.mlstm_proj_factor * d)
    nh = cfg.n_heads
    dh = dp // nh

    u = jnp.einsum("bsd,dp->bsp", x, p["w_up_main"].astype(dt), preferred_element_type=F32).astype(dt)
    z = jnp.einsum("bsd,dp->bsp", x, p["w_up_gate"].astype(dt), preferred_element_type=F32).astype(dt)
    u = constrain(u, ("batch", "seq", None), sctx)

    K = cfg.conv_width
    new_conv = None
    u_ext = None
    if mode == "decode":
        assert state is not None
        uc_t, new_conv = causal_conv1d_step(u[:, 0], state.conv, p["conv_w"], p["conv_b"])
        uc = jax.nn.silu(uc_t.astype(F32)).astype(dt)[:, None, :]
    elif mode == "chunk":
        # Chunked prefill: the previous chunk's conv taps are the left
        # context; gate masking below makes padded tail steps exact
        # identity updates of the recurrence state.
        assert state is not None and chunk_len is not None
        u_ext = jnp.concatenate([state.conv.astype(u.dtype), u], axis=1)
        uc = jax.nn.silu(
            causal_conv1d_train(u_ext, p["conv_w"], p["conv_b"])[:, K - 1 :].astype(F32)
        ).astype(dt)
    else:
        uc = jax.nn.silu(
            causal_conv1d_train(u, p["conv_w"], p["conv_b"]).astype(F32)
        ).astype(dt)

    uc_h = uc.reshape(B, -1, nh, dh)
    q = jnp.einsum("bshd,hde->bshe", uc_h, p["wq"].astype(dt), preferred_element_type=F32).astype(dt) * (dh ** -0.5)
    k = jnp.einsum("bshd,hde->bshe", uc_h, p["wk"].astype(dt), preferred_element_type=F32).astype(dt) * (dh ** -0.5)
    u_h = u.reshape(B, -1, nh, dh)
    v = jnp.einsum("bshd,hde->bshe", u_h, p["wv"].astype(dt), preferred_element_type=F32).astype(dt)
    i_pre = jnp.einsum("bsp,ph->bsh", uc, p["w_igate"].astype(F32)) + p["b_igate"].astype(F32)
    f_pre = jnp.einsum("bsp,ph->bsh", uc, p["w_fgate"].astype(F32)) + p["b_fgate"].astype(F32)

    new_state: MLSTMState | None = None
    if mode == "decode":
        h, (C, n, m) = mlstm_step(
            q[:, 0], k[:, 0], v[:, 0], i_pre[:, 0], f_pre[:, 0], (state.C, state.n, state.m)
        )
        h = h[:, None]
        new_state = MLSTMState(C=C, n=n, m=m, conv=new_conv)
    elif mode == "chunk":
        # Padded tail steps become exact no-ops: forget gate saturates to
        # log f = 0 and the input gate to weight 0 (both exact in fp32), so
        # the chunk-end state equals the state at chunk_len - 1.
        valid = (jnp.arange(S) < chunk_len)[None, :, None]
        i_pre = jnp.where(valid, i_pre, -1e30)
        f_pre = jnp.where(valid, f_pre, 1e9)
        h, (C, n, m) = mlstm_chunked(
            q, k, v, i_pre, f_pre,
            state=(state.C, state.n, state.m), chunk=64 if S >= 64 else S,
        )
        conv_new = jax.lax.dynamic_slice_in_dim(u_ext, chunk_len, K - 1, axis=1)
        new_state = MLSTMState(C=C, n=n, m=m, conv=conv_new.astype(F32))
    else:
        h, (C, n, m) = mlstm_chunked(q, k, v, i_pre, f_pre, chunk=64 if S >= 64 else S)
        if mode == "prefill":
            new_state = MLSTMState(
                C=C, n=n, m=m, conv=u[:, -(K - 1) :].astype(F32)
            )

    h = groupnorm_heads(h).reshape(B, -1, dp).astype(dt)
    h = h + p["learnable_skip"].astype(dt) * uc
    y = h * jax.nn.silu(z.astype(F32)).astype(dt)
    out = jnp.einsum("bsp,pd->bsd", y, p["w_down"].astype(dt), preferred_element_type=F32)
    return constrain(out.astype(dt), ("batch", "seq", "embed_act"), sctx), new_state


def init_mlstm_state(cfg: ModelConfig, batch: int) -> dict[str, ParamSpec]:
    dp = int(cfg.mlstm_proj_factor * cfg.d_model)
    nh = cfg.n_heads
    dh = dp // nh
    return {
        "C": ParamSpec((batch, nh, dh, dh), ("batch", "heads", "state_row", "state_col"), dtype=F32, init="zeros"),
        "n": ParamSpec((batch, nh, dh), ("batch", "heads", "state_col"), dtype=F32, init="zeros"),
        "m": ParamSpec((batch, nh), ("batch", "heads"), dtype=F32, init="zeros"),
        "conv": ParamSpec((batch, cfg.conv_width - 1, dp), ("batch", None, "mlp"), dtype=F32, init="zeros"),
    }


# ==========================================================================
# sLSTM (xLSTM scalar-memory block)
# ==========================================================================
class SLSTMState(NamedTuple):
    c: jax.Array  # (B, nh, dh) fp32
    n: jax.Array
    h: jax.Array
    m: jax.Array


def slstm_schema(cfg: ModelConfig) -> dict[str, Any]:
    d = cfg.d_model
    nh = cfg.n_heads
    dh = d // nh
    ffs = int(cfg.slstm_proj_factor * d)
    # Recurrent weights stay replicated over the model axis: sharding the
    # tiny (dh x dh) recurrences would emit collectives inside the
    # per-timestep scan (measured: ~600k all-gathers per step). sLSTM is
    # data-parallel by construction; the FFN below still tensor-parallelises.
    return {
        "w_gates": ParamSpec((d, 4, nh, dh), ("embed", None, None, None)),
        "r_gates": ParamSpec((nh, dh, 4, dh), (None, None, None, None), init="small"),
        "b_gates": ParamSpec((4, nh, dh), (None, None, None), init="zeros"),
        "ffn_gate": ParamSpec((d, ffs), ("embed", "mlp")),
        "ffn_up": ParamSpec((d, ffs), ("embed", "mlp")),
        "ffn_down": ParamSpec((ffs, d), ("mlp", "embed")),
    }


def slstm_scan(
    gates: jax.Array,  # (B, S, 4, nh, dh) pre-activations from W x + b
    r: jax.Array,  # (nh, dh, 4, dh) recurrent weights
    state: SLSTMState,
    valid: jax.Array | None = None,  # (S,) True for real tokens (chunk mode)
) -> tuple[jax.Array, SLSTMState]:
    B, S = gates.shape[:2]

    def step(carry: SLSTMState, inp):
        g_t, valid_t = inp
        rec = jnp.einsum("bhd,hdge->bghe", carry.h, r.astype(F32))  # (B,4,nh,dh)
        z_pre, i_pre, f_pre, o_pre = [
            g_t[:, j].astype(F32) + rec[:, j] for j in range(4)
        ]
        z = jnp.tanh(z_pre)
        o = jax.nn.sigmoid(o_pre)
        log_f = -jax.nn.softplus(-f_pre)
        m_new = jnp.maximum(log_f + carry.m, i_pre)
        fp = jnp.exp(log_f + carry.m - m_new)
        ip = jnp.exp(i_pre - m_new)
        c = fp * carry.c + ip * z
        n = jnp.maximum(fp * carry.n + ip, 1e-6)
        h = o * (c / n)
        new = SLSTMState(c=c, n=n, h=h, m=m_new)
        # Padded chunk-tail steps must not touch the recurrence (h feeds
        # back through the recurrent weights, so gate saturation alone
        # would not keep it frozen).
        new = jax.tree.map(lambda a, b: jnp.where(valid_t, a, b), new, carry)
        return new, h

    v = jnp.ones((S,), bool) if valid is None else valid
    final, hs = jax.lax.scan(step, state, (gates.swapaxes(0, 1), v))
    return hs.swapaxes(0, 1), final  # (B, S, nh, dh)


def _shard_map_batched(fn, sctx: ShardingCtx, batch_dim_size: int):
    """Run the recurrence per batch shard via shard_map.

    The sLSTM recurrent weight is reused every timestep; under plain SPMD
    with a sharded batch, its gradient accumulation forces an all-reduce per
    timestep (measured: 5.7 TB/step at 4k seq). Inside shard_map the batch
    contraction is local, so the transpose inserts ONE psum at the boundary.
    """
    mesh = sctx.mesh
    if mesh is None:
        return fn
    axes: list = []
    size = 1
    for a in sctx.profile.candidates("batch"):
        if a in mesh.shape and batch_dim_size % (size * mesh.shape[a]) == 0:
            axes.append(a)
            size *= mesh.shape[a]
    if not axes:
        return fn
    from jax.sharding import PartitionSpec as P

    bspec = P(tuple(axes))

    def wrapped(gates, r, state):
        return jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=(bspec, P(), jax.tree.map(lambda _: bspec, state)),
            out_specs=(bspec, jax.tree.map(lambda _: bspec, state)),
            check_vma=False,
        )(gates, r, state)

    return wrapped


def slstm_block(
    p: dict[str, Any],
    cfg: ModelConfig,
    x: jax.Array,
    *,
    mode: str,
    state: SLSTMState | None = None,
    chunk_len: jax.Array | None = None,  # valid tokens (chunk mode)
    sctx: ShardingCtx,
) -> tuple[jax.Array, SLSTMState | None]:
    dt = cdt(cfg)
    B, S, d = x.shape
    nh = cfg.n_heads
    dh = d // nh
    gates = (
        jnp.einsum("bsd,dghe->bsghe", x, p["w_gates"].astype(dt), preferred_element_type=F32)
        + p["b_gates"].astype(F32)
    )  # (B, S, 4, nh, dh)
    if state is None:
        state = SLSTMState(
            c=jnp.zeros((B, nh, dh), F32),
            n=jnp.ones((B, nh, dh), F32) * 1e-6,
            h=jnp.zeros((B, nh, dh), F32),
            m=jnp.full((B, nh, dh), -1e30, F32),
        )
    if mode == "chunk":
        # Chunk serving is per-slot (B == 1): run the recurrence directly
        # from the carried state, masking padded tail steps.
        assert chunk_len is not None
        hs, final = slstm_scan(
            gates.astype(F32), p["r_gates"].astype(F32), state,
            valid=jnp.arange(S) < chunk_len,
        )
    else:
        scan_fn = _shard_map_batched(slstm_scan, sctx, B)
        hs, final = scan_fn(gates.astype(F32), p["r_gates"].astype(F32), state)
    h = groupnorm_heads(hs).reshape(B, S, d).astype(dt)
    # Post-recurrence gated FFN (proj factor 4/3), part of the sLSTM block.
    g = jnp.einsum("bsd,df->bsf", h, p["ffn_gate"].astype(dt), preferred_element_type=F32)
    u = jnp.einsum("bsd,df->bsf", h, p["ffn_up"].astype(dt), preferred_element_type=F32)
    y = (jax.nn.gelu(g) * u).astype(dt)
    out = jnp.einsum("bsf,fd->bsd", y, p["ffn_down"].astype(dt), preferred_element_type=F32)
    new_state = final if mode in ("prefill", "decode", "chunk") else None
    return constrain(out.astype(dt), ("batch", "seq", "embed_act"), sctx), new_state


def init_slstm_state(cfg: ModelConfig, batch: int) -> dict[str, ParamSpec]:
    nh = cfg.n_heads
    dh = cfg.d_model // nh
    mk = lambda init: ParamSpec((batch, nh, dh), ("batch", "heads", "state_col"), dtype=F32, init=init)
    return {"c": mk("zeros"), "n": mk("zeros"), "h": mk("zeros"), "m": mk("zeros")}
