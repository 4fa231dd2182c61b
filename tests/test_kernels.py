"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps
and property tests on chunking invariance."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops, ref
from repro.kernels.moe_gmm import pad_group_sizes_to_blocks

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _tol(dtype):
    return TOL[jnp.bfloat16] if jnp.dtype(dtype) == jnp.bfloat16 else TOL[jnp.float32]


def _fold(x):
    B, S, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)


def _bcast_kv(k, H):
    B, S, KV, D = k.shape
    G = H // KV
    return jnp.broadcast_to(k[:, :, :, None, :], (B, S, KV, G, D)).reshape(B, S, H, D)


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "B,S,H,KV,D,blk,causal,window",
        [
            (2, 128, 4, 4, 64, 64, True, 0),  # MHA causal
            (1, 256, 8, 2, 64, 64, True, 0),  # GQA causal
            (2, 128, 4, 1, 32, 32, True, 0),  # MQA
            (1, 128, 2, 2, 64, 64, False, 0),  # bidirectional
            (1, 256, 2, 2, 64, 64, True, 64),  # sliding window
            (1, 192, 2, 2, 128, 64, True, 0),  # non-pow2 seq, d=128
        ],
    )
    def test_fwd_vs_ref(self, dtype, B, S, H, KV, D, blk, causal, window):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, S, H, D), dtype)
        k = jax.random.normal(ks[1], (B, S, KV, D), dtype)
        v = jax.random.normal(ks[2], (B, S, KV, D), dtype)
        o = ops.flash_attention(q, k, v, causal=causal, window=window, blk_q=blk, blk_k=blk)
        oref = ref.sdpa_ref(
            _fold(q), _fold(_bcast_kv(k, H)), _fold(_bcast_kv(v, H)),
            causal=causal, window=window,
        ).reshape(B, H, S, D).transpose(0, 2, 1, 3)
        err = float(jnp.max(jnp.abs(o.astype(jnp.float32) - oref.astype(jnp.float32))))
        assert err < _tol(dtype), err

    def test_bwd_vs_autodiff_ref(self):
        B, S, H, KV, D = 1, 128, 2, 1, 32
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (B, S, H, D))
        k = jax.random.normal(ks[1], (B, S, KV, D))
        v = jax.random.normal(ks[2], (B, S, KV, D))

        def loss_k(q, k, v):
            return jnp.sum(ops.flash_attention(q, k, v, causal=True, blk_q=64, blk_k=64) ** 2)

        def loss_r(q, k, v):
            o = ref.sdpa_ref(
                _fold(q), _fold(_bcast_kv(k, H)), _fold(_bcast_kv(v, H)), causal=True
            )
            return jnp.sum(o ** 2)

        gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gk, gr, "qkv"):
            scale = float(jnp.max(jnp.abs(b))) + 1e-6
            err = float(jnp.max(jnp.abs(a - b))) / scale
            assert err < 1e-4, f"d{name} rel err {err}"

    def test_block_size_invariance(self):
        B, S, H, D = 1, 256, 2, 64
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (B, S, H, D))
        k = jax.random.normal(ks[1], (B, S, H, D))
        v = jax.random.normal(ks[2], (B, S, H, D))
        o1 = ops.flash_attention(q, k, v, blk_q=64, blk_k=64)
        o2 = ops.flash_attention(q, k, v, blk_q=128, blk_k=32)
        assert float(jnp.max(jnp.abs(o1 - o2))) < 1e-5


class TestStackedPagedKernels:
    """The paged kernels read a layer of the whole stack's pools at a layer
    index: at the first and the last layer of a pool whose layers hold
    different data, each equals the reference on that layer alone."""

    L, P, PAGE, KV, G, D, MP = 3, 12, 8, 2, 4, 16, 5

    def _stack(self, seed):
        rng = np.random.default_rng(seed)
        shape = (self.L, self.P + 1, self.PAGE, self.KV, self.D)
        # Layer l's values sit near 3 * l: every layer reads differently.
        off = 3.0 * np.arange(self.L).reshape(-1, 1, 1, 1, 1)
        kp = jnp.asarray(rng.normal(size=shape) + off, jnp.float32)
        vp = jnp.asarray(rng.normal(size=shape) - off, jnp.float32)
        pt = np.full((2, self.MP), self.P, np.int32)  # row P: the trash page
        pt[0, :4] = [3, 7, 0, 9]
        pt[1, :2] = [5, 11]
        return rng, kp, vp, jnp.asarray(pt)

    @pytest.mark.parametrize("layer", [0, L - 1], ids=["first", "last"])
    def test_decode_matches_ref(self, layer):
        rng, kp, vp, pt = self._stack(0)
        q = jnp.asarray(rng.normal(size=(2, 1, self.KV * self.G, self.D)), jnp.float32)
        cur = jnp.asarray([27, 12], jnp.int32)
        o = ops.paged_decode_attention_op(
            q, kp, vp, pt, cur, jnp.asarray(layer, jnp.int32), n_lp=self.MP
        )
        oref = ref.paged_decode_attention_ref(
            q.reshape(2, self.KV, self.G, self.D), kp[layer], vp[layer], pt, cur,
            n_lp=self.MP,
        )
        err = float(jnp.max(jnp.abs(o.reshape(oref.shape) - oref)))
        assert err < _tol(jnp.float32), err

    @pytest.mark.parametrize("layer", [0, L - 1], ids=["first", "last"])
    def test_chunk_matches_ref(self, layer):
        rng, kp, vp, pt = self._stack(1)
        C = 8
        q = jnp.asarray(rng.normal(size=(1, C, self.KV * self.G, self.D)), jnp.float32)
        start = jnp.asarray([19], jnp.int32)
        o = ops.paged_chunk_attention_op(
            q, kp, vp, pt[:1], start, jnp.asarray(layer, jnp.int32), n_lp=self.MP
        )
        qf = q.reshape(1, C, self.KV, self.G, self.D).transpose(0, 2, 1, 3, 4)
        oref = ref.paged_chunk_attention_ref(
            qf.reshape(1, self.KV, C * self.G, self.D), kp[layer], vp[layer],
            pt[:1], start, n_lp=self.MP, group=self.G,
        )
        oref = oref.reshape(1, self.KV, C, self.G, self.D).transpose(0, 2, 1, 3, 4)
        err = float(jnp.max(jnp.abs(o - oref.reshape(o.shape))))
        assert err < _tol(jnp.float32), err


class TestDecodeAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("window", [0, 64])
    def test_vs_ref(self, dtype, window):
        B, H, KV, D, T = 2, 4, 2, 64, 256
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, 1, H, D), dtype)
        kc = jax.random.normal(ks[1], (B, T, KV, D), dtype)
        vc = jax.random.normal(ks[2], (B, T, KV, D), dtype)
        k_pos = jnp.arange(T)
        cur = jnp.asarray(137)
        o = ops.decode_attention_op(q, kc, vc, k_pos, cur, window=window, blk_k=64)
        G = H // KV
        qf = q.reshape(B, KV, G, D).reshape(B * KV, G, D)
        kf = kc.transpose(0, 2, 1, 3).reshape(B * KV, T, D)
        vf = vc.transpose(0, 2, 1, 3).reshape(B * KV, T, D)
        oref = ref.decode_attention_ref(qf, kf, vf, k_pos, cur, window=window)
        oref = oref.reshape(B, KV, G, D).reshape(B, 1, H, D)
        err = float(jnp.max(jnp.abs(o.astype(jnp.float32) - oref.astype(jnp.float32))))
        assert err < _tol(dtype), err

    def test_ring_positions_mask_unwritten(self):
        """Negative k_pos (never-written ring slots) must not contribute."""
        B, H, KV, D, T = 1, 2, 1, 32, 64
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (B, 1, H, D))
        kc = jax.random.normal(ks[1], (B, T, KV, D))
        vc = jax.random.normal(ks[2], (B, T, KV, D))
        k_pos = jnp.where(jnp.arange(T) < 10, jnp.arange(T), -1)
        cur = jnp.asarray(9)
        o = ops.decode_attention_op(q, kc, vc, k_pos, cur, blk_k=32)
        # corrupting masked slots must not change the output
        kc2 = kc.at[:, 10:].set(1e3)
        o2 = ops.decode_attention_op(q, kc2, vc, k_pos, cur, blk_k=32)
        assert float(jnp.max(jnp.abs(o - o2))) == 0.0


class TestRGLRU:
    @pytest.mark.parametrize("B,T,D,bt,bd", [(2, 128, 256, 32, 128), (1, 64, 128, 64, 64)])
    def test_vs_ref(self, B, T, D, bt, bd):
        ks = jax.random.split(jax.random.PRNGKey(0), 2)
        a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, T, D)))
        b = jax.random.normal(ks[1], (B, T, D)) * 0.1
        h = ops.rglru_op(a, b, blk_t=bt, blk_d=bd)
        hr = ref.rglru_ref(a, b)
        assert float(jnp.max(jnp.abs(h - hr))) < 1e-5

    def test_initial_state(self):
        B, T, D = 1, 32, 64
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, T, D)))
        b = jax.random.normal(ks[1], (B, T, D))
        h0 = jax.random.normal(ks[2], (B, D))
        h = ops.rglru_op(a, b, h0, blk_t=16, blk_d=64)
        hr = ref.rglru_ref(a, b, h0)
        assert float(jnp.max(jnp.abs(h - hr))) < 1e-5

    @given(
        t=st.sampled_from([16, 32, 64]),
        bt=st.sampled_from([8, 16, 32]),
        seed=st.integers(0, 100),
    )
    @settings(max_examples=10, deadline=None)
    def test_property_block_invariance(self, t, bt, seed):
        ks = jax.random.split(jax.random.PRNGKey(seed), 2)
        a = jax.nn.sigmoid(jax.random.normal(ks[0], (1, t, 128)))
        b = jax.random.normal(ks[1], (1, t, 128))
        h = ops.rglru_op(a, b, blk_t=min(bt, t), blk_d=128)
        hr = ref.rglru_ref(a, b)
        assert float(jnp.max(jnp.abs(h - hr))) < 1e-5


class TestMLSTM:
    @pytest.mark.parametrize("chunk", [16, 32, 64])
    def test_vs_sequential_ref(self, chunk):
        B, S, nh, dh = 2, 64, 2, 32
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        sc = dh ** -0.5
        q = jax.random.normal(ks[0], (B, S, nh, dh)) * 0.3
        k = jax.random.normal(ks[1], (B, S, nh, dh)) * 0.3
        v = jax.random.normal(ks[2], (B, S, nh, dh))
        i_pre = jax.random.normal(ks[3], (B, S, nh))
        f_pre = jax.random.normal(ks[4], (B, S, nh)) + 2.0
        h = ops.mlstm_op(q * sc, k * sc, v, i_pre, f_pre, chunk=chunk)
        fold = lambda x: x.transpose(0, 2, 1, 3).reshape(B * nh, S, dh)
        foldg = lambda x: x.transpose(0, 2, 1).reshape(B * nh, S)
        hr = ref.mlstm_ref(fold(q * sc), fold(k * sc), fold(v), foldg(i_pre), foldg(f_pre))
        hr = hr.reshape(B, nh, S, dh).transpose(0, 2, 1, 3)
        err = float(jnp.max(jnp.abs(h - hr)))
        assert err < 1e-5, err

    def test_jnp_chunked_matches_sequential(self):
        """models.recurrent.mlstm_chunked (the XLA path) vs step oracle."""
        from repro.models.recurrent import mlstm_chunked, mlstm_sequential

        B, S, nh, dh = 1, 96, 2, 16
        ks = jax.random.split(jax.random.PRNGKey(1), 5)
        args = (
            jax.random.normal(ks[0], (B, S, nh, dh)) * 0.3,
            jax.random.normal(ks[1], (B, S, nh, dh)) * 0.3,
            jax.random.normal(ks[2], (B, S, nh, dh)),
            jax.random.normal(ks[3], (B, S, nh)),
            jax.random.normal(ks[4], (B, S, nh)) + 1.0,
        )
        h1, _ = mlstm_chunked(*args, chunk=32)
        h2, _ = mlstm_sequential(*args)
        assert float(jnp.max(jnp.abs(h1 - h2))) < 1e-5


class TestMoEGMM:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_vs_ref(self, dtype):
        M, K, N, G, blk = 256, 64, 96, 3, 64
        ks = jax.random.split(jax.random.PRNGKey(0), 2)
        lhs = jax.random.normal(ks[0], (M, K), dtype)
        rhs = jax.random.normal(ks[1], (G, K, N), dtype)
        gs = jnp.array([64, 128, 64], jnp.int32)
        out = ops.moe_gmm_op(lhs, rhs, gs, blk_m=blk, blk_n=32)
        gm = pad_group_sizes_to_blocks(gs, blk, M)
        outr = ref.gmm_ref(lhs, rhs, np.asarray(gm), blk)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - outr.astype(jnp.float32))))
        assert err < _tol(dtype), err

    def test_group_map_helper(self):
        gs = jnp.array([128, 0, 256], jnp.int32)
        gm = pad_group_sizes_to_blocks(gs, 128, 384)
        assert list(np.asarray(gm)) == [0, 2, 2]


class TestInterpretMode:
    @pytest.mark.parametrize(
        "backend,want", [("cpu", True), ("tpu", False), ("gpu", None)]
    )
    def test_default_interpret_by_backend(self, monkeypatch, backend, want):
        """Interpret on the CPU, compile on a TPU, and refuse anything else
        rather than hide an accelerator behind the interpreter."""
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        if want is None:
            with pytest.raises(RuntimeError, match="'gpu'"):
                ops.default_interpret()
        else:
            assert ops.default_interpret() is want
