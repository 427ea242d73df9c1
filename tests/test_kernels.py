"""Per-kernel validation: Pallas interpret mode vs pure-jnp oracles,
with hypothesis sweeps over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.block_gemm.kernel import block_sparse_matmul as bg_kernel
from repro.kernels.block_gemm.ops import block_sparse_matmul as bg_op
from repro.kernels.block_gemm.ref import block_sparse_matmul_ref
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ops import flash_attention_bshd
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.rwkv6_scan.kernel import rwkv6_scan


class TestBlockGemm:
    @given(
        p=st.integers(1, 6),
        o=st.integers(1, 3),
        bm=st.sampled_from([8, 16, 32]),
        bk=st.sampled_from([16, 32]),
        bn=st.sampled_from([16, 32]),
        dtype=st.sampled_from(["float32", "bfloat16"]),
        seed=st.integers(0, 100),
    )
    @settings(max_examples=12, deadline=None)
    def test_matches_ref(self, p, o, bm, bk, bn, dtype, seed):
        o = min(o, p)
        key = jax.random.PRNGKey(seed)
        k1, k2, k3 = jax.random.split(key, 3)
        dt = jnp.dtype(dtype)
        lhs = jax.random.normal(k1, (p, bm, bk), jnp.float32).astype(dt)
        rhs = jax.random.normal(k2, (p, bk, bn), jnp.float32).astype(dt)
        out_idx = jnp.sort(
            jnp.concatenate([jnp.arange(o),
                             jax.random.randint(k3, (p - o,), 0, o)])
        ).astype(jnp.int32)
        got = bg_op(lhs, rhs, out_idx, o, bm=16, bn=128, bk=128, interpret=True)
        want = block_sparse_matmul_ref(lhs, rhs, out_idx, o)
        tol = 1e-5 if dtype == "float32" else 5e-2
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=tol, atol=tol)

    def test_k_tiling_accumulation(self):
        """BK > tile: the kernel must accumulate across k-steps."""
        key = jax.random.PRNGKey(0)
        lhs = jax.random.normal(key, (3, 16, 512), jnp.float32)
        rhs = jax.random.normal(key, (3, 512, 128), jnp.float32)
        idx = jnp.array([0, 0, 1], jnp.int32)
        got = bg_kernel(lhs, rhs, idx, 2, bm=16, bn=128, bk=128, interpret=True)
        want = block_sparse_matmul_ref(lhs, rhs, idx, 2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("window", [8, 24, 1 << 16])
    def test_row_chunked_segment_sum(self, monkeypatch, window):
        """Buckets whose scatter window exceeds SCATTER_WINDOW are reduced
        in row chunks (ragged last chunk included) with the same result."""
        from repro.kernels.block_gemm import ops

        monkeypatch.setattr(ops, "SCATTER_WINDOW", window)
        key = jax.random.PRNGKey(1)
        lhs = jax.random.normal(key, (5, 20, 6), jnp.float32)
        rhs = jax.random.normal(jax.random.fold_in(key, 1), (5, 6, 4),
                                jnp.float32)
        idx = jnp.array([0, 0, 1, 2, 2], jnp.int32)
        got = bg_op(lhs, rhs, idx, 4, use_kernel=False)
        want = block_sparse_matmul_ref(lhs, rhs, idx, 4)
        assert got.shape == (4, 20, 4)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


class TestFlashAttention:
    @given(
        s=st.sampled_from([64, 128, 256]),
        d=st.sampled_from([32, 64, 128]),
        bh=st.integers(1, 4),
        dtype=st.sampled_from(["float32", "bfloat16"]),
        seed=st.integers(0, 50),
    )
    @settings(max_examples=10, deadline=None)
    def test_matches_ref(self, s, d, bh, dtype, seed):
        key = jax.random.PRNGKey(seed)
        dt = jnp.dtype(dtype)
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (bh, s, d), jnp.float32).astype(dt)
        k = jax.random.normal(ks[1], (bh, s, d), jnp.float32).astype(dt)
        v = jax.random.normal(ks[2], (bh, s, d), jnp.float32).astype(dt)
        got = flash_attention(q, k, v, bq=64, bk=64, interpret=True)
        want = flash_attention_ref(q, k, v)
        tol = 2e-5 if dtype == "float32" else 3e-2
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=tol, atol=tol)

    def test_bshd_gqa_wrapper(self):
        """GQA layout + head-dim padding path vs the model's attention."""
        from repro.models.attention import causal_attention

        key = jax.random.PRNGKey(1)
        ks = jax.random.split(key, 3)
        b, s, h, hkv, d = 2, 128, 8, 2, 48  # d=48 forces lane padding
        q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, s, hkv, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, s, hkv, d), jnp.float32)
        got = flash_attention_bshd(q, k, v, bq=64, bk=64, interpret=True)
        want = causal_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_long_causality(self):
        """Future keys must not affect output (strict causality)."""
        key = jax.random.PRNGKey(2)
        q = jax.random.normal(key, (1, 128, 64), jnp.float32)
        k = jax.random.normal(key, (1, 128, 64), jnp.float32)
        v = jax.random.normal(key, (1, 128, 64), jnp.float32)
        o1 = flash_attention(q, k, v, bq=32, bk=32, interpret=True)
        k2 = k.at[:, 64:].set(99.0)
        v2 = v.at[:, 64:].set(-99.0)
        o2 = flash_attention(q, k2, v2, bq=32, bk=32, interpret=True)
        np.testing.assert_allclose(np.asarray(o1[:, :64]),
                                   np.asarray(o2[:, :64]), rtol=1e-6)


class TestRwkv6Scan:
    def _inputs(self, bh, t, n, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), 5)
        r = jax.random.normal(ks[0], (bh, t, n), jnp.float32) * 0.5
        k = jax.random.normal(ks[1], (bh, t, n), jnp.float32) * 0.5
        v = jax.random.normal(ks[2], (bh, t, n), jnp.float32)
        logw = -jnp.exp(jax.random.normal(ks[3], (bh, t, n)) * 0.5)
        u = jax.random.normal(ks[4], (bh, n), jnp.float32) * 0.1
        return r, k, v, logw, u

    def _ref(self, r, k, v, logw, u):
        """Naive O(T) recurrence oracle."""
        bh, t, n = r.shape
        s = jnp.zeros((bh, n, n))
        outs = []
        for i in range(t):
            kv = jnp.einsum("bn,bm->bnm", k[:, i], v[:, i])
            outs.append(jnp.einsum("bn,bnm->bm", r[:, i],
                                   s + u[:, :, None] * kv))
            s = s * jnp.exp(logw[:, i])[:, :, None] + kv
        return jnp.stack(outs, axis=1)

    @given(t=st.sampled_from([16, 32, 64]), chunk=st.sampled_from([8, 16, 32]),
           seed=st.integers(0, 30))
    @settings(max_examples=8, deadline=None)
    def test_matches_recurrence(self, t, chunk, seed):
        r, k, v, logw, u = self._inputs(2, t, 16, seed)
        got = rwkv6_scan(r, k, v, logw, u, chunk=chunk, interpret=True)
        want = self._ref(r, k, v, logw, u)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_matches_model_chunked(self):
        """Kernel == the model's jnp chunked path (same algorithm)."""
        from repro.models import rwkv6 as rk

        bh, t, n = 4, 64, 16
        r, k, v, logw, u = self._inputs(bh, t, n, seed=3)
        got = rwkv6_scan(r, k, v, logw, u, chunk=16, interpret=True)
        want = self._ref(r, k, v, logw, u)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
