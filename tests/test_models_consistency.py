"""Numerical-consistency tests across equivalent model paths:
chunked vs naive attention, chunked vs sequential WKV, associative vs
sequential RG-LRU scan, RMSNorm against float64, and decode-vs-forward
logits equality."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import build
from repro.models.layers import (chunked_attention, naive_attention,
                                 rms_norm)
from repro.models.rglru import rglru_scan, rglru_scan_reference
from repro.models.rwkv import wkv6_chunked, wkv6_reference


# (B, H, KV, Q, K, dh, causal, window, block, dtype); where Q < K the
# queries sit at the end of K.
_f32, _bf16 = jnp.float32, jnp.bfloat16
ATTN_CASES = [
    pytest.param(2, 4, 2, 64, 64, 16, True, None, 16, _f32,
                 id="True-None"),
    pytest.param(2, 4, 2, 64, 64, 16, True, 24, 16, _f32,
                 id="True-24"),
    pytest.param(2, 4, 2, 64, 64, 16, False, None, 16, _f32,
                 id="False-None"),
    pytest.param(1, 1, 1, 128, 128, 64, True, None, 64, _f32,
                 id="causal-1x1-128x128-d64"),
    pytest.param(2, 2, 2, 256, 256, 64, True, None, 128, _f32,
                 id="causal-2x2-256x256-d64"),
    pytest.param(1, 4, 2, 256, 512, 128, True, None, 128, _f32,
                 id="causal-gqa4of2-256x512-d128"),
    pytest.param(2, 1, 1, 512, 512, 32, True, None, 128, _f32,
                 id="causal-2x1-512x512-d32"),
    *(pytest.param(1, 2, 2, 256, 256, 64, True, w, 128, _f32,
                   id=f"window{w}-256x256") for w in (64, 128, 256)),
    pytest.param(2, 2, 2, 256, 512, 64, False, None, 128, _f32,
                 id="noncausal-256x512"),
    pytest.param(1, 2, 2, 128, 128, 64, True, None, 64, _bf16,
                 id="bf16-128x128"),
    pytest.param(1, 2, 2, 200, 200, 64, True, None, 64, _f32,
                 id="padded-tail-q200"),
]


class TestAttentionPaths:
    @pytest.mark.parametrize(
        "B,H,KV,Q,K,dh,causal,window,block,dtype", ATTN_CASES)
    def test_chunked_matches_naive(self, B, H, KV, Q, K, dh, causal,
                                   window, block, dtype):
        tol = 2e-2 if dtype == _bf16 else 2e-5
        ks = jax.random.split(jax.random.key(0), 3)
        q = jax.random.normal(ks[0], (B, Q, H, dh)).astype(dtype)
        k = jax.random.normal(ks[1], (B, K, KV, dh)).astype(dtype)
        v = jax.random.normal(ks[2], (B, K, KV, dh)).astype(dtype)
        qpos, kpos = jnp.arange(K - Q, K), jnp.arange(K)
        a = naive_attention(q, k, v, causal=causal, window=window,
                            q_positions=qpos, k_positions=kpos)
        b = chunked_attention(q, k, v, causal=causal, window=window,
                              q_positions=qpos, k_positions=kpos,
                              q_block=block, k_block=block)
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=tol, rtol=tol)

    def test_chunked_unroll_matches(self):
        key = jax.random.key(1)
        B, Q, H, dh = 1, 48, 2, 8
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (B, Q, H, dh))
        k = jax.random.normal(ks[1], (B, Q, H, dh))
        v = jax.random.normal(ks[2], (B, Q, H, dh))
        pos = jnp.arange(Q)
        a = chunked_attention(q, k, v, causal=True, window=None,
                              q_positions=pos, k_positions=pos,
                              q_block=16, k_block=16, unroll=False)
        b = chunked_attention(q, k, v, causal=True, window=None,
                              q_positions=pos, k_positions=pos,
                              q_block=16, k_block=16, unroll=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6)


class TestRecurrences:
    @pytest.mark.parametrize("B,H,T,dh,chunk", [
        (2, 3, 80, 8, 16),
        (1, 1, 64, 32, 16),
        (2, 2, 128, 64, 32),
        (1, 3, 96, 16, 32),
        (1, 2, 100, 16, 32),     # padded tail: T not a multiple of chunk
    ])
    def test_wkv6_chunked_vs_sequential(self, B, H, T, dh, chunk):
        ks = jax.random.split(jax.random.key(0), 5)
        r, k, v = (jax.random.normal(ks[i], (B, T, H, dh)) for i in range(3))
        w = jax.random.uniform(ks[3], (B, T, H, dh), minval=0.8,
                               maxval=0.999)
        u = jax.random.normal(ks[4], (H, dh)) * 0.3
        ref, Sr = wkv6_reference(r, k, v, w, u)
        out, S = wkv6_chunked(r, k, v, w, u, jnp.zeros((B, H, dh, dh)),
                              chunk=chunk)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=5e-4, rtol=1e-3)
        np.testing.assert_allclose(np.asarray(S), np.asarray(Sr),
                                   atol=5e-4, rtol=1e-3)

    def test_rglru_associative_vs_sequential(self):
        key = jax.random.key(0)
        B, T, W = 2, 33, 8
        a = jax.random.uniform(key, (B, T, W), minval=0.7, maxval=0.99)
        bx = jax.random.normal(jax.random.key(1), (B, T, W))
        h0 = jax.random.normal(jax.random.key(2), (B, W))
        got = rglru_scan(a, bx, h0)
        ref = rglru_scan_reference(a, bx, h0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_rglru_no_initial_state(self):
        a = jnp.full((1, 5, 2), 0.5)
        bx = jnp.ones((1, 5, 2))
        got = rglru_scan(a, bx)
        ref = rglru_scan_reference(a, bx)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-6)


@pytest.mark.parametrize("N,d,dtype,atol", [
    (256, 128, jnp.float32, 1e-5),
    (512, 256, jnp.float32, 1e-5),
    (128, 512, jnp.float32, 1e-5),
    (128, 128, jnp.bfloat16, 2e-2),
])
def test_rms_norm_matches_float64(N, d, dtype, atol):
    eps = 1e-6
    x = jax.random.normal(jax.random.key(0), (N, d)).astype(dtype)
    w = (jax.random.normal(jax.random.key(1), (d,)) * 0.1).astype(dtype)
    got = rms_norm(x, w, eps)
    assert got.dtype == dtype
    x64 = np.asarray(x, np.float64)
    w64 = np.asarray(w, np.float64)
    want = x64 / np.sqrt(np.mean(x64 * x64, axis=-1, keepdims=True) + eps)
    want = want * (1.0 + w64)
    np.testing.assert_allclose(np.asarray(got, np.float64), want,
                               atol=atol, rtol=atol)


DECODE_ARCHS = ["gemma-7b", "h2o-danube-3-4b", "deepseek-v2-lite-16b",
                "rwkv6-3b", "recurrentgemma-9b", "mixtral-8x22b"]


class TestDecodeConsistency:
    @pytest.mark.parametrize("arch", DECODE_ARCHS)
    def test_decode_matches_forward(self, arch):
        """Feeding tokens one-by-one through the cached decode path must
        reproduce the teacher-forced forward logits."""
        cfg = get_arch(arch).smoke
        api = build(cfg)
        params, _ = api.init(jax.random.key(0))
        B, S = 2, 12
        toks = jax.random.randint(jax.random.key(3), (B, S), 0, cfg.vocab)
        full_logits, _ = api.forward(params, toks)
        state = api.init_decode_state(B, S + 2)
        step = jax.jit(lambda p, s, t, pos: api.decode_step(p, s, t, pos))
        errs = []
        for pos in range(S):
            logits, state = step(params, state, toks[:, pos:pos + 1],
                                 jnp.int32(pos))
            errs.append(float(jnp.max(jnp.abs(
                logits[:, 0] - full_logits[:, pos]))))
        scale = float(jnp.max(jnp.abs(full_logits))) + 1e-9
        assert max(errs) / scale < 5e-3, errs
