"""Pallas kernel validation: shape/dtype sweeps, assert_allclose vs the
pure-jnp oracle (ref.py), executed with interpret=True on CPU.  What the
TPU compiler accepts is checked in test_chip_compile.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _mk_paged(key, B, Tq, H, KV, d, ps, N, Pmax, dtype):
    ks = jax.random.split(jax.random.PRNGKey(key), 8)
    q = (jax.random.normal(ks[0], (B, Tq, H, d)) * 0.5).astype(dtype)
    kpg = (jax.random.normal(ks[1], (N, ps, KV, d)) * 0.5).astype(dtype)
    vpg = (jax.random.normal(ks[2], (N, ps, KV, d)) * 0.5).astype(dtype)
    perm = np.random.RandomState(key).permutation(N - 1)
    bt = jnp.asarray(perm[: B * Pmax].reshape(B, Pmax), jnp.int32)
    return q, kpg, vpg, bt


PAGED_CASES = [
    # B, Tq, H, KV, d, ps, N, Pmax
    (3, 1, 4, 2, 64, 8, 16, 4),        # decode GQA
    (2, 1, 8, 8, 128, 16, 32, 3),      # decode MHA, 128-dim
    (2, 8, 4, 4, 64, 8, 16, 4),        # chunked prefill
    (1, 16, 6, 2, 32, 4, 32, 8),       # chunk, d=32 (padded to lane)
    (2, 4, 4, 4, 112, 8, 16, 4),       # zamba head_dim=112 (lane pad)
]


@pytest.mark.parametrize("case", PAGED_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_vs_ref(case, dtype):
    B, Tq, H, KV, d, ps, N, Pmax = case
    q, kpg, vpg, bt = _mk_paged(0, B, Tq, H, KV, d, ps, N, Pmax, dtype)
    hist = np.random.RandomState(1).randint(0, Pmax * ps - Tq, size=B)
    q_pos = jnp.asarray(hist, jnp.int32)
    kv_lens = q_pos + Tq
    out = ops.paged_attention(q, kpg, vpg, bt, kv_lens, q_pos, scale=0.2,
                              interpret=True)
    G = H // KV
    qk = q.reshape(B, Tq, KV, G, d).transpose(0, 2, 1, 3, 4)
    want = ref.paged_attention_ref(qk, kpg, vpg, bt, kv_lens, q_pos, scale=0.2)
    want = want.transpose(0, 2, 1, 3, 4).reshape(B, Tq, H, d)
    atol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=atol)


@pytest.mark.parametrize("softcap,window", [(None, None), (20.0, None),
                                            (None, 7), (30.0, 5)])
def test_paged_attention_softcap_window(softcap, window):
    B, Tq, H, KV, d, ps, N, Pmax = 2, 4, 4, 2, 64, 8, 16, 4
    q, kpg, vpg, bt = _mk_paged(3, B, Tq, H, KV, d, ps, N, Pmax, jnp.float32)
    q_pos = jnp.asarray([8, 3], jnp.int32)
    kv_lens = q_pos + Tq
    out = ops.paged_attention(q, kpg, vpg, bt, kv_lens, q_pos, scale=0.2,
                              softcap=softcap, window=window, interpret=True)
    G = H // KV
    qk = q.reshape(B, Tq, KV, G, d).transpose(0, 2, 1, 3, 4)
    want = ref.paged_attention_ref(qk, kpg, vpg, bt, kv_lens, q_pos,
                                   scale=0.2, softcap=softcap, window=window)
    want = want.transpose(0, 2, 1, 3, 4).reshape(B, Tq, H, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


FLASH_CASES = [
    # B, T, Tk, H, KV, d, bq, bk
    (2, 32, 32, 4, 2, 64, 8, 8),
    (1, 64, 64, 8, 8, 128, 16, 16),
    (2, 16, 16, 6, 2, 32, 16, 8),
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_vs_ref(case, dtype):
    B, T, Tk, H, KV, d, bq, bk = case
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = (jax.random.normal(ks[0], (B, T, H, d)) * 0.5).astype(dtype)
    k = (jax.random.normal(ks[1], (B, Tk, KV, d)) * 0.5).astype(dtype)
    v = (jax.random.normal(ks[2], (B, Tk, KV, d)) * 0.5).astype(dtype)
    kv_lens = jnp.asarray([Tk] + [Tk - 5] * (B - 1), jnp.int32)
    out = ops.flash_attention(q, k, v, kv_lens, scale=0.2, block_q=bq,
                              block_k=bk, interpret=True)
    G = H // KV
    qk = q.reshape(B, T, KV, G, d).transpose(0, 2, 1, 3, 4)
    kk = k.transpose(0, 2, 1, 3)
    vv = v.transpose(0, 2, 1, 3)
    want = ref.flash_attention_ref(qk, kk, vv, kv_lens, scale=0.2)
    want = want.transpose(0, 2, 1, 3, 4).reshape(B, T, H, d)
    atol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=atol)


def test_flash_window_softcap():
    B, T, H, KV, d = 1, 32, 4, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, T, H, d)) * 0.5
    k = jax.random.normal(ks[1], (B, T, KV, d)) * 0.5
    v = jax.random.normal(ks[2], (B, T, KV, d)) * 0.5
    lens = jnp.asarray([T], jnp.int32)
    out = ops.flash_attention(q, k, v, lens, scale=0.2, window=8,
                              softcap=25.0, block_q=8, block_k=8,
                              interpret=True)
    qk = q.reshape(B, T, KV, 1, d).transpose(0, 2, 1, 3, 4)
    want = ref.flash_attention_ref(qk, k.transpose(0, 2, 1, 3),
                                   v.transpose(0, 2, 1, 3), lens, scale=0.2,
                                   window=8, softcap=25.0)
    want = want.transpose(0, 2, 1, 3, 4).reshape(B, T, H, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


def test_paged_kernel_is_splitwiser_unified():
    """One kernel serves both phases: decode (C=1) and chunked prefill
    (C=chunk) produce identical results to two separate ref calls on the
    same pool — the fused mixed-batch property."""
    B, H, KV, d, ps, N, Pmax = 2, 4, 2, 64, 8, 24, 6
    q1, kpg, vpg, bt = _mk_paged(11, B, 1, H, KV, d, ps, N, Pmax, jnp.float32)
    qc = jax.random.normal(jax.random.PRNGKey(12), (B, 8, H, d)) * 0.5
    lens_dec = jnp.asarray([30, 17], jnp.int32)
    out_dec = ops.paged_attention(q1, kpg, vpg, bt, lens_dec + 1, lens_dec,
                                  scale=0.2, interpret=True)
    start = jnp.asarray([4, 0], jnp.int32)
    out_chunk = ops.paged_attention(qc, kpg, vpg, bt, start + 8, start,
                                    scale=0.2, interpret=True)
    assert out_dec.shape == (B, 1, H, d)
    assert out_chunk.shape == (B, 8, H, d)
    assert bool(jnp.isfinite(out_dec).all() and jnp.isfinite(out_chunk).all())
