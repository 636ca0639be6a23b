"""Pallas kernel validation: shape/dtype sweeps, assert_allclose vs the
pure-jnp oracle (ref.py), executed with interpret=True on CPU.  What the
TPU compiler accepts is checked in test_chip_compile.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _mk_pool(key, B, H, KV, d, ps, Pmax, dtype, L=3):
    """q [B, H, d] and whole pools [L, N, ps, KV * d] of random pages;
    each row's table is a random set of distinct pages, the last page
    (the engine's trash page) in none."""
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    N = B * Pmax + 1
    q = (jax.random.normal(ks[0], (B, H, d)) * 0.5).astype(dtype)
    kpg = (jax.random.normal(ks[1], (L, N, ps, KV * d)) * 0.5).astype(dtype)
    vpg = (jax.random.normal(ks[2], (L, N, ps, KV * d)) * 0.5).astype(dtype)
    perm = np.random.RandomState(key).permutation(N - 1)
    bt = jnp.asarray(perm.reshape(B, Pmax), jnp.int32)
    return q, kpg, vpg, bt


def _decode_vs_ref(shape, dtype, window=None, softcap=None, layer=1):
    H, KV, d, ps, Pmax, lens = shape
    q, kpg, vpg, bt = _mk_pool(len(lens), len(lens), H, KV, d, ps, Pmax,
                               dtype)
    lens = jnp.asarray(lens, jnp.int32)
    win = jnp.int32(2**30 if window is None else window)
    out = ops.paged_decode_attention(q, kpg, vpg, jnp.int32(layer), bt, lens,
                                     win, scale=0.2, softcap=softcap,
                                     interpret=True)
    want = ref.paged_decode_attention_ref(q, kpg, vpg, jnp.int32(layer), bt,
                                          lens, window, scale=0.2,
                                          softcap=softcap)
    assert out.shape == q.shape and out.dtype == q.dtype
    out = np.asarray(out, np.float32)
    assert np.isfinite(out).all()
    assert (out[np.asarray(lens) == 0] == 0).all()     # dead rows read nothing
    atol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out, np.asarray(want, np.float32), atol=atol)


# H, KV, d, ps, Pmax, kv lens (0: a dead row). A block holds the pages of
# 512 KiB of K (``paged_attention.pages_per_block``): 64 pages (bf16) or 32
# (f32) of a 256-lane row, 16 or 8 of a 768-lane row.
DECODE_SHAPES = {
    # starcoder2-3b: 2 KV heads x 128, 12 query heads each; a full table
    "kv2x128": (24, 2, 128, 16, 128, [0, 1, 16, 512, 1024, 1025, 2048]),
    # opt-125m: 12 KV heads x 64, one query head each
    "kv12x64": (12, 12, 64, 16, 32, [1, 0, 17, 128, 256, 257, 512]),
    # between them: 4 KV heads x 64, 2 query heads each; a table of 96
    # pages, so a bf16 row's second block is a partial one
    "kv4x64": (8, 4, 64, 16, 96, [3, 0, 1023, 1024, 1536]),
    # one KV head of 128 lanes, pages of 8 tokens
    "kv1x128": (4, 1, 128, 8, 40, [320, 9, 0, 8]),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("name", sorted(DECODE_SHAPES))
def test_paged_decode_attention_vs_ref(name, dtype):
    _decode_vs_ref(DECODE_SHAPES[name], dtype)


@pytest.mark.parametrize("softcap,window", [
    (None, None), (20.0, None), (None, 7), (30.0, 5),
    # a window that starts past the first block: blocks before it are
    # neither copied nor computed
    (None, 200), (25.0, 129)])
def test_paged_decode_attention_softcap_window(softcap, window):
    shape = (12, 12, 64, 16, 32, [0, 1, 6, 140, 300, 512])
    _decode_vs_ref(shape, jnp.float32, window=window, softcap=softcap,
                   layer=2)


@pytest.mark.parametrize("name", ["kv2x128", "kv12x64"])
def test_paged_decode_attn_writes_then_reads_the_same_pool(name):
    """The kernel's write+attend step against the reference's: one decode
    token written into the same pool, inactive rows included, gives the
    same pools and, on every active row, the same output."""
    from repro.models import transformer as T
    H, KV, d, ps, Pmax, _ = DECODE_SHAPES[name]
    B = 4
    q, kpg, vpg, bt = _mk_pool(5, B, H, KV, d, ps, Pmax, jnp.bfloat16)
    kn = jax.random.normal(jax.random.PRNGKey(6), (2, B, KV, d)) * 0.5
    kn = kn.astype(jnp.bfloat16)
    seq = jnp.asarray([Pmax * ps - 1, 0, 37, 5 * ps], jnp.int32)
    active = jnp.asarray([True, False, True, True])
    kw = dict(scale=0.2, window=jnp.int32(T.NO_WINDOW), attn_softcap=None)
    args = (q[:, None], kn[0], kn[1], kpg, vpg, jnp.int32(1), bt, seq, active)
    o_ref, k_ref, v_ref = T.default_decode_attn(*args, **kw)
    o, k, v = ops.paged_decode_attn(*args, **kw, interpret=True)
    np.testing.assert_array_equal(np.asarray(k), np.asarray(k_ref))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(v_ref))
    on = np.asarray(active)
    np.testing.assert_allclose(np.asarray(o, np.float32)[on],
                               np.asarray(o_ref, np.float32)[on], atol=2e-2)
    assert (np.asarray(o, np.float32)[~on] == 0).all()


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


def test_mixed_with_the_decode_kernel_matches_the_reference():
    """``transformer.mixed`` with the kernel's write+attend step as its
    decode attention, against ``default_decode_attn``: on a reduced dense
    model over a random pool, with one decode slot inactive, the pools
    agree (exactly where no decode row's attention feeds them), the
    decode logits agree within bf16 rounding and greedy picks the same
    tokens."""
    import dataclasses
    from functools import partial

    from conftest import reduced_model
    from repro.models import transformer as T
    cfg = dataclasses.replace(reduced_model("starcoder2-3b").cfg, n_layers=3)
    params = T.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)
    ps, W, P, B = 4, 8, 2, 4
    pages = np.random.RandomState(1).permutation(
        (P + B) * W).reshape(P + B, W)
    kpg, vpg = T.init_pages(cfg, (P + B) * W + 1, ps, dtype=jnp.bfloat16)
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    kpg = (jax.random.normal(ks[0], kpg.shape) * 0.5).astype(kpg.dtype)
    vpg = (jax.random.normal(ks[1], vpg.shape) * 0.5).astype(vpg.dtype)
    toks = jax.random.randint(ks[2], (P * 8 + B,), 1, cfg.vocab_size)
    d_lens = np.asarray([20, 0, 7, W * ps - 1], np.int32)
    d_active = np.asarray([True, False, True, True])
    mb = dict(p_tokens=toks[:P * 8].reshape(P, 8),
              p_table=jnp.asarray(pages[:P], jnp.int32),
              p_start=jnp.asarray([0, 12], jnp.int32),
              p_lens=jnp.asarray([8, 5], jnp.int32),
              d_tokens=toks[P * 8:], d_table=jnp.asarray(pages[P:], jnp.int32),
              d_lens=jnp.asarray(d_lens), d_active=jnp.asarray(d_active))
    kernel = {"decode": partial(ops.paged_decode_attn, interpret=True)}
    _, want, (k_want, v_want), _ = T.mixed(params, cfg, mb, kpg, vpg)
    _, got, (k_got, v_got), _ = T.mixed(params, cfg, mb, kpg, vpg,
                                        attn_fn=kernel)
    # past the first layer a decode row's K/V depend on its attention;
    # the inactive slot writes into the trash page, which nothing reads
    fed = np.zeros(kpg.shape[:3], bool)[:, :-1]
    for b in np.flatnonzero(d_active):
        fed[1:, pages[P + b, d_lens[b] // ps], d_lens[b] % ps] = True
    for g, w in ((k_got, k_want), (v_got, v_want)):
        g, w = (np.asarray(a, np.float32)[:, :-1] for a in (g, w))
        np.testing.assert_array_equal(g[~fed], w[~fed])
        np.testing.assert_allclose(g[fed], w[fed], atol=3e-2, rtol=2e-2)
    got = np.asarray(got, np.float32)[d_active, :cfg.vocab_size]
    want = np.asarray(want, np.float32)[d_active, :cfg.vocab_size]
    np.testing.assert_allclose(got, want, atol=2e-2 * np.abs(want).max())
    np.testing.assert_array_equal(got.argmax(-1), want.argmax(-1))
