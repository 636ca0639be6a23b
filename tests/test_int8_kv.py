"""int8 KV cache (§Perf beyond-paper optimization): quantized paged
attention must match the bf16 path within quantization tolerance.

Covers the kernel stack bottom-up: q8_kv edge cases (all-zero planes,
partial tail pages, COW copies), the jnp reference, the Pallas
dequant-in-kernel launcher against that reference, and the engine
end-to-end (greedy int8 streams vs the fp oracle, bit-identical across
all four serve modes)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reduced_model
from repro.configs import ServeConfig
from repro.core.engine import Engine, Request, SamplingParams
from repro.kernels import ops
from repro.kernels.kv_int8 import SCALE_FLOOR, quant_kv
from repro.launch.spmd import paged_attention_int8, q8_kv
from repro.models.layers import paged_attention_ref


def test_int8_paged_attention_close_to_fp():
    B, Tq, H, KV, d, ps, N, Pmax = 2, 4, 4, 2, 32, 8, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, Tq, H, d)) * 0.5
    k = jax.random.normal(ks[1], (N, ps, KV, d)) * 0.5
    v = jax.random.normal(ks[2], (N, ps, KV, d)) * 0.5
    bt = jnp.asarray(np.random.RandomState(0).permutation(N - 1)
                     [: B * Pmax].reshape(B, Pmax), jnp.int32)
    q_pos = jnp.asarray([10, 3], jnp.int32)
    lens = q_pos + Tq
    want = paged_attention_ref(q, k, v, bt, lens,
                               q_pos[:, None] + jnp.arange(Tq)[None],
                               scale=0.3)
    kq, kscale = q8_kv(k)
    vq, vscale = q8_kv(v)
    got = paged_attention_int8(q, kq, kscale, vq, vscale, bt, lens,
                               q_pos[:, None] + jnp.arange(Tq)[None],
                               scale=0.3, window=None, attn_softcap=None)
    err = float(jnp.abs(got - want).max())
    rel = err / float(jnp.abs(want).max())
    assert rel < 2e-2, (err, rel)           # ~1e-3 typical, 2e-2 bound


def test_q8_kv_roundtrip():
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 2, 64))
    q, s = q8_kv(x)
    back = q.astype(jnp.float32) * s
    assert float(jnp.abs(back - x).max()) <= float(s.max()) * 0.5 + 1e-6


def test_q8_all_zero_page_has_floored_scale():
    """An all-zero (token, head) plane — pool init, or a genuinely zero
    KV row — must quantize to a positive finite scale, never 0 or NaN:
    downstream dequant multiplies by it inside the attention kernel."""
    q, s = q8_kv(jnp.zeros((2, 8, 2, 32)))
    assert bool(jnp.all(s == SCALE_FLOOR))
    assert bool(jnp.all(jnp.isfinite(s))) and float(s.min()) > 0
    assert bool(jnp.all(q == 0))
    back = q.astype(jnp.float32) * s
    assert bool(jnp.all(back == 0)) and bool(jnp.all(jnp.isfinite(back)))


def test_q8_single_token_tail_page():
    """A tail page holding ONE real token (the rest pool-init zeros)
    quantizes per-token: the real token keeps its own scale and
    roundtrips, the padding rows stay exactly zero."""
    page = jnp.zeros((1, 8, 2, 32))
    tok = jax.random.normal(jax.random.PRNGKey(2), (2, 32)) * 3.0
    page = page.at[0, 0].set(tok)
    q, s = q8_kv(page)
    back = q.astype(jnp.float32) * s
    err = float(jnp.abs(back[0, 0] - tok).max())
    assert err <= float(s[0, 0].max()) * 0.5 + 1e-6
    assert bool(jnp.all(back[0, 1:] == 0))
    assert bool(jnp.all(s[0, 1:] == SCALE_FLOOR))


def test_q8_roundtrip_survives_cow_copy():
    """A COW page copy moves codes AND scales together (the engine
    tree-maps the copy over the {"q","s"} pool dict): the copy must
    dequantize bit-identically to its source."""
    k = jax.random.normal(jax.random.PRNGKey(3), (2, 4, 8, 2, 16))
    v = jax.random.normal(jax.random.PRNGKey(4), (2, 4, 8, 2, 16))
    kpg, vpg = quant_kv(k, v)
    src, dst = 1, 3
    kpg = jax.tree.map(lambda a: a.at[:, dst].set(a[:, src]), kpg)
    def deq(pg, p):
        return pg["q"][:, p].astype(jnp.float32) * pg["s"][:, p]
    np.testing.assert_array_equal(np.asarray(deq(kpg, dst)),
                                  np.asarray(deq(kpg, src)))


def test_pallas_int8_kernel_matches_jnp_ref():
    """The promoted Pallas dequant-in-kernel launcher against the jnp
    reference (interpret mode on CPU)."""
    B, Tq, H, KV, d, ps, N, Pmax = 2, 4, 4, 2, 32, 8, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (B, Tq, H, d)) * 0.5
    kq, kscale = q8_kv(jax.random.normal(ks[1], (N, ps, KV, d)) * 0.5)
    vq, vscale = q8_kv(jax.random.normal(ks[2], (N, ps, KV, d)) * 0.5)
    bt = jnp.asarray(np.random.RandomState(1).permutation(N - 1)
                     [: B * Pmax].reshape(B, Pmax), jnp.int32)
    q_pos = jnp.asarray([9, 2], jnp.int32)
    lens = q_pos + Tq
    want = paged_attention_int8(q, kq, kscale, vq, vscale, bt, lens,
                                q_pos[:, None] + jnp.arange(Tq)[None],
                                scale=0.3, window=None, attn_softcap=None)
    got = ops.paged_attention_int8(q, kq, kscale, vq, vscale, bt, lens,
                                   q_pos, scale=0.3, interpret=True)
    rel = float(jnp.abs(got - want).max()) / float(jnp.abs(want).max())
    assert rel < 1e-5, rel


# ---------------------------------------------------- engine end-to-end ----
@pytest.fixture(scope="module")
def engine_setup():
    model = reduced_model("qwen3-0.6b")
    params = model.init(jax.random.PRNGKey(0))
    prompts = np.random.RandomState(7).randint(
        0, model.cfg.vocab_size, (4, 11)).tolist()
    def reqs():
        return [Request(rid=i, prompt=list(p),
                        sampling=SamplingParams(max_new_tokens=6))
                for i, p in enumerate(prompts)]
    return model, params, reqs


BASE = ServeConfig(max_batch=3, page_size=4, n_pages=64, max_pages_per_seq=12,
                   prefill_chunk=4, n_streams=2, enable_prefix_cache=True,
                   sanitize_level="step")


def _streams(model, params, reqs, **over):
    eng = Engine(model, params, dataclasses.replace(BASE, **over))
    m = eng.run(reqs())
    assert m.summary()["n_done"] == 4
    return {o.rid: tuple(o.tokens) for o in eng.poll()}, eng


def test_int8_greedy_matches_fp_oracle_all_modes(engine_setup):
    """Greedy int8 token streams vs the fp oracle, and bit-identical
    across all four serve modes (the tolerance story: on the reduced
    models the argmax never flips; EXPERIMENTS.md documents the logit
    closeness behind it)."""
    model, params, reqs = engine_setup
    oracle, _ = _streams(model, params, reqs, mode="sequential")
    for mode in ("sequential", "splitwiser", "splitwiser_mps", "chunked"):
        got, eng = _streams(model, params, reqs, mode=mode, kv_dtype="int8",
                            chunk_tokens=8 if mode == "chunked" else 16)
        assert got == oracle, mode
        assert eng.metrics.n_quant_pages > 0


def test_int8_pool_grows_at_equal_bytes(engine_setup):
    """The byte-denominated pool: flipping kv_dtype alone must buy
    >= 1.8x the usable pages at (at most) the same device bytes."""
    model, params, reqs = engine_setup
    _, fp = _streams(model, params, reqs)
    _, i8 = _streams(model, params, reqs, kv_dtype="int8")
    assert i8.metrics.kv_pool_bytes <= fp.metrics.kv_pool_bytes
    assert i8.alloc.n_pages >= 1.8 * fp.alloc.n_pages
    assert i8.metrics.kv_bytes_per_token < fp.metrics.kv_bytes_per_token
