"""The engine's mixed and decode programs write each new token's K/V into
the carried page pool at [layer, page, offset] and leave every other entry
of the pool as it was, in every layer.

The pools are filled with random values first, so an entry that a program
leaves alone is told apart from one it writes. The K/V each program should
have written is the full forward's (``forward_hidden(collect_kv=True)``)
over the same tokens.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ServeConfig, get_config
from repro.core.engine import build_step_programs, init_pool
from repro.kernels.kv_int8 import fake_quant_kv
from repro.models import transformer as T
from repro.models.registry import CACHE_KIND, FAMILY_MODULE, Model

PS, N_PAGES, W, C = 4, 24, 4, 8
TRASH = N_PAGES - 1
# sequence -> (its pages, its tokens); the mixed call prefills "a" (8
# tokens) and "c" (5 of a chunk of 8) and decodes the first token of "b"
# and "d"; the decode call then decodes "a" at position 8 and "b" at 1
TABLES = {"a": [3, 7, 11, 0], "b": [5, 0, 0, 0], "c": [9, 12, 0, 0],
          "d": [20, 0, 0, 0]}
LENGTHS = {"a": 9, "b": 2, "c": 5, "d": 1}
MIXED_WRITES = [("a", p) for p in range(8)] + [("c", p) for p in range(5)] + [
    ("b", 0), ("d", 0)]
DECODE_WRITES = [("a", 8), ("b", 1)]


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(get_config("qwen3-0.6b").reduced(), n_layers=3)
    model = Model("qwen3-0.6b", cfg, FAMILY_MODULE[cfg.family],
                  CACHE_KIND[cfg.family])
    params = model.init(jax.random.PRNGKey(0), jnp.bfloat16)
    rs = np.random.RandomState(3)
    tokens = {s: rs.randint(1, cfg.vocab_size, n).astype(np.int32)
              for s, n in LENGTHS.items()}
    return cfg, params, tokens


def _random_pool(pool, seed):
    rs = np.random.RandomState(seed)

    def fill(a):
        if a.dtype == jnp.int8:
            return jnp.asarray(rs.randint(-127, 128, a.shape), jnp.int8)
        if a.dtype == jnp.float32:                  # int8 scales: positive
            return jnp.asarray(rs.uniform(0.01, 0.1, a.shape), jnp.float32)
        return jnp.asarray(rs.normal(size=a.shape), a.dtype)
    return jax.tree.map(fill, pool)


def _table(seqs):
    return jnp.asarray([TABLES[s] if s else [0] * W for s in seqs], jnp.int32)


def _reference_kv(cfg, params, tokens, int8):
    """{seq: (k, v) [L, T, KV_p, hd] f32} from the full forward."""
    out = {}
    for s, toks in tokens.items():
        x = T.embed(params, cfg, jnp.asarray(toks)[None])
        pos = jnp.arange(len(toks), dtype=jnp.int32)[None]
        _, _, (k, v) = T.forward_hidden(
            params, cfg, x, pos, collect_kv=True,
            kv_fake_quant=fake_quant_kv if int8 else None)
        out[s] = (np.asarray(k[:, 0], np.float32), np.asarray(v[:, 0], np.float32))
    return out


def _entries(pool, int8):
    """The pool as f32 [L, N, ps, KV_p, hd] values (int8: codes x scales)."""
    if int8:
        q, s = np.asarray(pool["q"]), np.asarray(pool["s"])
        L, N, ps = q.shape[:3]
        return (q.reshape(L, N, ps, s.shape[-1], -1).astype(np.float32)
                * s[..., None])
    return np.asarray(pool, np.float32)


def _check(before, after, writes, ref, cfg, int8):
    written = np.zeros(np.asarray(jax.tree.leaves(after)[0]).shape[:3], bool)
    for kv in (0, 1):
        got = _entries(after[kv], int8)
        got = got.reshape(got.shape[:3] + (cfg.n_kv_heads, cfg.head_dim))
        for s, pos in writes:
            page, off = TABLES[s][pos // PS], pos % PS
            written[:, page, off] = True
            want = ref[s][kv][:, pos]                      # [L, KV_p, hd]
            # a few bf16 roundings (on the CPU the K/V agree exactly), plus
            # half a code step for int8
            tol = 2.0**-6 * np.abs(want).max(axis=(1, 2), keepdims=True)
            if int8:
                tol = tol + np.abs(want).max(axis=-1, keepdims=True) / 254
            assert np.all(np.abs(got[:, page, off] - want) <= tol), (
                kv, s, pos, np.abs(got[:, page, off] - want).max())
    written[:, TRASH] = True                # padding rows land in the trash page
    for leaf_b, leaf_a in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(np.asarray(leaf_a)[~written],
                                      leaf_b[~written])


@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
def test_mixed_and_decode_write_in_place(setup, kv_dtype):
    cfg, params, tokens = setup
    int8 = kv_dtype == "int8"
    serve = ServeConfig(page_size=PS, n_pages=N_PAGES, kv_dtype=kv_dtype)
    progs = build_step_programs(cfg, kv_dtype, "cpu")
    kpg, vpg = _random_pool(init_pool(cfg, serve, N_PAGES, jnp.bfloat16), 5)
    ref = _reference_kv(cfg, params, tokens, int8)

    chunk = np.zeros((2, C), np.int32)
    chunk[0], chunk[1, :5] = tokens["a"][:8], tokens["c"]
    mb = dict(p_tokens=jnp.asarray(chunk), p_table=_table("ac"),
              p_start=jnp.zeros((2,), jnp.int32),
              p_lens=jnp.asarray([8, 5], jnp.int32),
              d_tokens=jnp.asarray([tokens["b"][0], 0, tokens["d"][0]]),
              d_table=_table(["b", None, "d"]),
              d_lens=jnp.zeros((3,), jnp.int32),
              d_active=jnp.asarray([True, False, True]))
    before = jax.tree.map(np.asarray, (kpg, vpg))     # the programs donate
    _, _, pools, _ = progs.mixed(params, mb, kpg, vpg)
    _check(before, pools, MIXED_WRITES, ref, cfg, int8)

    before = jax.tree.map(np.asarray, pools)
    _, pools = progs.decode(
        params, jnp.asarray([tokens["a"][8], tokens["b"][1], 0]), *pools,
        _table(["a", "b", None]), jnp.asarray([8, 1, 0], jnp.int32),
        jnp.asarray([True, True, False]))
    _check(before, pools, DECODE_WRITES, ref, cfg, int8)
