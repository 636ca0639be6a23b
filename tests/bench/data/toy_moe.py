"""Plain reference of a toy sparse-expert decoder, for the harness's own
tests: the dense reference's attention with a per-head RMS norm on q and
k (``use_qk_norm``), and in place of the MLP a router over ``n_experts``
SwiGLU experts of which each token takes the ``top_k`` most probable,
their gates renormalised to sum to 1.  Every expert is computed for
every token and weighted by its gate (0 where not chosen), so nothing is
dropped: the program has to run with a capacity that drops nothing.

Copied into a benchmark tree as ``bench/references/toy_moe.py`` beside
``dense_transformer.py``, whose pieces it reuses; with its weight rules
and counts it is what a new family brings as a new file.
"""
from __future__ import annotations

import functools
import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from harness.costs import chunk_keys


def _dense():
    path = Path(__file__).with_name("dense_transformer.py")
    spec = importlib.util.spec_from_file_location("toy_moe_dense", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


dense = _dense()
_mm, _norm, _rope, _round_fp8 = dense._mm, dense._norm, dense._rope, dense._round_fp8


def _moe_sizes(config: dict):
    m = config["model"]
    return m["n_experts"], m["top_k"]


@functools.partial(jax.jit, static_argnames=("sizes", "quant"))
def _layer(x, lp, sizes, quant):
    s = dict(sizes)
    T, D = x.shape
    H, KV, hd, E, K = (s["n_heads"], s["n_kv_heads"], s["head_dim"],
                       s["n_experts"], s["top_k"])
    lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    pos = jnp.arange(T)
    h = _norm(x, lp["ln1"], s["norm_eps"])
    q = _mm(h, lp["wq"].reshape(D, H * hd), quant).reshape(T, H, hd)
    k = _mm(h, lp["wk"].reshape(D, KV * hd), quant).reshape(T, KV, hd)
    v = _mm(h, lp["wv"].reshape(D, KV * hd), quant).reshape(T, KV, hd)
    q, k = _norm(q, lp["q_norm"], s["norm_eps"]), _norm(k, lp["k_norm"], s["norm_eps"])
    q, k = _rope(q, pos, s["rope_theta"]), _rope(k, pos, s["rope_theta"])
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    if quant == "fp8":
        q, k, v = _round_fp8(q, -1), _round_fp8(k, -1), _round_fp8(v, 0)
    sc = jnp.einsum("thd,shd->hts", q, k,
                    precision=jax.lax.Precision.HIGHEST) / np.sqrt(hd)
    sc = jnp.where((pos[:, None] >= pos[None, :])[None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, -1)
    if quant == "fp8":
        p = _round_fp8(p, -1)
    o = jnp.einsum("hts,shd->thd", p, v, precision=jax.lax.Precision.HIGHEST)
    x = x + _mm(o.reshape(T, H * hd), lp["wo"].reshape(H * hd, D), quant)
    h = _norm(x, lp["ln2"], s["norm_eps"])
    moe = lp["moe"]
    probs = jax.nn.softmax(_mm(h, moe["router"], quant), -1)          # [T, E]
    top, idx = jax.lax.top_k(probs, K)
    top = top / top.sum(-1, keepdims=True)
    gate = (jax.nn.one_hot(idx, E) * top[..., None]).sum(1)           # [T, E]
    y = jnp.zeros_like(x)
    for e in range(E):
        u = jax.nn.silu(_mm(h, moe["w_gate"][e], quant)) * _mm(h, moe["w_up"][e], quant)
        y = y + gate[:, e:e + 1] * _mm(u, moe["w_down"][e], quant)
    return x + y


SIZE_KEYS = ("n_layers", "n_heads", "n_kv_heads", "head_dim", "rope_theta", "norm_eps")


def check_config(config: dict) -> None:
    want = {"mlp": "swiglu_moe", "norm": "rms_1plus", "bias": False,
            "positions": "rope", "sliding_window": None}
    for key, val in want.items():
        if config.get(key, val) != val:
            raise ValueError(f"the toy MoE reference implements {key}={val!r}, "
                             f"not {config.get(key)!r}")
    if not config["model"].get("use_qk_norm"):
        raise ValueError("the toy MoE reference implements use_qk_norm true")


def logits_at(params, config: dict, tokens, at, quant=None):
    check_config(config)
    E, K = _moe_sizes(config)
    sizes = tuple((k, config[k]) for k in SIZE_KEYS) + (("n_experts", E), ("top_k", K))
    blocks = params["blocks"]
    x = dense._embed(params["embed"], jnp.asarray(tokens, jnp.int32))
    for i in range(config["n_layers"]):
        x = _layer(x, jax.tree.map(lambda a, i=i: a[i], blocks), sizes, quant)
    return dense._head(x, jnp.asarray(at, jnp.int32), params["ln_f"], params["head"],
                       dense.frozen_sizes(config), quant)


def leaf_std(name: str, shape, config: dict) -> float:
    last = name.rsplit("/", 1)[-1]
    if last in ("q_norm", "k_norm"):
        return 0.1
    if last == "router":
        return 1.0 / math.sqrt(shape[1])                  # [L, D, E]
    if last in ("w_gate", "w_up"):
        return 1.0 / math.sqrt(shape[2])                  # [L, E, D, F]
    if last == "w_down":                                  # [L, E, F, D]
        return 1.0 / math.sqrt(2 * config["n_layers"]) / math.sqrt(shape[2])
    return dense.leaf_std(name, shape, config)


def _per_row(s: dict) -> int:
    D, H, KV, hd, F = (s["d_model"], s["n_heads"], s["n_kv_heads"],
                       s["head_dim"], s["d_ff"])
    E, K = _moe_sizes(s)
    return 2 * (D * H * hd + 2 * D * KV * hd + H * hd * D + D * E + K * 3 * D * F)


def call_flops(config: dict, chunks, decodes) -> int:
    chunks, decodes = list(chunks), list(decodes)
    rows = sum(n for _, n in chunks) + len(decodes)
    keys = sum(chunk_keys(st, n) for st, n in chunks) + sum(n + 1 for n in decodes)
    L, H, hd = config["n_layers"], config["n_heads"], config["head_dim"]
    return (rows * _per_row(config) * L + 4 * H * hd * keys * L
            + (len(chunks) + len(decodes)) * 2 * config["d_model"] * config["vocab_size"])


def call_bytes(config: dict, chunks, decodes, itemsize: int) -> int:
    chunks, decodes = list(chunks), list(decodes)
    D, H, KV, hd, F, L, V = (config[k] for k in ("d_model", "n_heads", "n_kv_heads",
                                                 "head_dim", "d_ff", "n_layers",
                                                 "vocab_size"))
    E, _ = _moe_sizes(config)
    layer = D * H * hd + 2 * D * KV * hd + H * hd * D + D * E + E * 3 * D * F + 2 * D + 2 * hd
    rows = sum(n for _, n in chunks) + len(decodes)
    kv = sum(st for st, _ in chunks) + sum(decodes) + rows
    return ((L * layer + V * D + D + rows * D) * itemsize
            + kv * dense._kv_bytes_per_token(config, itemsize))
