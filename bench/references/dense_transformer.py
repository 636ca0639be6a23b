"""Plain reference of the dense decoder the configurations describe.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision: no
paging, no batching, no kernels, and nothing imported from the program.
One sequence at a time, one layer at a time, so that a 3B model fits
beside its bf16 weights.

What it computes, from the configuration's keys:

  x = embed[tokens]
  per layer:  h = norm(x; ln1);  q, k, v = h Wq, h Wk, h Wv  (GQA: query
              head j reads key/value head j // (heads / kv_heads));
              rotary embedding on q and k (half-split pairs, base
              ``rope_theta``); causal softmax(q k^T / sqrt(head_dim)) v,
              over the last ``sliding_window`` keys where that is set;
              x += o Wo;  x += gelu_tanh(norm(x; ln2) W_in) W_out
  logits = norm(x; ln_f) head^T, over the first ``vocab_size`` rows
  norm(x; g) = x / sqrt(mean(x^2) + eps) * (1 + g)

``quant="fp8"`` is the control: every matrix product rounds both of its
operands to float8_e4m3fn first, each scaled to its largest magnitude:
in the weight GEMMs (projections, MLP, unembedding) weights per output
column and activations per row; in attention q and k per head vector, v per
(head, dim) column over the keys, and the softmax weights per query row.
Accumulation stays float32.

The module is also the dense family's part of the harness (see
``bench/harness/spec.py`` ``FAMILY_API``): the rule each weight is drawn
by (``leaf_std``) and the useful FLOPs and least bytes of a mixed call
(``call_flops``, ``call_bytes``).

Weight scales follow the usual pre-norm recipe: a projection from width
``n`` has standard deviation ``1 / sqrt(n)``, the two projections back
into the residual stream are further scaled by ``1 / sqrt(2 * layers)``,
and the output head takes 0.02.  Embeddings take 1, not the usual 0.02:
at 0.02 the token read is lost under the layers' sum, and a deep random
model puts its first choice on the same few tokens whatever the context
(rank collapse), so its greedy output would not depend on the attention
or the cache the check is there to test.  Norm gains are
``0.1 * N(0, 1)`` rather than zero, so that the reference has to apply
them as the program does.

FLOPs of a call, counted from the configuration's sizes and the rows the
call really served, never from the program's padded shapes:

  * every real row (prefill chunk token or active decode token) passes
    the layer GEMMs once: ``2 * (D*H*hd + 2*D*KV*hd + H*hd*D + 2*D*F)``
    per layer (q, k and v, o, and the two MLP matrices);
  * attention at live lengths: a query at position ``p`` attends ``p + 1``
    keys, which costs ``4 * H * hd * (p + 1)`` per layer (scores and the
    weighted sum);
  * the unembedding, ``2 * D * V``, for each row whose logits the step
    samples from: every active decode row and every stream that ran a
    chunk (the program computes one logits row per such stream).

Bytes, the least a perfect program moves through HBM:

  * every weight once: layer weights, norms, the output head (its
    ``V`` real rows), and the embedding rows of the real tokens;
  * the live KV read once: ``start`` history tokens for a chunk,
    ``seq_len`` for a decode row;
  * the new KV written once: ``n`` tokens for a chunk, one for a decode.
"""
from __future__ import annotations

import functools
import math
from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np

from harness.costs import Chunk, chunk_keys

F8_MAX = 448.0        # largest finite float8_e4m3fn


def _round_fp8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, quant):
    """x [T, n] @ w [n, m] in f32, or through fp8 operands."""
    if quant == "fp8":
        x = _round_fp8(x, axis=-1)
        w = _round_fp8(w, axis=0)
    return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + g)


def _rope(x, pos, theta):
    """x [T, H, d]: rotate (x[:d/2], x[d/2:]) pairs by pos * theta^(-2i/d)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


@functools.partial(jax.jit, static_argnames=("sizes", "quant"))
def _layer(x, lp, sizes, quant):
    s = dict(sizes)
    T, D = x.shape
    H, KV, hd = s["n_heads"], s["n_kv_heads"], s["head_dim"]
    lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    pos = jnp.arange(T)
    h = _norm(x, lp["ln1"], s["norm_eps"])
    q = _mm(h, lp["wq"].reshape(D, H * hd), quant).reshape(T, H, hd)
    k = _mm(h, lp["wk"].reshape(D, KV * hd), quant).reshape(T, KV, hd)
    v = _mm(h, lp["wv"].reshape(D, KV * hd), quant).reshape(T, KV, hd)
    q, k = _rope(q, pos, s["rope_theta"]), _rope(k, pos, s["rope_theta"])
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    if quant == "fp8":
        q, k, v = _round_fp8(q, -1), _round_fp8(k, -1), _round_fp8(v, 0)
    sc = jnp.einsum("thd,shd->hts", q, k,
                    precision=jax.lax.Precision.HIGHEST) / np.sqrt(hd)
    seen = pos[:, None] - pos[None, :]                       # query - key
    keep = seen >= 0
    if s["sliding_window"] is not None:
        keep = keep & (seen < s["sliding_window"])
    sc = jnp.where(keep[None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, -1)
    if quant == "fp8":
        p = _round_fp8(p, -1)
    o = jnp.einsum("hts,shd->thd", p, v, precision=jax.lax.Precision.HIGHEST)
    x = x + _mm(o.reshape(T, H * hd), lp["wo"].reshape(H * hd, D), quant)
    h = _norm(x, lp["ln2"], s["norm_eps"])
    u = jax.nn.gelu(_mm(h, lp["mlp"]["w_in"], quant), approximate=True)
    return x + _mm(u, lp["mlp"]["w_out"], quant)


@functools.partial(jax.jit, static_argnames=("sizes", "quant"))
def _head(x, at, ln_f, head, sizes, quant):
    s = dict(sizes)
    h = _norm(x[at], ln_f.astype(jnp.float32), s["norm_eps"])
    w = head[: s["vocab_size"]].astype(jnp.float32)
    return _mm(h, w.T, quant)


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(jnp.float32)


SIZE_KEYS = ("n_layers", "n_heads", "n_kv_heads", "head_dim", "vocab_size",
             "rope_theta", "norm_eps", "sliding_window")


def frozen_sizes(config: dict) -> tuple:
    """The hashable sizes the jitted pieces are specialised on."""
    return tuple((k, config[k]) for k in SIZE_KEYS)


def check_config(config: dict) -> None:
    """Refuse a configuration this reference does not implement."""
    want = {"mlp": "gelu_tanh", "norm": "rms_1plus", "bias": False,
            "positions": "rope"}
    for key, val in want.items():
        if config.get(key, val) != val:
            raise ValueError(f"the dense reference implements {key}={val!r}, "
                             f"not {config.get(key)!r}")


def logits_at(params, config: dict, tokens, at, quant=None):
    """f32 logits [len(at), vocab_size] of one sequence ``tokens`` [T] at
    positions ``at``.  ``params`` is the served tree (any float type)."""
    check_config(config)
    sizes = frozen_sizes(config)
    blocks = params["blocks"]
    x = _embed(params["embed"], jnp.asarray(tokens, jnp.int32))
    for i in range(config["n_layers"]):
        lp = jax.tree.map(lambda a, i=i: a[i], blocks)
        x = _layer(x, lp, sizes, quant)
    return _head(x, jnp.asarray(at, jnp.int32), params["ln_f"],
                 params.get("head", params["embed"]), sizes, quant)


# ------------------------------------------------------ the weights' rules --
def leaf_std(name: str, shape, config: dict) -> float:
    """Standard deviation of the leaf ``name`` (``blocks/wq``, ...)."""
    L = config["n_layers"]
    out = 1.0 / math.sqrt(2 * L)
    last = name.rsplit("/", 1)[-1]
    if last == "embed":
        return 1.0
    if last == "head":
        return 0.02
    if last.startswith("ln"):
        return 0.1
    if last in ("wq", "wk", "wv", "w_in"):
        return 1.0 / math.sqrt(shape[1])                    # [L, D, ...]
    if last == "wo":
        return out / math.sqrt(shape[1] * shape[2])         # [L, H, hd, D]
    if last == "w_out":
        return out / math.sqrt(shape[1])                    # [L, F, D]
    raise ValueError(f"no initialisation rule for parameter {name!r}")


# --------------------------------------------------- a call's FLOPs, bytes --
def gemm_flops_per_row(s: dict) -> int:
    D, H, KV, hd, F = (s["d_model"], s["n_heads"], s["n_kv_heads"],
                       s["head_dim"], s["d_ff"])
    return 2 * (D * H * hd + 2 * D * KV * hd + H * hd * D + 2 * D * F) * s["n_layers"]


def attn_flops(s: dict, keys: int) -> int:
    """FLOPs of queries that attend ``keys`` keys in total."""
    return 4 * s["n_heads"] * s["head_dim"] * keys * s["n_layers"]


def unembed_flops(s: dict) -> int:
    return 2 * s["d_model"] * s["vocab_size"]


def call_flops(s: dict, chunks: Iterable[Chunk], decodes: Iterable[int]) -> int:
    """Useful FLOPs of one call: ``chunks`` of (start, n), decode rows of
    kv length ``seq_len`` (the new token sits at position ``seq_len``)."""
    chunks, decodes = list(chunks), list(decodes)
    rows = sum(n for _, n in chunks) + len(decodes)
    keys = (sum(chunk_keys(st, n) for st, n in chunks)
            + sum(L + 1 for L in decodes))
    logit_rows = len(chunks) + len(decodes)
    return (rows * gemm_flops_per_row(s) + attn_flops(s, keys)
            + logit_rows * unembed_flops(s))


def weight_bytes(s: dict, itemsize: int) -> int:
    D, H, KV, hd, F, L, V = (s["d_model"], s["n_heads"], s["n_kv_heads"],
                             s["head_dim"], s["d_ff"], s["n_layers"],
                             s["vocab_size"])
    per_layer = D * H * hd + 2 * D * KV * hd + H * hd * D + 2 * D * F + 2 * D
    return (L * per_layer + V * D + D) * itemsize


def _kv_bytes_per_token(s: dict, itemsize: int) -> int:
    """K and V of one token over every layer."""
    return 2 * s["n_kv_heads"] * s["head_dim"] * s["n_layers"] * itemsize


def call_bytes(s: dict, chunks: Iterable[Chunk], decodes: Iterable[int],
               itemsize: int) -> int:
    chunks, decodes = list(chunks), list(decodes)
    rows = sum(n for _, n in chunks) + len(decodes)
    kv_read = sum(st for st, _ in chunks) + sum(decodes)
    kv_write = rows
    return (weight_bytes(s, itemsize) + rows * s["d_model"] * itemsize
            + (kv_read + kv_write) * _kv_bytes_per_token(s, itemsize))
