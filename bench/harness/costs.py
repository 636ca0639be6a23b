"""Useful FLOPs and least bytes of one mixed-program call, from shapes.

Counted from the configuration's sizes and the rows the call really
served, never from the program's padded shapes:

FLOPs
  * every real row (prefill chunk token or active decode token) passes
    the layer GEMMs once: ``2 * (D*H*hd + 2*D*KV*hd + H*hd*D + 2*D*F)``
    per layer (q, k and v, o, and the two MLP matrices);
  * attention at live lengths: a query at position ``p`` attends ``p + 1``
    keys, which costs ``4 * H * hd * (p + 1)`` per layer (scores and the
    weighted sum);
  * the unembedding, ``2 * D * V``, for each row whose logits the step
    samples from: every active decode row and every stream that ran a
    chunk (the program computes one logits row per such stream).

Bytes (the least a perfect program moves through HBM)
  * every weight once: layer weights, norms, the output head (its
    ``V`` real rows), and the embedding rows of the real tokens;
  * the live KV read once: ``start`` history tokens for a chunk,
    ``seq_len`` for a decode row;
  * the new KV written once: ``n`` tokens for a chunk, one for a decode.
"""
from __future__ import annotations

from typing import Iterable, Tuple

Chunk = Tuple[int, int]        # (start position, tokens)


def sizes_of(cfg: dict) -> dict:
    return {k: cfg[k] for k in ("n_layers", "d_model", "n_heads", "n_kv_heads",
                                "head_dim", "d_ff", "vocab_size")}


def gemm_flops_per_row(s: dict) -> int:
    D, H, KV, hd, F = (s["d_model"], s["n_heads"], s["n_kv_heads"],
                       s["head_dim"], s["d_ff"])
    return 2 * (D * H * hd + 2 * D * KV * hd + H * hd * D + 2 * D * F) * s["n_layers"]


def attn_flops(s: dict, keys: int) -> int:
    """FLOPs of queries that attend ``keys`` keys in total."""
    return 4 * s["n_heads"] * s["head_dim"] * keys * s["n_layers"]


def chunk_keys(start: int, n: int) -> int:
    """Keys attended by a chunk of ``n`` queries from position ``start``."""
    return n * start + n * (n + 1) // 2


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


def kv_bytes_per_token(s: dict, itemsize: int) -> int:
    """K and V of one token over every layer."""
    return 2 * s["n_kv_heads"] * s["head_dim"] * s["n_layers"] * itemsize


def call_bytes(s: dict, chunks: Iterable[Chunk], decodes: Iterable[int],
               itemsize: int) -> int:
    chunks, decodes = list(chunks), list(decodes)
    rows = sum(n for _, n in chunks) + len(decodes)
    kv_read = sum(st for st, _ in chunks) + sum(decodes)
    kv_write = rows
    return (weight_bytes(s, itemsize) + rows * s["d_model"] * itemsize
            + (kv_read + kv_write) * kv_bytes_per_token(s, itemsize))


def ideal_seconds(flops: float, nbytes: float, peaks: dict) -> Tuple[float, str]:
    """The least time of a call on the chip, and which bound sets it."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
