"""Pure-jnp oracles for the Pallas kernels.

These are the same functions the models use on the XLA-native path
(repro.models.layers), re-exported under kernel-facing signatures so the
kernel tests sweep one call site.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.models.layers import flash_attention as _flash_ref
from repro.models.layers import paged_attention_ref as _paged_ref


def paged_decode_attention_ref(q, k_pages, v_pages, layer, block_table,
                               kv_lens, window, *, scale, softcap=None):
    """q [B, H_p, d]; whole pools [L, N, ps, KV_p * d] read at ``layer``;
    each row's one query sits at position ``kv_lens - 1``. -> [B, H_p, d]."""
    o = _paged_ref(q[:, None], k_pages, v_pages, block_table, kv_lens,
                   (kv_lens - 1)[:, None], scale=scale, layer=layer,
                   window=window, attn_softcap=softcap)
    return o[:, 0]


def flash_attention_ref(q, k, v, kv_lens, *, scale, causal=True, window=None,
                        softcap=None):
    """q [B, KV_p, T, G, d]; k/v [B, KV_p, Tk, d] -> o like q."""
    B, KV_p, T, G, d = q.shape
    Tk = k.shape[2]
    qm = q.transpose(0, 2, 1, 3, 4).reshape(B, T, KV_p * G, d)
    km = k.transpose(0, 2, 1, 3)                     # [B, Tk, KV_p, d]
    vm = v.transpose(0, 2, 1, 3)
    q_positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    kv_positions = jnp.broadcast_to(jnp.arange(Tk, dtype=jnp.int32)[None], (B, Tk))
    o = _flash_ref(qm, km, vm, q_positions=q_positions,
                   kv_positions=kv_positions, kv_valid_len=kv_lens,
                   scale=scale, causal=causal, window=window,
                   attn_softcap=softcap)
    return o.reshape(B, T, KV_p, G, d).transpose(0, 2, 1, 3, 4)
