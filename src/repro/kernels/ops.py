"""Jit'd wrappers around the Pallas kernels.

Handle layout adaptation (model convention <-> kernel tiling) and head-dim
padding to the 128-lane VREG width.  Every caller states ``interpret``:
True runs the Pallas interpreter (how the CPU tests validate the kernels
against the ref.py oracles), False hands the kernel to the TPU compiler.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import flash_attention as _flash_mod
from repro.kernels import paged_attention as _paged_mod
from repro.kernels import paged_attention_int8 as _paged_i8_mod
from repro.models import transformer as T

LANE = 128


def _pad_d(x, d_pad):
    d = x.shape[-1]
    if d == d_pad:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, d_pad - d)])


@partial(jax.jit, static_argnames=("scale", "softcap", "interpret"))
def paged_decode_attention(q, k_pages, v_pages, layer, block_table, kv_lens,
                           window, *, scale, softcap=None, interpret):
    """Model-layout decode attention over the whole pools.

    q [B, H_p, hd]; pools [L, N, ps, KV_p * hd] read at ``layer``;
    kv_lens [B] positions each row sees (0: a dead row, which reads
    nothing and returns zeros); window a traced scalar. Returns
    [B, H_p, hd] in q's dtype.
    """
    B, H_p, d = q.shape
    KV_p = k_pages.shape[-1] // d
    kv_of = np.arange(H_p) // (H_p // KV_p)       # padded q slot -> KV slot
    own = jnp.asarray(kv_of[:, None] == np.arange(KV_p)[None])
    q_row = jnp.where(own[None, :, :, None], q[:, :, None, :], 0)
    o = _paged_mod.paged_decode_attention(
        q_row.reshape(B, H_p, KV_p * d).astype(k_pages.dtype), k_pages,
        v_pages, layer, block_table, kv_lens, window, scale=scale,
        softcap=softcap, interpret=interpret)
    o = o.reshape(B, H_p, KV_p, d)[:, np.arange(H_p), kv_of]
    return o.astype(q.dtype)


def paged_decode_attn(q, k_new, v_new, kpg, vpg, layer, block_table,
                      seq_lens, active, *, scale, window, attn_softcap,
                      interpret):
    """``transformer.default_decode_attn`` with the Pallas decode kernel.

    Writes the new token as the reference does, then attends over each
    active row's ``seq_lens + 1`` positions; inactive rows read nothing
    and their output is zeros. Returns (o [B,1,H_p,hd], kpg, vpg).
    """
    kpg, vpg = T.write_kv_token(kpg, vpg, layer, k_new, v_new, block_table,
                                seq_lens, active)
    if q.shape[0] == 0:                 # a program with no decode rows
        return q, kpg, vpg
    kv_lens = jnp.where(active, seq_lens + 1, 0)
    win = jnp.asarray(T.NO_WINDOW if window is None else window, jnp.int32)
    o = paged_decode_attention(q[:, 0], kpg, vpg, layer, block_table,
                               kv_lens, win, scale=scale,
                               softcap=attn_softcap, interpret=interpret)
    return o[:, None], kpg, vpg


@partial(jax.jit, static_argnames=("scale", "window", "softcap", "interpret"))
def paged_attention_int8(q, k_pages, k_scales, v_pages, v_scales,
                         block_table, kv_lens, q_pos, *,
                         scale, window=None, softcap=None, interpret):
    """Model-layout ragged paged attention over int8 pages.

    q [B, Tq, H_p, d] fp; code pages [N, ps, KV_p, d] int8; scale
    sidecars [N, ps, KV_p, 1] f32.  Returns [B, Tq, H_p, d].
    Codes pad with zeros to the 128-lane width — the padded columns
    dequantize to exactly 0 and are sliced off after the kernel.
    """
    B, Tq, H_p, d = q.shape
    KV_p = k_pages.shape[2]
    G = H_p // KV_p
    d_pad = ((d + LANE - 1) // LANE) * LANE
    qk = _pad_d(q, d_pad).reshape(B, Tq, KV_p, G, d_pad).transpose(0, 2, 1, 3, 4)
    kp = _pad_d(k_pages, d_pad)
    vp = _pad_d(v_pages, d_pad)
    o = _paged_i8_mod.paged_attention_int8(
        qk, kp, k_scales, vp, v_scales, block_table.astype(jnp.int32),
        kv_lens.astype(jnp.int32), q_pos.astype(jnp.int32), scale=scale,
        window=window, softcap=softcap, interpret=interpret)
    o = o.transpose(0, 2, 1, 3, 4).reshape(B, Tq, H_p, d_pad)
    return o[..., :d]


@partial(jax.jit, static_argnames=("scale", "causal", "window", "softcap",
                                   "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, kv_lens, *, scale, causal=True, window=None,
                    softcap=None, block_q=128, block_k=128, interpret):
    """Model-layout flash attention.

    q [B, T, H_p, d]; k/v [B, Tk, KV_p, d]. Returns [B, T, H_p, d].
    """
    B, T, H_p, d = q.shape
    KV_p = k.shape[2]
    G = H_p // KV_p
    d_pad = ((d + LANE - 1) // LANE) * LANE
    qk = _pad_d(q, d_pad).reshape(B, T, KV_p, G, d_pad).transpose(0, 2, 1, 3, 4)
    kk = _pad_d(k, d_pad).transpose(0, 2, 1, 3)
    vk = _pad_d(v, d_pad).transpose(0, 2, 1, 3)
    o = _flash_mod.flash_attention(
        qk, kk, vk, kv_lens.astype(jnp.int32), scale=scale, causal=causal,
        window=window, softcap=softcap,
        block_q=min(block_q, T), block_k=min(block_k, k.shape[1]),
        interpret=interpret)
    o = o.transpose(0, 2, 1, 3, 4).reshape(B, T, H_p, d_pad)
    return o[..., :d]
