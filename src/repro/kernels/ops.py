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

from repro.kernels import flash_attention as _flash_mod
from repro.kernels import paged_attention as _paged_mod
from repro.kernels import paged_attention_int8 as _paged_i8_mod

LANE = 128


def _pad_d(x, d_pad):
    d = x.shape[-1]
    if d == d_pad:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, d_pad - d)])


@partial(jax.jit, static_argnames=("scale", "window", "softcap", "interpret"))
def paged_attention(q, k_pages, v_pages, block_table, kv_lens, q_pos, *,
                    scale, window=None, softcap=None, interpret):
    """Model-layout ragged paged attention.

    q [B, Tq, H_p, d]; pages [N, ps, KV_p, d]. Returns [B, Tq, H_p, d].
    """
    B, Tq, H_p, d = q.shape
    KV_p = k_pages.shape[2]
    G = H_p // KV_p
    d_pad = ((d + LANE - 1) // LANE) * LANE
    qk = _pad_d(q, d_pad).reshape(B, Tq, KV_p, G, d_pad).transpose(0, 2, 1, 3, 4)
    kp = _pad_d(k_pages, d_pad)
    vp = _pad_d(v_pages, d_pad)
    o = _paged_mod.paged_attention(
        qk, kp, vp, block_table.astype(jnp.int32), kv_lens.astype(jnp.int32),
        q_pos.astype(jnp.int32), scale=scale, window=window, softcap=softcap,
        interpret=interpret)
    o = o.transpose(0, 2, 1, 3, 4).reshape(B, Tq, H_p, d_pad)
    return o[..., :d]


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


def paged_attn_model_fn(interpret):
    """Adapter matching transformer.default_paged_attn's signature."""
    def fn(q, kpg, vpg, block_table, kv_lens, q_positions, *, scale, window,
           attn_softcap):
        q_pos0 = q_positions[:, 0]
        w = None
        if window is not None:
            import numpy as np
            w = int(window) if not hasattr(window, "aval") else None
            # traced per-layer window (local/global patterns) falls back to
            # the ref path in model code; kernels take static windows.
        return paged_attention(q, kpg, vpg, block_table, kv_lens, q_pos0,
                               scale=scale, window=w, softcap=attn_softcap,
                               interpret=interpret)
    return fn
