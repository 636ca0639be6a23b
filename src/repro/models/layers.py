"""Core layer primitives: norms, RoPE, flash attention (pure-jnp online
softmax over KV blocks), paged attention reference, MLP variants, init.

All attention here is the XLA-native path (used for training, the dry-run,
and as the oracle for the Pallas kernels in repro.kernels).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


# ---------------------------------------------------------------- norms ----
def rms_norm(x, w, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def head_rms_norm(x, w, eps=1e-6):
    """Per-head RMS norm over the last (head_dim) axis (qwen3 qk-norm)."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


# ----------------------------------------------------------------- rope ----
def rope(x, positions, theta=10_000.0):
    """x: [..., T, H, d]; positions: [..., T] (broadcastable)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[..., None].astype(jnp.float32) * inv          # [..., T, d/2]
    cos = jnp.cos(ang)[..., None, :]                               # [..., T, 1, d/2]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


def softcap(logits, cap):
    return jnp.tanh(logits / cap) * cap


# ------------------------------------------------- flash attention (jnp) ----
def flash_attention(
    q,                      # [B, Tq, H, d]  (already scaled is NOT assumed)
    k,                      # [B, Tk, KV, d]
    v,                      # [B, Tk, KV, d]
    *,
    q_positions,            # [B, Tq] int32
    kv_positions,           # [B, Tk] int32
    kv_valid_len=None,      # [B] int32 (positions >= len masked); None = all
    scale: float,
    causal: bool = True,
    window=None,            # None | int | [B?] per-example? -> int or [Tq-broadcast]
    window_per_layer=None,  # scalar jnp value overriding window (scan-friendly)
    attn_softcap: Optional[float] = None,
    block_kv: int = 512,
    _return_lse: bool = False,
    k_scale=None,           # [B, Tk, KV, 1] dequant scales (int8 KV cache)
    v_scale=None,
):
    """Online-softmax attention over KV blocks; O(Tq * block) live memory.

    GQA is handled by folding query heads into groups of the KV heads:
    H must be a multiple of KV. With _return_lse, also returns the
    log-normalizer [B, KV, G, Tq] (for the custom backward).
    """
    B, Tq, H, d = q.shape
    Tk, KV = k.shape[1], k.shape[2]
    if H % KV:
        raise ValueError(f"query heads H={H} must be a multiple of KV={KV}")
    G = H // KV

    orig_tk = Tk
    pad = (-Tk) % block_kv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pad)), constant_values=2**30)
        if k_scale is not None:
            k_scale = jnp.pad(k_scale, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v_scale = jnp.pad(v_scale, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Tk = Tk + pad
    nblk = Tk // block_kv

    qg = q.reshape(B, Tq, KV, G, d).astype(jnp.float32) * scale
    kb_all = k.reshape(B, nblk, block_kv, KV, d)
    vb_all = v.reshape(B, nblk, block_kv, KV, d)
    pos_all = kv_positions.reshape(B, nblk, block_kv)
    if k_scale is not None:
        ks_all = k_scale.reshape(B, nblk, block_kv, KV, 1).transpose(1, 0, 2, 3, 4)
        vs_all = v_scale.reshape(B, nblk, block_kv, KV, 1).transpose(1, 0, 2, 3, 4)

    if window_per_layer is not None:
        window = window_per_layer

    def flash_vmem_body(carry, xs):
        m, l, acc = carry
        if k_scale is not None:
            kb, vb, posb, ksb, vsb = xs         # int8 codes + scales
            kb = kb.astype(jnp.float32) * ksb   # dequant in "VMEM"
            vb = vb.astype(jnp.float32) * vsb
        else:
            kb, vb, posb = xs                   # [B, blk, KV, d], [B, blk]
        # logits: [B, KV, G, Tq, blk]
        logits = jnp.einsum(
            "bqKgd,bkKd->bKgqk", qg, kb.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        if attn_softcap is not None:
            logits = softcap(logits, attn_softcap)
        mask = jnp.ones((B, 1, 1, Tq, block_kv), dtype=bool)
        pb = posb[:, None, None, None, :]
        qp = q_positions[:, None, None, :, None]
        if causal:
            mask &= pb <= qp
        if window is not None:
            mask &= pb > qp - window
        if kv_valid_len is not None:
            mask &= pb < kv_valid_len[:, None, None, None, None]
        mask &= pb < 2**30  # padding sentinel
        logits = jnp.where(mask, logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[..., None]) * mask
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bKgqk,bkKd->bKgqd", p, vb.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, G, Tq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Tq), jnp.float32)
    a0 = jnp.zeros((B, KV, G, Tq, d), jnp.float32)
    xs = (kb_all.transpose(1, 0, 2, 3, 4), vb_all.transpose(1, 0, 2, 3, 4),
          pos_all.transpose(1, 0, 2))
    if k_scale is not None:
        xs = xs + (ks_all, vs_all)
    (m, l, acc), _ = jax.lax.scan(flash_vmem_body, (m0, l0, a0), xs)
    out = acc / jnp.maximum(l, 1e-30)[..., None]          # [B, KV, G, Tq, d]
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Tq, H, d)
    if _return_lse:
        lse = m + jnp.log(jnp.maximum(l, 1e-30))          # [B, KV, G, Tq]
        return out.astype(q.dtype), lse
    return out.astype(q.dtype)


# -------------------------------------- flash attention with kernel bwd ----
# §Perf optimization: differentiating through the jnp flash scan makes JAX
# stack per-block residuals (measured ~3.3 TB global on qwen3 train_4k —
# EXPERIMENTS.md §Perf). The kernel-style backward saves only (o, lse) and
# recomputes logits per block — exactly what the Pallas flash bwd does.
NO_WINDOW_STATIC = 2**30


def flash_attention_ckpt(q, k, v, q_positions, kv_positions, kv_valid_len, *,
                         scale, causal=True, window=None, attn_softcap=None,
                         block_kv=512):
    """flash_attention with a custom recompute-based backward."""
    win = window if window is not None else NO_WINDOW_STATIC
    return _flash_ckpt(q, k, v, q_positions, kv_positions,
                       kv_valid_len if kv_valid_len is not None
                       else jnp.full((q.shape[0],), 2**30, jnp.int32),
                       jnp.asarray(win, jnp.int32),
                       scale, causal,
                       attn_softcap if attn_softcap is not None else 0.0,
                       block_kv)


@partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _flash_ckpt(q, k, v, q_pos, kv_pos, kv_len, window, scale, causal,
                softcap, block_kv):
    o, _ = _flash_ckpt_fwd(q, k, v, q_pos, kv_pos, kv_len, window, scale,
                           causal, softcap, block_kv)
    return o


def _flash_ckpt_fwd(q, k, v, q_pos, kv_pos, kv_len, window, scale, causal,
                    softcap, block_kv):
    sc = None if softcap == 0.0 else softcap
    out, lse = flash_attention(
        q, k, v, q_positions=q_pos, kv_positions=kv_pos, kv_valid_len=kv_len,
        scale=scale, causal=causal, window_per_layer=window,
        attn_softcap=sc, block_kv=block_kv, _return_lse=True)
    return out, (q, k, v, q_pos, kv_pos, kv_len, window, out, lse)


def _flash_ckpt_bwd(scale, causal, softcap, block_kv, res, do):
    q, k, v, q_pos, kv_pos, kv_len, window, out, lse = res
    B, Tq, H, d = q.shape
    Tk, KV = k.shape[1], k.shape[2]
    G = H // KV
    sc = None if softcap == 0.0 else softcap

    pad = (-Tk) % block_kv
    if pad:
        kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos_p = jnp.pad(kv_pos, ((0, 0), (0, pad)), constant_values=2**30)
    else:
        kp, vp, kv_pos_p = k, v, kv_pos
    nblk = (Tk + pad) // block_kv

    qg = q.reshape(B, Tq, KV, G, d).astype(jnp.float32) * scale
    og = out.reshape(B, Tq, KV, G, d).astype(jnp.float32)
    dog = do.reshape(B, Tq, KV, G, d).astype(jnp.float32)
    lseg = lse                                             # [B, KV, G, Tq]
    delta = (og * dog).sum(-1).transpose(0, 2, 3, 1)       # [B, KV, G, Tq]
    kb_all = kp.reshape(B, nblk, block_kv, KV, d).transpose(1, 0, 2, 3, 4)
    vb_all = vp.reshape(B, nblk, block_kv, KV, d).transpose(1, 0, 2, 3, 4)
    pos_all = kv_pos_p.reshape(B, nblk, block_kv).transpose(1, 0, 2)

    def flashbwd_vmem_body(dq_acc, xs):
        kb, vb, posb = xs
        logits = jnp.einsum("bqKgd,bkKd->bKgqk", qg, kb.astype(jnp.float32),
                            preferred_element_type=jnp.float32)
        dcap = 1.0
        if sc is not None:
            t = jnp.tanh(logits / sc)
            logits_c = t * sc
            dcap = 1.0 - jnp.square(t)
        else:
            logits_c = logits
        mask = jnp.ones((B, 1, 1, Tq, block_kv), dtype=bool)
        pb = posb[:, None, None, None, :]
        qp = q_pos[:, None, None, :, None]
        if causal:
            mask &= pb <= qp
        mask &= pb > qp - window
        mask &= pb < kv_len[:, None, None, None, None]
        mask &= pb < 2**30
        p = jnp.where(mask, jnp.exp(logits_c - lseg[..., None]), 0.0)
        dp = jnp.einsum("bqKgd,bkKd->bKgqk", dog, vb.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta[..., None]) * dcap            # [B,KV,G,Tq,blk]
        dq_blk = jnp.einsum("bKgqk,bkKd->bqKgd", ds, kb.astype(jnp.float32),
                            preferred_element_type=jnp.float32)
        dk_blk = jnp.einsum("bKgqk,bqKgd->bkKd", ds, qg,
                            preferred_element_type=jnp.float32)
        dv_blk = jnp.einsum("bKgqk,bqKgd->bkKd", p, dog,
                            preferred_element_type=jnp.float32)
        return dq_acc + dq_blk, (dk_blk, dv_blk)

    dq0 = jnp.zeros((B, Tq, KV, G, d), jnp.float32)
    dq, (dk_s, dv_s) = jax.lax.scan(flashbwd_vmem_body, dq0,
                                    (kb_all, vb_all, pos_all))
    dq = (dq * scale).reshape(B, Tq, H, d).astype(q.dtype)
    dk = dk_s.transpose(1, 0, 2, 3, 4).reshape(B, Tk + pad, KV, d)[:, :Tk]
    dv = dv_s.transpose(1, 0, 2, 3, 4).reshape(B, Tk + pad, KV, d)[:, :Tk]
    f0 = lambda x: np.zeros(x.shape, jax.dtypes.float0)
    return (dq, dk.astype(k.dtype), dv.astype(v.dtype),
            f0(q_pos), f0(kv_pos), f0(kv_len), f0(window))


_flash_ckpt.defvjp(_flash_ckpt_fwd, _flash_ckpt_bwd)


# ----------------------------------------------- paged attention (ref) -----
def gather_pages(pages, block_table, layer, head_dim):
    """Each row's pages: block_table [B, Pmax] -> [B, Pmax*ps, KV, head_dim].

    pages is the whole pool [L, N, ps, KV * head_dim] (``init_pages``),
    read at ``layer`` by one gather: no slab of the layer is taken first,
    since XLA may copy it. With ``layer`` None, pages is one layer in the
    int8 Pallas kernel's layout [N, ps, KV, head_dim], for its oracle.
    """
    B, Pmax = block_table.shape
    if layer is None:                   # as a pool of that one layer
        pages, layer = pages.reshape(*pages.shape[:2], -1)[None], 0
    g = pages[layer, block_table]       # [B, Pmax, ps, KV * head_dim]
    return g.reshape(B, Pmax * g.shape[2], g.shape[3] // head_dim, head_dim)


def paged_attention_ref(
    q,                 # [B, Tq, H, d] (Tq=1 decode, Tq=chunk prefill)
    k_pages, v_pages,  # whole pools [L, N, ps, KV*d] read at layer;
                       # one layer [N, ps, KV, d] if layer is None
    block_table,       # [B, Pmax] int32 (local page indices)
    kv_lens,           # [B] valid kv length (incl. freshly written tokens)
    q_positions,       # [B, Tq]
    *,
    scale, layer=None, window=None, attn_softcap=None, block_kv=512,
):
    """Reference paged attention: gather pages then flash over them.

    Used as the CPU/dry-run implementation and as the oracle for the
    Pallas kernels.
    """
    d = q.shape[-1]
    k = gather_pages(k_pages, block_table, layer, d)
    v = gather_pages(v_pages, block_table, layer, d)
    B, Tk = k.shape[:2]
    kv_pos = jnp.broadcast_to(jnp.arange(Tk, dtype=jnp.int32)[None], (B, Tk))
    return flash_attention(
        q, k, v, q_positions=q_positions, kv_positions=kv_pos,
        kv_valid_len=kv_lens, scale=scale, causal=True, window=window,
        attn_softcap=attn_softcap, block_kv=min(block_kv, Tk),
    )


# ------------------------------------------------------------------ mlp ----
def act_fn(name):
    return {"silu": jax.nn.silu, "gelu": partial(jax.nn.gelu, approximate=True),
            "relu": jax.nn.relu}[name]


def mlp_apply(p, x, act: str):
    """x [..., D]. Gated (SwiGLU/GeGLU) or classic 2-matrix MLP."""
    if act == "gelu_mlp":
        h = jax.nn.gelu(jnp.einsum("...d,df->...f", x, p["w_in"]), approximate=True)
        return jnp.einsum("...f,fd->...d", h, p["w_out"])
    g = act_fn(act)(jnp.einsum("...d,df->...f", x, p["w_gate"]))
    u = jnp.einsum("...d,df->...f", x, p["w_up"])
    return jnp.einsum("...f,fd->...d", g * u, p["w_down"])


# ----------------------------------------------------------------- init ----
def dense_init(key, shape, in_axis_size, dtype, scale=1.0):
    std = scale / math.sqrt(in_axis_size)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def mlp_init(key, d_model, d_ff, act, dtype, n_layers_scale=1.0, stack=()):
    ks = jax.random.split(key, 3)
    s = tuple(stack)
    if act == "gelu_mlp":
        return {
            "w_in": dense_init(ks[0], s + (d_model, d_ff), d_model, dtype),
            "w_out": dense_init(ks[1], s + (d_ff, d_model), d_ff, dtype, n_layers_scale),
        }
    return {
        "w_gate": dense_init(ks[0], s + (d_model, d_ff), d_model, dtype),
        "w_up": dense_init(ks[1], s + (d_model, d_ff), d_model, dtype),
        "w_down": dense_init(ks[2], s + (d_ff, d_model), d_ff, dtype, n_layers_scale),
    }
