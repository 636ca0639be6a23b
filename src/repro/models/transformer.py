"""Decoder-only transformer (dense / MoE / VLM families).

Provides init plus the four step flavors the system needs:
  - train_hidden / train_logits       (full-sequence causal)
  - prefill                           (batched prompt -> KV + last logits)
  - decode                            (one token/seq over paged KV)
  - mixed                             (Splitwiser: prefill chunks + decode
                                       tokens fused in ONE program, sharing
                                       every GEMM)

All functions are pure and `jax.eval_shape`-able (the multi-pod dry-run
lowers them from ShapeDtypeStructs without allocating).

GQA/TP head padding: when kv heads don't divide the tensor-parallel axis,
q/kv heads are padded *at apply time* (and in the wq/wo storage layout)
while wk/wv keep the real architecture's parameters; padded q heads are
masked before the output projection so they are exactly inert (zero
gradient, zero contribution). See `gqa_layout`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers
from repro.models.layers import (
    NEG_INF, dense_init, flash_attention, flash_attention_ckpt,
    head_rms_norm, mlp_apply, mlp_init, paged_attention_ref, rms_norm, rope,
    softcap, act_fn,
)
from repro.models.moe import moe_apply, moe_init
from repro.models.sharding import constrain

VOCAB_PAD = 256
NO_WINDOW = 2**30


def pad_vocab(v: int) -> int:
    return ((v + VOCAB_PAD - 1) // VOCAB_PAD) * VOCAB_PAD


# ------------------------------------------------------------ GQA layout ---
def gqa_layout(H: int, KV: int, tp: int = 1):
    """Padded head layout for tensor parallelism.

    Returns (H_p, KV_p, q_map, kv_map, head_mask):
      q_map [H_p]   -> real q head feeding padded slot (-1 = inert pad)
      kv_map [KV_p] -> real kv head replicated into padded kv slot
      head_mask [H_p] float 0/1 (applied to attention output)
    Padded groups are uniform: padded q slot j uses padded kv slot j // G_p.
    """
    if KV % tp == 0:
        KV_p = KV
    else:
        if not KV < tp:
            raise ValueError(
                f"KV heads ({KV}) must divide or be smaller than tp={tp} "
                f"to replicate into padded slots (H={H})")
        KV_p = tp * math.ceil(KV / tp)
        if KV_p % KV:
            raise ValueError(
                f"padded KV heads {KV_p} not a multiple of KV={KV} (tp={tp})")
    R = KV_p // KV
    if H % KV:
        raise ValueError(f"query heads H={H} must be a multiple of KV={KV}")
    G = H // KV
    G_p = math.ceil(G / R)
    H_p = KV_p * G_p
    q_map = np.full(H_p, -1, np.int32)
    for r in range(KV):
        for i in range(R):
            for t in range(G_p):
                src = i * G_p + t
                if src < G:
                    q_map[(r * R + i) * G_p + t] = r * G + src
    kv_map = (np.arange(KV_p) // R).astype(np.int32)
    head_mask = (q_map >= 0).astype(np.float32)
    return H_p, KV_p, q_map, kv_map, head_mask


def layer_windows(cfg) -> np.ndarray:
    """Per-layer attention window (NO_WINDOW = global) as a scan input."""
    L = cfg.n_layers
    if cfg.local_global_pattern and cfg.sliding_window:
        pat = cfg.local_global_pattern
        return np.array(
            [cfg.sliding_window if pat[i % len(pat)] == "local" else NO_WINDOW
             for i in range(L)], np.int32)
    return np.full(L, NO_WINDOW, np.int32)


# ----------------------------------------------------------------- init ----
def init_params(cfg, key, dtype=jnp.float32, tp: int = 1):
    D, hd, L = cfg.d_model, cfg.head_dim, cfg.n_layers
    H_p, KV_p, q_map, _, _ = gqa_layout(cfg.n_heads, cfg.n_kv_heads, tp)
    Vp = pad_vocab(cfg.vocab_size)
    keys = iter(jax.random.split(key, 24))
    out_scale = 1.0 / math.sqrt(2 * L)

    # wq stored in the padded layout (pad columns zero & inert); wk/wv real.
    wq = dense_init(next(keys), (L, D, H_p, hd), D, dtype)
    wq = wq * jnp.asarray(q_map >= 0, dtype)[None, None, :, None]
    wo = dense_init(next(keys), (L, H_p, hd, D), H_p * hd, dtype, out_scale)

    blocks = {
        "ln1": jnp.zeros((L, D), dtype),
        "ln2": jnp.zeros((L, D), dtype),
        "wq": wq,
        "wk": dense_init(next(keys), (L, D, cfg.n_kv_heads, hd), D, dtype),
        "wv": dense_init(next(keys), (L, D, cfg.n_kv_heads, hd), D, dtype),
        "wo": wo,
    }
    if cfg.use_qk_norm:
        blocks["q_norm"] = jnp.zeros((L, hd), dtype)
        blocks["k_norm"] = jnp.zeros((L, hd), dtype)
    if cfg.post_attn_norm:
        blocks["ln1b"] = jnp.zeros((L, D), dtype)
        blocks["ln2b"] = jnp.zeros((L, D), dtype)
    if cfg.is_moe:
        blocks["moe"] = moe_init(next(keys), cfg, dtype, stack=(L,))
    else:
        blocks["mlp"] = mlp_init(next(keys), D, cfg.d_ff, cfg.mlp_act, dtype,
                                 out_scale, stack=(L,))
    params = {
        "embed": (jax.random.normal(next(keys), (Vp, D), jnp.float32) * 0.02).astype(dtype),
        "ln_f": jnp.zeros((D,), dtype),
        "blocks": blocks,
    }
    if not cfg.tie_embeddings:
        params["head"] = (jax.random.normal(next(keys), (Vp, D), jnp.float32) * 0.02).astype(dtype)
    if cfg.family == "vlm":
        params["proj"] = {
            "ln": jnp.zeros((cfg.d_vision,), dtype),
            "w1": dense_init(next(keys), (cfg.d_vision, D), cfg.d_vision, dtype),
            "w2": dense_init(next(keys), (D, D), D, dtype),
        }
    return params


# ------------------------------------------------------------- embedding ---
def embed(params, cfg, tokens, policy=None):
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.emb_scale_by_sqrt_dim:
        x = x * math.sqrt(cfg.d_model)
    return x


def vision_prefix(params, cfg, patches):
    """[B, Np, d_vision] precomputed patch embeds -> [B, Np, D] prefix."""
    p = params["proj"]
    h = rms_norm(patches, p["ln"], cfg.norm_eps)
    h = jax.nn.gelu(jnp.einsum("bnd,dD->bnD", h, p["w1"]), approximate=True)
    return jnp.einsum("bnd,dD->bnD", h, p["w2"])


def unembed(params, cfg, x, policy=None):
    table = params["head"] if "head" in params else params["embed"]
    logits = jnp.einsum("...d,vd->...v", x, table, preferred_element_type=jnp.float32)
    if cfg.final_logit_softcap:
        logits = softcap(logits, cfg.final_logit_softcap)
    Vp = table.shape[0]
    if Vp != cfg.vocab_size:
        vmask = jnp.arange(Vp) < cfg.vocab_size
        logits = jnp.where(vmask, logits, NEG_INF)
    return logits


# ----------------------------------------------------------- block pieces --
def _qkv(cfg, lay, lp, x):
    """x [..., D] -> q [..., H_p, hd] (padded layout), k/v [..., KV, hd]."""
    q = jnp.einsum("...d,dhk->...hk", x, lp["wq"])
    k = jnp.einsum("...d,dhk->...hk", x, lp["wk"])
    v = jnp.einsum("...d,dhk->...hk", x, lp["wv"])
    if cfg.use_qk_norm:
        q = head_rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = head_rms_norm(k, lp["k_norm"], cfg.norm_eps)
    return q, k, v


def _expand_kv(t, kv_map, policy=None, names=()):
    if len(kv_map) == t.shape[-2] and bool(np.all(kv_map == np.arange(len(kv_map)))):
        out = t                                   # identity (no TP padding)
    else:
        out = jnp.take(t, jnp.asarray(kv_map), axis=-2)
    if policy is not None and names:
        out = constrain(out, policy, *names)
    return out


def _attn_scale(cfg):
    return cfg.attn_scale_override or (1.0 / math.sqrt(cfg.head_dim))


def _o_proj(cfg, lp, o, head_mask):
    o = o * jnp.asarray(head_mask, o.dtype)[..., :, None]
    return jnp.einsum("...hk,hkd->...d", o, lp["wo"])


def _ffn(cfg, lp, x2d, moe_fn):
    """x2d [T, D] -> (y2d, aux)."""
    if cfg.is_moe:
        return moe_fn(lp["moe"], x2d)
    return mlp_apply(lp["mlp"], x2d, cfg.mlp_act), jnp.float32(0.0)


def default_moe_fn(cfg):
    gate_act = act_fn("silu" if cfg.mlp_act == "silu" else "gelu")
    def fn(lp, x2d):
        return moe_apply(lp, x2d, n_experts=cfg.n_experts, top_k=cfg.top_k,
                         act=gate_act,
                         capacity_factor=cfg.moe_capacity_factor)
    return fn


# ------------------------------------------------------- full-seq forward --
def _seq_block(cfg, lay, lp, window, x, positions, *, policy, moe_fn,
               collect_kv=False, kv_fake_quant=None):
    """One layer on a full sequence. x [B, T, D]; positions [B, T].

    kv_fake_quant: optional quantize-dequantize applied to K/V at the
    ATTENTION input only (collected KV stays fp, commit re-quantizes to
    the identical codes — q8 is idempotent).  The int8 serving path uses
    it so monolithic prefill attends to exactly the values the chunked
    paths re-read from quantized pages; see ``kernels/kv_int8``.
    """
    H_p, KV_p, _, kv_map, head_mask = lay
    B, T, D = x.shape
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    q, k, v = _qkv(cfg, lay, lp, h)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    ke = _expand_kv(k, kv_map, policy, ("batch", "seq", "kv_heads", None))
    ve = _expand_kv(v, kv_map, policy, ("batch", "seq", "kv_heads", None))
    ka = ke if kv_fake_quant is None else kv_fake_quant(ke)
    va = ve if kv_fake_quant is None else kv_fake_quant(ve)
    # custom recompute-based backward (kernel-style; §Perf)
    o = flash_attention_ckpt(
        q, ka, va, positions, positions, None,
        scale=_attn_scale(cfg), causal=True, window=window,
        attn_softcap=cfg.attn_logit_softcap)
    attn_out = _o_proj(cfg, lp, o, head_mask)
    if cfg.post_attn_norm:
        attn_out = rms_norm(attn_out, lp["ln1b"], cfg.norm_eps)
    x = x + attn_out
    h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
    y2d, aux = _ffn(cfg, lp, h2.reshape(B * T, D), moe_fn)
    y = y2d.reshape(B, T, D)
    if cfg.post_attn_norm:
        y = rms_norm(y, lp["ln2b"], cfg.norm_eps)
    x = x + y
    if policy is not None:
        x = constrain(x, policy, "batch", "seq", None)
    kv_out = (ke, ve) if collect_kv else None
    return x, aux, kv_out


def forward_hidden(params, cfg, x, positions, *, tp=1, policy=None,
                   moe_fn=None, remat=False, collect_kv=False,
                   kv_fake_quant=None):
    """Scan the layer stack. Returns (hidden [B,T,D], aux, kv or None)."""
    lay = gqa_layout(cfg.n_heads, cfg.n_kv_heads, tp)
    moe_fn = moe_fn or (default_moe_fn(cfg) if cfg.is_moe else None)
    windows = jnp.asarray(layer_windows(cfg))

    def body(carry, xs):
        xc, aux = carry
        lp, win = xs
        xc, a, kv = _seq_block(cfg, lay, lp, win, xc, positions,
                               policy=policy, moe_fn=moe_fn,
                               collect_kv=collect_kv,
                               kv_fake_quant=kv_fake_quant)
        return (xc, aux + a), kv

    if remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    (x, aux), kv = jax.lax.scan(body, (x, jnp.float32(0.0)),
                                (params["blocks"], windows))
    return rms_norm(x, params["ln_f"], cfg.norm_eps), aux, kv


def train_hidden(params, cfg, batch, *, tp=1, policy=None, moe_fn=None,
                 remat=False):
    """batch: tokens [B,T] (+ patches for vlm). Returns (hidden, aux)."""
    tokens = batch["tokens"]
    x = embed(params, cfg, tokens, policy)
    if cfg.family == "vlm":
        x = jnp.concatenate([vision_prefix(params, cfg, batch["patches"]), x], axis=1)
    B, T, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    if policy is not None:
        x = constrain(x, policy, "batch", "seq", None)
    hidden, aux, _ = forward_hidden(params, cfg, x, positions, tp=tp,
                                    policy=policy, moe_fn=moe_fn, remat=remat)
    return hidden, aux


def train_logits(params, cfg, batch, **kw):
    hidden, aux = train_hidden(params, cfg, batch, **kw)
    return unembed(params, cfg, hidden), aux


# ----------------------------------------------------------------- prefill -
def prefill(params, cfg, tokens, *, patches=None, tp=1, policy=None,
            moe_fn=None, start_pos=0, kv_fake_quant=None):
    """Full-prompt prefill. tokens [B, S].

    Returns (last_logits [B, Vp], (k, v) each [L, B, S_tot, KV_p, hd]).
    """
    x = embed(params, cfg, tokens, policy)
    if cfg.family == "vlm" and patches is not None:
        x = jnp.concatenate([vision_prefix(params, cfg, patches), x], axis=1)
    B, S, _ = x.shape
    positions = start_pos + jnp.broadcast_to(
        jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    if policy is not None:
        x = constrain(x, policy, "batch", "seq", None)
    hidden, aux, kv = forward_hidden(params, cfg, x, positions, tp=tp,
                                     policy=policy, moe_fn=moe_fn,
                                     collect_kv=True,
                                     kv_fake_quant=kv_fake_quant)
    last = hidden[:, -1]
    return unembed(params, cfg, last, policy), kv


# ------------------------------------------------------------------ decode -
def write_kv_token(kpg, vpg, layer, k, v, block_table, seq_lens, active):
    """Scatter one new token per sequence into layer ``layer`` of the pool.

    kpg/vpg [L, N, ps, KV_p * hd] (``init_pages``); k/v [B, KV_p, hd],
    stored as one row per token. The last page of the pool is the trash
    page for inactive slots (never allocated by the engine). One scatter
    per pool, in place when the pool is donated or carried.
    """
    with jax.named_scope("kv_write"):
        ps = kpg.shape[2]
        pidx = jnp.take_along_axis(block_table, (seq_lens // ps)[:, None],
                                   1)[:, 0]
        off = seq_lens % ps
        pidx = jnp.where(active, pidx, kpg.shape[1] - 1)
        row = (pidx.shape[0], kpg.shape[-1])
        return (kpg.at[layer, pidx, off].set(k.reshape(row)),
                vpg.at[layer, pidx, off].set(v.reshape(row)))


# Pluggable paged write+attend steps. The WRITE lives inside the pluggable
# fn so the production path can run it in a shard_map island (GSPMD cannot
# partition data-dependent page scatters/gathers; see launch/spmd.py).
# Contract, for every implementation: the fn takes the WHOLE pools
# kpg/vpg [L, N, ps, KV_p * hd] (``init_pages``; pytrees of such leaves
# in kernels/kv_int8.py) and the scan's layer index, writes the new K/V at
# [layer, page, offset], attends over that layer's pages (the reference
# through one gather indexed by (layer, block_table), the Pallas decode
# kernel in kernels/ops.py by reading each live page in place), and
# returns the whole pools.
# The layer scans carry the pools, so the writes stay in place and no
# per-layer slab is sliced or restacked.
def default_decode_attn(q, k_new, v_new, kpg, vpg, layer, block_table,
                        seq_lens, active, *, scale, window, attn_softcap):
    """q [B,1,H_p,hd]; k_new/v_new [B,KV_p,hd]. Returns (o, kpg, vpg)."""
    kpg, vpg = write_kv_token(kpg, vpg, layer, k_new, v_new, block_table,
                              seq_lens, active)
    o = paged_attention_ref(q, kpg, vpg, block_table, seq_lens + 1,
                            seq_lens[:, None], scale=scale, layer=layer,
                            window=window, attn_softcap=attn_softcap)
    return o, kpg, vpg


def default_chunk_attn(q, k_new, v_new, kpg, vpg, layer, block_table, start,
                       lens, *, scale, window, attn_softcap):
    """q [P,C,H_p,hd]; k_new/v_new [P,C,KV_p,hd]. Returns (o, kpg, vpg)."""
    kpg, vpg = write_kv_chunk(kpg, vpg, layer, k_new, v_new, block_table,
                              start, lens)
    C = q.shape[1]
    q_pos = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
    o = paged_attention_ref(q, kpg, vpg, block_table, start + lens, q_pos,
                            scale=scale, layer=layer, window=window,
                            attn_softcap=attn_softcap)
    return o, kpg, vpg


def decode(params, cfg, tokens, k_pages, v_pages, block_table, seq_lens, *,
           active=None, attn_fn=None, tp=1, policy=None, moe_fn=None):
    """One decode step. tokens [B]; pages [L, N, ps, KV_p * hd].

    attn_fn: a `default_decode_attn`-shaped write+attend step.
    Returns (logits [B, Vp], (k_pages, v_pages)).
    """
    lay = gqa_layout(cfg.n_heads, cfg.n_kv_heads, tp)
    H_p, KV_p, _, kv_map, head_mask = lay
    attn_fn = attn_fn or default_decode_attn
    moe_fn = moe_fn or (default_moe_fn(cfg) if cfg.is_moe else None)
    windows = jnp.asarray(layer_windows(cfg))

    with jax.named_scope("embed"):
        x = embed(params, cfg, tokens, policy)    # [B, D]
    if policy is not None:
        x = constrain(x, policy, "batch", None)
    B, D = x.shape
    pos = seq_lens                                 # next position == current len
    act = active if active is not None else jnp.ones((B,), bool)

    def body(carry, xs):
        xc, aux, kpg, vpg = carry
        lp, win, layer = xs
        with jax.named_scope("qkv"):
            h = rms_norm(xc, lp["ln1"], cfg.norm_eps)
            q, k, v = _qkv(cfg, lay, lp, h)        # q [B,H_p,hd]; k/v [B,KV,hd]
        with jax.named_scope("decode_attn"):
            q = rope(q[:, None], pos[:, None], cfg.rope_theta)   # [B,1,H_p,hd]
            k = rope(k[:, None], pos[:, None], cfg.rope_theta)[:, 0]
            ke = _expand_kv(k, kv_map, policy, ("batch", "kv_heads", None))
            ve = _expand_kv(v, kv_map, policy, ("batch", "kv_heads", None))
            o, kpg, vpg = attn_fn(q, ke, ve, kpg, vpg, layer, block_table,
                                  seq_lens, act, scale=_attn_scale(cfg),
                                  window=win,
                                  attn_softcap=cfg.attn_logit_softcap)
        with jax.named_scope("o_proj"):
            attn_out = _o_proj(cfg, lp, o[:, 0], head_mask)
            if cfg.post_attn_norm:
                attn_out = rms_norm(attn_out, lp["ln1b"], cfg.norm_eps)
            xc = xc + attn_out
        with jax.named_scope("ffn"):
            h2 = rms_norm(xc, lp["ln2"], cfg.norm_eps)
            y, a = _ffn(cfg, lp, h2, moe_fn)
            if cfg.post_attn_norm:
                y = rms_norm(y, lp["ln2b"], cfg.norm_eps)
            xc = xc + y
        if policy is not None:
            xc = constrain(xc, policy, "batch", None)
        return (xc, aux + a, kpg, vpg), None

    (x, _, k_pages, v_pages), _ = jax.lax.scan(
        body, (x, jnp.float32(0.0), k_pages, v_pages),
        (params["blocks"], windows, jnp.arange(len(windows), dtype=jnp.int32)))
    with jax.named_scope("logits"):
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        return unembed(params, cfg, x, policy), (k_pages, v_pages)


# ------------------------------------------------------------------- mixed -
def write_kv_chunk(kpg, vpg, layer, k, v, block_table, start, lens):
    """Scatter a prefill chunk into layer ``layer`` of the pool.

    kpg [L, N, ps, KV_p * hd]; k/v [P, C, KV_p, hd]; block_table [P, Pmax];
    start [P] first position of chunk; lens [P] valid tokens (rest->trash).
    """
    with jax.named_scope("kv_write"):
        P, C = k.shape[:2]
        ps = kpg.shape[2]
        j = jnp.arange(C, dtype=jnp.int32)[None]                   # [1, C]
        gpos = start[:, None] + j                                  # [P, C]
        page_slot = gpos // ps
        pidx = jnp.take_along_axis(block_table, page_slot, axis=1) # [P, C]
        off = gpos % ps
        trash = kpg.shape[1] - 1
        valid = j < lens[:, None]
        pidx = jnp.where(valid, pidx, trash)
        pidx, off = pidx.reshape(P * C), off.reshape(P * C)
        row = (P * C, kpg.shape[-1])
        kpg = kpg.at[layer, pidx, off].set(k.reshape(row))
        vpg = vpg.at[layer, pidx, off].set(v.reshape(row))
        return kpg, vpg


def mixed(params, cfg, mb, k_pages, v_pages, *, attn_fn=None, tp=1,
          policy=None, moe_fn=None):
    """Splitwiser fused step: prefill chunks + decode tokens in ONE program.

    mb keys:
      p_tokens [P, C] int32   prefill chunk tokens (pad id 0 beyond p_lens)
      p_table  [P, Pmax]      page table rows for chunk sequences
      p_start  [P]            chunk start position (= history length)
      p_lens   [P]            valid tokens in chunk
      d_tokens [B]            decode tokens
      d_table  [B, Pmax]
      d_lens   [B]            current kv lens (before this step)
      d_active [B] bool

    Every GEMM (QKV/O/FFN/MoE/unembed) runs on the union of prefill and
    decode tokens — the paper's "both phases share the device" realized as
    one fused XLA program. Attention splits by phase.

    Returns (p_logits [P, Vp] at each chunk's last valid token,
             d_logits [B, Vp], (k_pages, v_pages), aux).
    """
    lay = gqa_layout(cfg.n_heads, cfg.n_kv_heads, tp)
    H_p, KV_p, _, kv_map, head_mask = lay
    decode_attn = (attn_fn or {}).get("decode") if isinstance(attn_fn, dict) else None
    chunk_attn = (attn_fn or {}).get("chunk") if isinstance(attn_fn, dict) else None
    decode_attn = decode_attn or default_decode_attn
    chunk_attn = chunk_attn or default_chunk_attn
    moe_fn = moe_fn or (default_moe_fn(cfg) if cfg.is_moe else None)
    windows = jnp.asarray(layer_windows(cfg))

    P, C = mb["p_tokens"].shape
    B = mb["d_tokens"].shape[0]
    D = cfg.d_model

    with jax.named_scope("embed"):
        xp = embed(params, cfg, mb["p_tokens"], policy)        # [P, C, D]
        xd = embed(params, cfg, mb["d_tokens"], policy)        # [B, D]
        x = jnp.concatenate([xp.reshape(P * C, D), xd], axis=0)  # [P*C+B, D]
    if policy is not None:
        x = constrain(x, policy, "tokens", None)

    jC = jnp.arange(C, dtype=jnp.int32)[None]
    p_pos = mb["p_start"][:, None] + jC                        # [P, C]
    d_pos = mb["d_lens"]

    def body(carry, xs):
        xc, aux, kpg, vpg = carry
        lp, win, layer = xs
        with jax.named_scope("qkv"):
            h = rms_norm(xc, lp["ln1"], cfg.norm_eps)
            q, k, v = _qkv(cfg, lay, lp, h)                    # the shared GEMM
        qp, qd = q[: P * C].reshape(P, C, H_p, -1), q[P * C :][:, None]
        kp, kd = k[: P * C].reshape(P, C, cfg.n_kv_heads, -1), k[P * C :]
        vp, vd = v[: P * C].reshape(P, C, cfg.n_kv_heads, -1), v[P * C :]

        # --- prefill-phase attention (write chunk KV + attend history) ---
        with jax.named_scope("chunk_attn"):
            qp = rope(qp, p_pos, cfg.rope_theta)
            kp = rope(kp, p_pos, cfg.rope_theta)
            kpe = _expand_kv(kp, kv_map)
            vpe = _expand_kv(vp, kv_map)
            o_p, kpg, vpg = chunk_attn(qp, kpe, vpe, kpg, vpg, layer,
                                       mb["p_table"], mb["p_start"],
                                       mb["p_lens"],
                                       scale=_attn_scale(cfg), window=win,
                                       attn_softcap=cfg.attn_logit_softcap)

        # --- decode-phase attention ---
        with jax.named_scope("decode_attn"):
            qd = rope(qd, d_pos[:, None], cfg.rope_theta)
            kd = rope(kd[:, None], d_pos[:, None], cfg.rope_theta)[:, 0]
            kde = _expand_kv(kd, kv_map)
            vde = _expand_kv(vd, kv_map)
            o_d, kpg, vpg = decode_attn(qd, kde, vde, kpg, vpg, layer,
                                        mb["d_table"], mb["d_lens"],
                                        mb["d_active"],
                                        scale=_attn_scale(cfg), window=win,
                                        attn_softcap=cfg.attn_logit_softcap)

        with jax.named_scope("o_proj"):
            o = jnp.concatenate([o_p.reshape(P * C, H_p, -1), o_d[:, 0]],
                                axis=0)
            attn_out = _o_proj(cfg, lp, o, head_mask)          # shared GEMM
            if cfg.post_attn_norm:
                attn_out = rms_norm(attn_out, lp["ln1b"], cfg.norm_eps)
            xc = xc + attn_out
        with jax.named_scope("ffn"):
            h2 = rms_norm(xc, lp["ln2"], cfg.norm_eps)
            y, a = _ffn(cfg, lp, h2, moe_fn)                   # shared GEMM
            if cfg.post_attn_norm:
                y = rms_norm(y, lp["ln2b"], cfg.norm_eps)
            xc = xc + y
        if policy is not None:
            xc = constrain(xc, policy, "tokens", None)
        return (xc, aux + a, kpg, vpg), None

    (x, aux, k_pages, v_pages), _ = jax.lax.scan(
        body, (x, jnp.float32(0.0), k_pages, v_pages),
        (params["blocks"], windows, jnp.arange(len(windows), dtype=jnp.int32)))
    with jax.named_scope("logits"):
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        xp = x[: P * C].reshape(P, C, D)
        last_idx = jnp.clip(mb["p_lens"] - 1, 0, C - 1)
        xp_last = xp[jnp.arange(P), last_idx]                  # [P, D]
        p_logits = unembed(params, cfg, xp_last, policy)
        d_logits = unembed(params, cfg, x[P * C :], policy)
    return p_logits, d_logits, (k_pages, v_pages), aux


# -------------------------------------------------------------- page utils -
def init_pages(cfg, n_pages, page_size, tp=1, dtype=jnp.float32,
               n_layers=None):
    """Zeroed pools [L, N, ps, KV_p * hd]: a token's heads in one row.

    The row is flat so that a TPU tiles a page's (ps, KV_p * hd) without
    padding whenever KV_p * hd is a multiple of 128, whatever hd is. With
    (KV_p, hd) as the minor dims and hd 64 (opt-125m), the v5e compiler
    lays the pool out with the page dim innermost, and the layer scan
    copies the whole pool into another layout for its writes and gathers.
    """
    _, KV_p, _, _, _ = gqa_layout(cfg.n_heads, cfg.n_kv_heads, tp)
    L = n_layers if n_layers is not None else cfg.n_layers
    shape = (L, n_pages, page_size, KV_p * cfg.head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def kv_to_pages(kv, page_size):
    """Prefill output [L, B, S, KV_p, hd] -> pages [L, B*S/ps, ps, KV_p * hd]."""
    L, B, S, KVp, hd = kv.shape
    if S % page_size:
        raise ValueError(
            f"prefill length S={S} must be page-aligned (page_size="
            f"{page_size}); callers pad the token batch to whole pages")
    return kv.reshape(L, B * (S // page_size), page_size, KVp * hd)
