"""Seamless-M4T-style encoder-decoder backbone.

The speech frontend is a stub per the assignment: the encoder consumes
precomputed frame embeddings [B, S_enc, D]. Phases for the serving engine:
"prefill" = encoder pass + cross-KV build + decoder prompt prefill;
"decode" = decoder token steps (paged self-KV + fixed cross-KV).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import (
    dense_init, flash_attention, mlp_apply, mlp_init, rms_norm, rope,
)
from repro.models.sharding import constrain
from repro.models.transformer import (
    default_decode_attn, gqa_layout, pad_vocab, unembed,
)


def _attn_params(key, cfg, D_in, lay, dtype, out_scale):
    H_p, KV_p, q_map, _, _ = lay
    hd = cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    wq = dense_init(k1, (D_in, H_p, hd), D_in, dtype)
    wq = wq * jnp.asarray(q_map >= 0, dtype)[None, :, None]
    return {
        "wq": wq,
        "wk": dense_init(k2, (D_in, cfg.n_kv_heads, hd), D_in, dtype),
        "wv": dense_init(k3, (D_in, cfg.n_kv_heads, hd), D_in, dtype),
        "wo": dense_init(k4, (H_p, hd, cfg.d_model), H_p * hd, dtype, out_scale),
    }


def init_params(cfg, key, dtype=jnp.float32, tp: int = 1):
    D = cfg.d_model
    lay = gqa_layout(cfg.n_heads, cfg.n_kv_heads, tp)
    Vp = pad_vocab(cfg.vocab_size)
    Le, Ld = cfg.n_encoder_layers, cfg.n_layers
    ks = iter(jax.random.split(key, 12))
    s_enc = 1.0 / math.sqrt(2 * Le)
    s_dec = 1.0 / math.sqrt(2 * Ld)

    def stack_attn(key, L, scale):
        keys = jax.random.split(key, L)
        ps = [_attn_params(k, cfg, D, lay, dtype, scale) for k in keys]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *ps)

    def stack_mlp(key, L, scale):
        keys = jax.random.split(key, L)
        ps = [mlp_init(k, D, cfg.d_ff, cfg.mlp_act, dtype, scale) for k in keys]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *ps)

    enc_blocks = {
        "ln1": jnp.zeros((Le, D), dtype),
        "attn": stack_attn(next(ks), Le, s_enc),
        "ln2": jnp.zeros((Le, D), dtype),
        "mlp": stack_mlp(next(ks), Le, s_enc),
    }
    dec_blocks = {
        "ln1": jnp.zeros((Ld, D), dtype),
        "self": stack_attn(next(ks), Ld, s_dec),
        "lnx": jnp.zeros((Ld, D), dtype),
        "cross": stack_attn(next(ks), Ld, s_dec),
        "ln2": jnp.zeros((Ld, D), dtype),
        "mlp": stack_mlp(next(ks), Ld, s_dec),
    }
    return {
        "embed": (jax.random.normal(next(ks), (Vp, D), jnp.float32) * 0.02).astype(dtype),
        "enc_blocks": enc_blocks,
        "enc_ln_f": jnp.zeros((D,), dtype),
        "dec_blocks": dec_blocks,
        "ln_f": jnp.zeros((D,), dtype),
    }


def _mha(cfg, lay, ap, xq, xkv, q_pos, kv_pos, *, causal, kv_valid_len=None):
    H_p, KV_p, _, kv_map, head_mask = lay
    q = jnp.einsum("btd,dhk->bthk", xq, ap["wq"])
    k = jnp.einsum("bsd,dhk->bshk", xkv, ap["wk"])
    v = jnp.einsum("bsd,dhk->bshk", xkv, ap["wv"])
    q = rope(q, q_pos, cfg.rope_theta)
    k = rope(k, kv_pos, cfg.rope_theta)
    ke = jnp.take(k, jnp.asarray(kv_map), axis=-2)
    ve = jnp.take(v, jnp.asarray(kv_map), axis=-2)
    o = flash_attention(q, ke, ve, q_positions=q_pos, kv_positions=kv_pos,
                        kv_valid_len=kv_valid_len,
                        scale=1.0 / math.sqrt(cfg.head_dim), causal=causal)
    o = o * jnp.asarray(head_mask, o.dtype)[:, None]
    return jnp.einsum("bthk,hkd->btd", o, ap["wo"])


def encode(params, cfg, frames, *, policy=None, enc_valid_len=None):
    """frames [B, S_enc, D] (stub frontend output) -> [B, S_enc, D]."""
    lay = gqa_layout(cfg.n_heads, cfg.n_kv_heads, 1)
    B, S, D = frames.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = frames
    if policy is not None:
        x = constrain(x, policy, "batch", "seq", None)

    def body(xc, lp):
        h = rms_norm(xc, lp["ln1"], cfg.norm_eps)
        xc = xc + _mha(cfg, lay, lp["attn"], h, h, pos, pos, causal=False,
                       kv_valid_len=enc_valid_len)
        h2 = rms_norm(xc, lp["ln2"], cfg.norm_eps)
        xc = xc + mlp_apply(lp["mlp"], h2, cfg.mlp_act)
        if policy is not None:
            xc = constrain(xc, policy, "batch", "seq", None)
        return xc, None

    x, _ = jax.lax.scan(body, x, params["enc_blocks"])
    return rms_norm(x, params["enc_ln_f"], cfg.norm_eps)


def build_cross_kv(params, cfg, enc_out, tp=1):
    """Per-decoder-layer cross K/V from encoder output.

    Returns (xk, xv) [Ld, B, S_enc, KV_p, hd] (positions not roped —
    cross attention uses raw keys; rope is self-attn only here).
    """
    lay = gqa_layout(cfg.n_heads, cfg.n_kv_heads, tp)
    _, KV_p, _, kv_map, _ = lay

    def body(_, ap):
        k = jnp.einsum("bsd,dhk->bshk", enc_out, ap["wk"])
        v = jnp.einsum("bsd,dhk->bshk", enc_out, ap["wv"])
        ke = jnp.take(k, jnp.asarray(kv_map), axis=-2)
        ve = jnp.take(v, jnp.asarray(kv_map), axis=-2)
        return None, (ke, ve)

    _, (xk, xv) = jax.lax.scan(body, None, params["dec_blocks"]["cross"])
    return xk, xv


def _decoder_seq(params, cfg, tokens, enc_out, *, tp=1, policy=None,
                 collect_kv=False, enc_valid_len=None, start_pos=0):
    lay = gqa_layout(cfg.n_heads, cfg.n_kv_heads, tp)
    H_p, KV_p, _, kv_map, head_mask = lay
    x = jnp.take(params["embed"], tokens, axis=0)
    B, T, D = x.shape
    pos = start_pos + jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    S = enc_out.shape[1]
    enc_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    if policy is not None:
        x = constrain(x, policy, "batch", "seq", None)

    def body(xc, lp):
        h = rms_norm(xc, lp["ln1"], cfg.norm_eps)
        # self attention (causal, roped) — collect expanded k/v for cache
        q = jnp.einsum("btd,dhk->bthk", h, lp["self"]["wq"])
        k = jnp.einsum("btd,dhk->bthk", h, lp["self"]["wk"])
        v = jnp.einsum("btd,dhk->bthk", h, lp["self"]["wv"])
        q = rope(q, pos, cfg.rope_theta)
        k = rope(k, pos, cfg.rope_theta)
        ke = jnp.take(k, jnp.asarray(kv_map), axis=-2)
        ve = jnp.take(v, jnp.asarray(kv_map), axis=-2)
        o = flash_attention(q, ke, ve, q_positions=pos, kv_positions=pos,
                            scale=1.0 / math.sqrt(cfg.head_dim), causal=True)
        o = o * jnp.asarray(head_mask, o.dtype)[:, None]
        xc = xc + jnp.einsum("bthk,hkd->btd", o, lp["self"]["wo"])
        # cross attention (non-causal over encoder output, un-roped)
        hx = rms_norm(xc, lp["lnx"], cfg.norm_eps)
        qx = jnp.einsum("btd,dhk->bthk", hx, lp["cross"]["wq"])
        kx = jnp.einsum("bsd,dhk->bshk", enc_out, lp["cross"]["wk"])
        vx = jnp.einsum("bsd,dhk->bshk", enc_out, lp["cross"]["wv"])
        kxe = jnp.take(kx, jnp.asarray(kv_map), axis=-2)
        vxe = jnp.take(vx, jnp.asarray(kv_map), axis=-2)
        ox = flash_attention(qx, kxe, vxe, q_positions=pos, kv_positions=enc_pos,
                             kv_valid_len=enc_valid_len,
                             scale=1.0 / math.sqrt(cfg.head_dim), causal=False)
        ox = ox * jnp.asarray(head_mask, ox.dtype)[:, None]
        xc = xc + jnp.einsum("bthk,hkd->btd", ox, lp["cross"]["wo"])
        h2 = rms_norm(xc, lp["ln2"], cfg.norm_eps)
        xc = xc + mlp_apply(lp["mlp"], h2, cfg.mlp_act)
        if policy is not None:
            xc = constrain(xc, policy, "batch", "seq", None)
        return xc, (ke, ve) if collect_kv else None

    x, kv = jax.lax.scan(body, x, params["dec_blocks"])
    return rms_norm(x, params["ln_f"], cfg.norm_eps), kv


def train_logits(params, cfg, batch, *, tp=1, policy=None, moe_fn=None,
                 remat=False):
    """batch: frames [B, S_enc, D], tokens [B, T_dec]."""
    del moe_fn, remat
    enc_out = encode(params, cfg, batch["frames"], policy=policy)
    hidden, _ = _decoder_seq(params, cfg, batch["tokens"], enc_out, tp=tp,
                             policy=policy)
    return unembed(params, cfg, hidden, policy), jnp.float32(0.0)


def prefill(params, cfg, frames, tokens, *, tp=1, policy=None):
    """Encoder pass + decoder prompt prefill.

    Returns (last_logits, (k, v) self-KV [Ld,B,T,KV_p,hd],
             (xk, xv) cross-KV [Ld,B,S_enc,KV_p,hd]).
    """
    enc_out = encode(params, cfg, frames, policy=policy)
    hidden, kv = _decoder_seq(params, cfg, tokens, enc_out, tp=tp,
                              policy=policy, collect_kv=True)
    cross = build_cross_kv(params, cfg, enc_out, tp=tp)
    return unembed(params, cfg, hidden[:, -1], policy), kv, cross


def decode(params, cfg, tokens, k_pages, v_pages, cross_k, cross_v,
           block_table, seq_lens, *, active=None, attn_fn=None, tp=1,
           policy=None, enc_valid_len=None):
    """One decoder token step.

    tokens [B]; pages [Ld, N, ps, KV_p * hd];
    cross_k/v [Ld, B, S_enc, KV_p, hd].
    Returns (logits, (k_pages, v_pages)).
    """
    lay = gqa_layout(cfg.n_heads, cfg.n_kv_heads, tp)
    H_p, KV_p, _, kv_map, head_mask = lay
    attn_fn = attn_fn or default_decode_attn
    act = active if active is not None else jnp.ones((tokens.shape[0],), bool)
    x = jnp.take(params["embed"], tokens, axis=0)
    if policy is not None:
        x = constrain(x, policy, "batch", None)
    B = x.shape[0]
    pos = seq_lens
    S = cross_k.shape[2]
    enc_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    def body(carry, xs):
        xc, kpg, vpg = carry
        lp, xk, xv, layer = xs
        h = rms_norm(xc, lp["ln1"], cfg.norm_eps)
        q = jnp.einsum("bd,dhk->bhk", h, lp["self"]["wq"])
        k = jnp.einsum("bd,dhk->bhk", h, lp["self"]["wk"])
        v = jnp.einsum("bd,dhk->bhk", h, lp["self"]["wv"])
        q = rope(q[:, None], pos[:, None], cfg.rope_theta)
        k = rope(k[:, None], pos[:, None], cfg.rope_theta)[:, 0]
        ke = jnp.take(k, jnp.asarray(kv_map), axis=-2)
        ve = jnp.take(v, jnp.asarray(kv_map), axis=-2)
        o, kpg, vpg = attn_fn(q, ke, ve, kpg, vpg, layer, block_table,
                              seq_lens, act,
                              scale=1.0 / math.sqrt(cfg.head_dim), window=None,
                              attn_softcap=None)
        o = o[:, 0] * jnp.asarray(head_mask, o.dtype)[:, None]
        xc = xc + jnp.einsum("bhk,hkd->bd", o, lp["self"]["wo"])
        hx = rms_norm(xc, lp["lnx"], cfg.norm_eps)
        qx = jnp.einsum("bd,dhk->bhk", hx, lp["cross"]["wq"])[:, None]
        ox = flash_attention(qx, xk, xv, q_positions=pos[:, None],
                             kv_positions=enc_pos, kv_valid_len=enc_valid_len,
                             scale=1.0 / math.sqrt(cfg.head_dim), causal=False)
        ox = ox[:, 0] * jnp.asarray(head_mask, ox.dtype)[:, None]
        xc = xc + jnp.einsum("bhk,hkd->bd", ox, lp["cross"]["wo"])
        h2 = rms_norm(xc, lp["ln2"], cfg.norm_eps)
        xc = xc + mlp_apply(lp["mlp"], h2, cfg.mlp_act)
        if policy is not None:
            xc = constrain(xc, policy, "batch", None)
        return (xc, kpg, vpg), None

    (x, k_pages, v_pages), _ = jax.lax.scan(
        body, (x, k_pages, v_pages),
        (params["dec_blocks"], cross_k, cross_v,
         jnp.arange(cross_k.shape[0], dtype=jnp.int32)))
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    return unembed(params, cfg, x, policy), (k_pages, v_pages)
