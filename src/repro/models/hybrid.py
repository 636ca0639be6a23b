"""Zamba2-style hybrid: Mamba2 backbone + ONE shared attention block
applied every 6th layer-unit with per-invocation LoRA deltas.

Layer-unit layout (cfg.n_layers = 81): 13 groups x (5 mamba + 1 shared
attn) + 3 trailing mamba = 68 mamba units + 13 attn invocations.
The shared block takes concat(hidden, initial_embedding) [2D] as input
(Zamba's re-injection of the embedding stream).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import ssm
from repro.models.layers import (
    dense_init, flash_attention, mlp_apply, mlp_init, rms_norm, rope,
)
from repro.models.sharding import constrain
from repro.models.transformer import (
    default_decode_attn, gqa_layout, pad_vocab, unembed,
)

def group_structure(cfg):
    """(n_attn, n_mamba, n_grouped, n_trailing, n_per_group).

    Every cfg.shared_attn_every-th layer-unit is the shared attn block;
    full zamba2-7b: 81 units -> 13 attn + 68 mamba (13x5 grouped + 3 trail).
    """
    n_per_group = cfg.shared_attn_every - 1
    n_attn = cfg.n_layers // cfg.shared_attn_every
    n_mamba = cfg.n_layers - n_attn
    n_grouped = n_attn * n_per_group
    n_trailing = n_mamba - n_grouped
    return n_attn, n_mamba, n_grouped, n_trailing, n_per_group


def init_params(cfg, key, dtype=jnp.float32, tp: int = 1):
    D, hd = cfg.d_model, cfg.head_dim
    H_p, KV_p, q_map, _, _ = gqa_layout(cfg.n_heads, cfg.n_kv_heads, tp)
    n_attn, n_mamba, _, _, _ = group_structure(cfg)
    Vp = pad_vocab(cfg.vocab_size)
    r = cfg.shared_attn_lora_rank
    ks = iter(jax.random.split(key, 24))

    wq = dense_init(next(ks), (2 * D, H_p, hd), 2 * D, dtype)
    wq = wq * jnp.asarray(q_map >= 0, dtype)[None, :, None]
    shared = {
        "ln1": jnp.zeros((2 * D,), dtype),
        "wq": wq,
        "wk": dense_init(next(ks), (2 * D, cfg.n_kv_heads, hd), 2 * D, dtype),
        "wv": dense_init(next(ks), (2 * D, cfg.n_kv_heads, hd), 2 * D, dtype),
        "wo": dense_init(next(ks), (H_p, hd, D), H_p * hd, dtype,
                         1.0 / math.sqrt(2 * n_attn)),
        "ln2": jnp.zeros((D,), dtype),
        "mlp": mlp_init(next(ks), D, cfg.d_ff, cfg.mlp_act, dtype,
                        1.0 / math.sqrt(2 * n_attn)),
    }
    lora = {
        "qa": dense_init(next(ks), (n_attn, 2 * D, r), 2 * D, dtype),
        "qb": jnp.zeros((n_attn, r, H_p * hd), dtype),
        "ka": dense_init(next(ks), (n_attn, 2 * D, r), 2 * D, dtype),
        "kb": jnp.zeros((n_attn, r, cfg.n_kv_heads * hd), dtype),
        "va": dense_init(next(ks), (n_attn, 2 * D, r), 2 * D, dtype),
        "vb": jnp.zeros((n_attn, r, cfg.n_kv_heads * hd), dtype),
    }
    return {
        "embed": (jax.random.normal(next(ks), (Vp, D), jnp.float32) * 0.02).astype(dtype),
        "mamba": ssm.mamba2_init(next(ks), cfg, dtype, stack=(n_mamba,)),
        "shared": shared,
        "lora": lora,
        "ln_f": jnp.zeros((D,), dtype),
    }


def _shared_qkv(cfg, shared, lora_i, h2, lay):
    """h2 [..., 2D] -> q [..., H_p, hd], k/v [..., KV, hd] with LoRA deltas."""
    H_p, KV_p, _, kv_map, _ = gqa_layout(cfg.n_heads, cfg.n_kv_heads, 1)
    hd = cfg.head_dim
    q = jnp.einsum("...d,dhk->...hk", h2, shared["wq"])
    k = jnp.einsum("...d,dhk->...hk", h2, shared["wk"])
    v = jnp.einsum("...d,dhk->...hk", h2, shared["wv"])
    dq = jnp.einsum("...d,dr,re->...e", h2, lora_i["qa"], lora_i["qb"])
    dk = jnp.einsum("...d,dr,re->...e", h2, lora_i["ka"], lora_i["kb"])
    dv = jnp.einsum("...d,dr,re->...e", h2, lora_i["va"], lora_i["vb"])
    q = q + dq.reshape(dq.shape[:-1] + (q.shape[-2], hd))
    k = k + dk.reshape(dk.shape[:-1] + (cfg.n_kv_heads, hd))
    v = v + dv.reshape(dv.shape[:-1] + (cfg.n_kv_heads, hd))
    return q, k, v


def _shared_block_seq(cfg, lay, shared, lora_i, x, x0, positions, *,
                      collect_kv=False, policy=None):
    """Full-seq shared attention block. x/x0 [B,T,D]."""
    H_p, KV_p, _, kv_map, head_mask = lay
    h2 = rms_norm(jnp.concatenate([x, x0], axis=-1), shared["ln1"], cfg.norm_eps)
    q, k, v = _shared_qkv(cfg, shared, lora_i, h2, lay)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    ke = jnp.take(k, jnp.asarray(kv_map), axis=-2)
    ve = jnp.take(v, jnp.asarray(kv_map), axis=-2)
    o = flash_attention(q, ke, ve, q_positions=positions,
                        kv_positions=positions, scale=1.0 / math.sqrt(cfg.head_dim),
                        causal=True)
    o = o * jnp.asarray(head_mask, o.dtype)[:, None]
    attn = jnp.einsum("bthk,hkd->btd", o, shared["wo"])
    x = x + attn
    y = mlp_apply(shared["mlp"], rms_norm(x, shared["ln2"], cfg.norm_eps), cfg.mlp_act)
    x = x + y
    return x, (ke, ve) if collect_kv else None


def forward_seq(params, cfg, tokens, *, tp=1, policy=None, remat=False,
                collect_kv=False, chunk=64, conv0=None, ssm0=None,
                start_pos=0):
    """Full-sequence forward (train / prefill).

    Returns (hidden [B,T,D], kv list or None, (conv_states, ssm_states)).
    """
    lay = gqa_layout(cfg.n_heads, cfg.n_kv_heads, tp)
    n_attn, n_mamba, n_grouped, n_trailing, n_per_group = group_structure(cfg)
    x = jnp.take(params["embed"], tokens, axis=0)
    if policy is not None:
        x = constrain(x, policy, "batch", "seq", None)
    x0 = x
    B, T, D = x.shape
    positions = start_pos + jnp.broadcast_to(
        jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    dt = jax.tree.leaves(params)[0].dtype
    cs_shape, ss_shape = ssm.mamba2_state_shapes(cfg, B)
    if conv0 is None:
        conv0 = {k: jnp.zeros((n_mamba,) + v, dt) for k, v in cs_shape.items()}
    ssm0 = ssm0 if ssm0 is not None else jnp.zeros((n_mamba,) + ss_shape, jnp.float32)

    group = lambda a: a[:n_grouped].reshape((n_attn, n_per_group) + a.shape[1:])
    mg = jax.tree.map(group, params["mamba"])
    cg = jax.tree.map(group, conv0)
    sg = group(ssm0)

    def mamba_scan(x, mp, c0, s0):
        def mbody(xc, xs):
            lp, c, s = xs
            xc, c2, s2 = ssm.mamba2_block(lp, cfg, xc, c, s, chunk=chunk)
            return xc, (c2, s2)
        if remat:
            mbody = jax.checkpoint(
                mbody, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        return jax.lax.scan(mbody, x, (mp, c0, s0))

    def group_body(xc, xs):
        mp, c0, s0, lora_i = xs
        xc, (c2, s2) = mamba_scan(xc, mp, c0, s0)
        xc, kv = _shared_block_seq(cfg, lay, params["shared"], lora_i, xc, x0,
                                   positions, collect_kv=collect_kv)
        if policy is not None:
            xc = constrain(xc, policy, "batch", "seq", None)
        return xc, (c2, s2, kv)

    x, (cg2, sg2, kv) = jax.lax.scan(group_body, x, (mg, cg, sg, params["lora"]))
    mt = jax.tree.map(lambda a: a[n_grouped:], params["mamba"])
    ct0 = jax.tree.map(lambda a: a[n_grouped:], conv0)
    x, (ct2, st2) = mamba_scan(x, mt, ct0, ssm0[n_grouped:])
    ungroup = lambda g, t: jnp.concatenate([g.reshape((n_grouped,) + g.shape[2:]), t], 0)
    conv_out = jax.tree.map(ungroup, cg2, ct2)
    ssm_out = ungroup(sg2, st2)
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    return x, kv, (conv_out, ssm_out)


def train_logits(params, cfg, batch, *, tp=1, policy=None, moe_fn=None,
                 remat=False):
    del moe_fn
    hidden, _, _ = forward_seq(params, cfg, batch["tokens"], tp=tp,
                               policy=policy, remat=remat)
    return unembed(params, cfg, hidden, policy), jnp.float32(0.0)


def prefill(params, cfg, tokens, *, tp=1, policy=None):
    """Returns (last_logits, (k, v) [n_attn, B, S, KV_p, hd], (conv, ssm))."""
    hidden, kv, states = forward_seq(params, cfg, tokens, tp=tp, policy=policy,
                                     collect_kv=True)
    logits = unembed(params, cfg, hidden[:, -1], policy)
    return logits, kv, states


def decode(params, cfg, tokens, conv_states, ssm_states, k_pages, v_pages,
           block_table, seq_lens, *, active=None, attn_fn=None, tp=1,
           policy=None):
    """One token step. tokens [B]; pages [n_attn, N, ps, KV_p * hd].

    Returns (logits, (conv, ssm), (k_pages, v_pages)).
    """
    lay = gqa_layout(cfg.n_heads, cfg.n_kv_heads, tp)
    H_p, KV_p, _, kv_map, head_mask = lay
    attn_fn = attn_fn or default_decode_attn
    n_attn, n_mamba, n_grouped, n_trailing, n_per_group = group_structure(cfg)
    act = active if active is not None else jnp.ones((tokens.shape[0],), bool)
    x = jnp.take(params["embed"], tokens, axis=0)           # [B, D]
    if policy is not None:
        x = constrain(x, policy, "batch", None)
    x0 = x
    pos = seq_lens

    group = lambda a: a[:n_grouped].reshape((n_attn, n_per_group) + a.shape[1:])
    mg = jax.tree.map(group, params["mamba"])
    cg = jax.tree.map(group, conv_states)
    sg = group(ssm_states)

    def mamba_scan(x, mp, c0, s0):
        def mbody(xc, xs):
            lp, c, s = xs
            xc, c2, s2 = ssm.mamba2_decode(lp, cfg, xc, c, s)
            return xc, (c2, s2)
        return jax.lax.scan(mbody, x, (mp, c0, s0))

    def group_body(carry, xs):
        xc, kpg, vpg = carry
        mp, c0, s0, lora_i, layer = xs
        xc, (c2, s2) = mamba_scan(xc, mp, c0, s0)
        h2 = rms_norm(jnp.concatenate([xc, x0], axis=-1),
                      params["shared"]["ln1"], cfg.norm_eps)
        q, k, v = _shared_qkv(cfg, params["shared"], lora_i, h2, lay)
        q = rope(q[:, None], pos[:, None], cfg.rope_theta)
        k = rope(k[:, None], pos[:, None], cfg.rope_theta)[:, 0]
        ke = jnp.take(k, jnp.asarray(kv_map), axis=-2)
        ve = jnp.take(v, jnp.asarray(kv_map), axis=-2)
        o, kpg, vpg = attn_fn(q, ke, ve, kpg, vpg, layer, block_table,
                              seq_lens, act,
                              scale=1.0 / math.sqrt(cfg.head_dim), window=None,
                              attn_softcap=None)
        o = o[:, 0] * jnp.asarray(head_mask, o.dtype)[:, None]
        xc = xc + jnp.einsum("bhk,hkd->bd", o, params["shared"]["wo"])
        y = mlp_apply(params["shared"]["mlp"],
                      rms_norm(xc, params["shared"]["ln2"], cfg.norm_eps),
                      cfg.mlp_act)
        xc = xc + y
        if policy is not None:
            xc = constrain(xc, policy, "batch", None)
        return (xc, kpg, vpg), (c2, s2)

    (x, k_pages, v_pages), (cg2, sg2) = jax.lax.scan(
        group_body, (x, k_pages, v_pages),
        (mg, cg, sg, params["lora"], jnp.arange(n_attn, dtype=jnp.int32)))
    mt = jax.tree.map(lambda a: a[n_grouped:], params["mamba"])
    ct0 = jax.tree.map(lambda a: a[n_grouped:], conv_states)
    x, (ct2, st2) = mamba_scan(x, mt, ct0, ssm_states[n_grouped:])
    ungroup = lambda g, t: jnp.concatenate([g.reshape((n_grouped,) + g.shape[2:]), t], 0)
    conv_out = jax.tree.map(ungroup, cg2, ct2)
    ssm_out = ungroup(sg2, st2)
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    return unembed(params, cfg, x, policy), (conv_out, ssm_out), (k_pages, v_pages)
