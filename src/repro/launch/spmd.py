"""shard_map islands for the two ops GSPMD cannot partition well:

1. paged attention + KV page writes (data-dependent page scatter/gather:
   under plain GSPMD the partitioner cannot prove block-table locality and
   materializes all-gathers of the page pool — the measured baseline
   pathology in EXPERIMENTS.md §Perf);
2. MoE dispatch (data-dependent scatter): formulated as expert-local
   compute + ONE psum over `model` — the same all-reduce a dense TP MLP
   pays, so EP adds no extra collective phase.

Both wrappers keep the *global* calling convention of the model code; the
bodies run on per-shard local arrays.

Locality invariant: a sequence's pages live on its data shard and block
tables store pool-local indices modulo the per-shard pool size (the
engine's allocator partitions the pool per data shard; `% N_local` maps
global ids to local ones).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map

from repro.models.layers import paged_attention_ref, act_fn
from repro.models.moe import moe_apply
from repro.models.transformer import write_kv_chunk, write_kv_token

# int8 KV machinery now lives in kernels/kv_int8.py (promoted from here);
# re-exported so existing imports (tests/test_int8_kv.py, downstream
# users of the spmd entry point) keep working unchanged.
from repro.kernels.kv_int8 import (  # noqa: F401  (re-export surface)
    int8_chunk_attn, int8_decode_attn, paged_attention_int8, q8_kv,
)


def _dspec(data):
    return data if len(data) > 1 else data[0]


def make_sharded_decode_attn(mesh, *, data=("data",), model="model",
                             shard_batch=True, kv_int8=False):
    """default_decode_attn-shaped write+attend step inside shard_map.

    The whole pools [L, N, ps, KV_p * hd] come in with an unsharded layer
    axis, the row sharded over model (whole heads per shard), and the
    layer index as a replicated operand.
    shard_batch=False replicates the (tiny) decode batch over data —
    pages then shard over their PAGE dim instead (single-sequence long-
    context layout)."""
    d = _dspec(data)
    if shard_batch:
        q_spec = P(d, None, model, None)
        kn_spec = P(d, model, None)
        pg_spec = P(None, d, None, model)
        bt_spec, v_spec = P(d, None), P(d)
    else:
        q_spec = P(None, None, model, None)
        kn_spec = P(None, model, None)
        pg_spec = P(None, None, None, model)      # replicated over data
        bt_spec, v_spec = P(None, None), P(None)

    def local(q, k_new, v_new, kpg, vpg, layer, bt, lens, active, win, *,
              scale, softcap):
        if kv_int8:
            bt_loc = bt % kpg["q"].shape[1]
            return int8_decode_attn(q, k_new, v_new, kpg, vpg, layer, bt_loc,
                                    lens, active, scale=scale, window=win,
                                    attn_softcap=softcap)
        bt_loc = bt % kpg.shape[1]
        kpg, vpg = write_kv_token(kpg, vpg, layer, k_new, v_new, bt_loc, lens,
                                  active)
        o = paged_attention_ref(q, kpg, vpg, bt_loc, lens + 1, lens[:, None],
                                scale=scale, layer=layer, window=win,
                                attn_softcap=softcap)
        return o, kpg, vpg

    def fn(q, k_new, v_new, kpg, vpg, layer, bt, lens, active, *, scale,
           window, attn_softcap):
        win = jnp.asarray(window if window is not None else 2**30, jnp.int32)
        body = functools.partial(local, scale=scale, softcap=attn_softcap)
        pspec = {"q": pg_spec, "s": pg_spec} if kv_int8 else pg_spec
        mapped = shard_map(
            body, mesh=mesh,
            in_specs=(q_spec, kn_spec, kn_spec, pspec, pspec, P(), bt_spec,
                      v_spec, v_spec, P()),
            out_specs=(q_spec, pspec, pspec),
            check_vma=False,
        )
        return mapped(q, k_new, v_new, kpg, vpg, layer, bt, lens, active, win)

    return fn


def make_sharded_chunk_attn(mesh, *, data=("data",), model="model",
                            kv_int8=False):
    """default_chunk_attn-shaped step: chunked-prefill write + attend over
    paged history, on the whole pools with the layer index replicated.
    Streams shard over data (engine pins stream i to data shard
    i*P/n_data)."""
    d = _dspec(data)
    q_spec = P(d, None, model, None)
    kn_spec = P(d, None, model, None)
    pg_spec = P(None, d, None, model)
    bt_spec, v_spec = P(d, None), P(d)

    def local(q, k_new, v_new, kpg, vpg, layer, bt, start, lens, win, *,
              scale, softcap):
        C = q.shape[1]
        q_pos = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
        if kv_int8:
            bt_loc = bt % kpg["q"].shape[1]
            return int8_chunk_attn(q, k_new, v_new, kpg, vpg, layer, bt_loc,
                                   start, lens, scale=scale, window=win,
                                   attn_softcap=softcap)
        bt_loc = bt % kpg.shape[1]
        kpg, vpg = write_kv_chunk(kpg, vpg, layer, k_new, v_new, bt_loc,
                                  start, lens)
        o = paged_attention_ref(q, kpg, vpg, bt_loc, start + lens, q_pos,
                                scale=scale, layer=layer, window=win,
                                attn_softcap=softcap)
        return o, kpg, vpg

    def fn(q, k_new, v_new, kpg, vpg, layer, bt, start, lens, *, scale,
           window, attn_softcap):
        win = jnp.asarray(window if window is not None else 2**30, jnp.int32)
        body = functools.partial(local, scale=scale, softcap=attn_softcap)
        pspec = {"q": pg_spec, "s": pg_spec} if kv_int8 else pg_spec
        mapped = shard_map(
            body, mesh=mesh,
            in_specs=(q_spec, kn_spec, kn_spec, pspec, pspec, P(), bt_spec,
                      v_spec, v_spec, P()),
            out_specs=(q_spec, pspec, pspec),
            check_vma=False,
        )
        return mapped(q, k_new, v_new, kpg, vpg, layer, bt, start, lens, win)

    return fn


def make_sharded_moe_fn(mesh, cfg, *, tp: int, data=("data",), model="model",
                        flat_f=False, fsdp_gather=False):
    """EP/expert-TP MoE: local dispatch + expert GEMMs + one psum.

    flat_f (the large-model decode scheme, §Perf): expert d_ff is sharded
    over EVERY mesh axis (e.g. 32768/256 = 128 per chip for grok-1) with
    token activations replicated — per-chip expert bytes drop n_data-fold
    and no weight collective exists at all; combine = one [T, D] psum over
    all axes."""
    d = _dspec(data)
    E = cfg.n_experts
    ep = E % tp == 0 and not flat_f
    gate_act = act_fn("silu" if cfg.mlp_act == "silu" else "gelu")
    flat = tuple(data) + (model,)

    if fsdp_gather:
        # training profile for FSDP'd expert weights: keep the data-axis
        # shard INSIDE the island and all-gather here — the autodiff
        # transpose is then a reduce-scatter (not a replicated-grad
        # rematerialization; fixes a 600 GiB/dev peak on grok-1 train).
        dax = data[-1]
        # per-layer shapes: w_gate/w_up [E, D, F] (FSDP on D=dim1),
        # w_down [E, F, D] (FSDP on D=dim2) — mirrors param_pspecs
        if ep:
            w_spec = {"router": P(None, None),
                      "w_gate": P(model, dax, None), "w_up": P(model, dax, None),
                      "w_down": P(model, None, dax)}
        else:
            w_spec = {"router": P(None, None),
                      "w_gate": P(None, dax, model), "w_up": P(None, dax, model),
                      "w_down": P(None, model, dax)}
        x_spec = P(_dspec(data), None)
        psum_axes = (model,)
    elif flat_f:
        w_spec = {"router": P(None, None),
                  "w_gate": P(None, None, flat), "w_up": P(None, None, flat),
                  "w_down": P(None, flat, None)}
        x_spec = P(None, None)          # tokens replicated
        psum_axes = flat
    elif ep:
        w_spec = {"router": P(None, None),
                  "w_gate": P(model, None, None), "w_up": P(model, None, None),
                  "w_down": P(model, None, None)}
        x_spec = P(d, None)
        psum_axes = (model,)
    else:
        w_spec = {"router": P(None, None),
                  "w_gate": P(None, None, model), "w_up": P(None, None, model),
                  "w_down": P(None, model, None)}
        x_spec = P(d, None)
        psum_axes = (model,)

    def local(lp, x2d):
        if fsdp_gather:
            dax = data[-1]
            lp = dict(lp)
            for kname in ("w_gate", "w_up"):
                lp[kname] = jax.lax.all_gather(lp[kname], dax, axis=1,
                                               tiled=True)
            lp["w_down"] = jax.lax.all_gather(lp["w_down"], dax, axis=2,
                                              tiled=True)
        offset = jax.lax.axis_index(model) * (E // tp) if ep else 0
        y, aux = moe_apply(lp, x2d, n_experts=E, top_k=cfg.top_k,
                           act=gate_act, expert_offset=offset,
                           capacity_factor=cfg.moe_capacity_factor)
        y = jax.lax.psum(y, psum_axes)
        aux = jax.lax.pmean(aux, psum_axes)
        return y, aux

    mapped = shard_map(
        local, mesh=mesh,
        in_specs=(w_spec, x_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )
    return lambda lp, x2d: mapped(lp, x2d)
