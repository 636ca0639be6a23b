"""Dry-run cell builders: (architecture x input-shape) -> jit-able step.

Each builder returns a dict:
  fn            — python callable
  args          — tuple of ShapeDtypeStruct pytrees (abstract, no alloc)
  in_shardings  — matching tuple of PartitionSpec pytrees
  donate        — argnums to donate (page pools / train state)
  note          — human-readable cell description

Shapes (assignment): train_4k / prefill_32k / decode_32k / long_500k,
plus mixed_32k — the paper-representative Splitwiser fused step (16
prompt streams x 2048-token chunks + 128 decode slots @32k).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import TrainConfig, get_config
from repro.kernels.kv_int8 import init_pages_int8
from repro.launch import spmd
from repro.launch.shardings import param_pspecs
from repro.models import encdec, hybrid, rwkv
from repro.models import transformer as T
from repro.models.registry import Model, FAMILY_MODULE, CACHE_KIND
from repro.models.sharding import Policy, make_rules
from repro.train.trainer import init_state, make_train_step

PAGE = 64

SHAPES = {
    "train_4k": dict(kind="train", seq=4096, batch=256),
    "prefill_32k": dict(kind="prefill", seq=32768, batch=32),
    "decode_32k": dict(kind="decode", seq=32768, batch=128),
    "long_500k": dict(kind="decode", seq=524288, batch=1),
    "mixed_32k": dict(kind="mixed", seq=32768, batch=128, chunk=2048,
                      streams=16),
}

# archs whose weights exceed one chip's HBM share at TP=16 -> ZeRO-3-style
# data-axis weight sharding even for serving
SERVE_FSDP = {"grok-1-314b"}

F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def cell_supported(cfg, shape_name: str):
    if shape_name == "long_500k" and not cfg.supports_long_context:
        return False, ("full-attention KV residency at 524288 ctx; "
                       "needs context-streaming attention — skipped")
    if shape_name == "mixed_32k" and cfg.family not in ("dense", "moe", "vlm"):
        return False, "mixed fused step is transformer-family (paper cell)"
    return True, ""


def get_model(arch):
    cfg = get_config(arch)
    return Model(arch, cfg, FAMILY_MODULE[cfg.family], CACHE_KIND[cfg.family])


def _axes(mesh):
    multi = "pod" in mesh.axis_names
    da = ("pod", "data") if multi else ("data",)
    n_data = math.prod(mesh.shape[a] for a in da)
    return da, n_data, mesh.shape["model"]


def _dspec(da):
    return da if len(da) > 1 else da[0]


def _policy(mesh, da, fsdp: bool):
    return Policy(make_rules(da, "model", fsdp=fsdp), mesh)


# ------------------------------------------------------------------ train --
def build_train(arch, mesh, scheme="tp"):
    '''scheme: "tp" (baseline: TP over model + ZeRO over data),
    "fsdp" (pure 256-way DP + ZeRO-3 over BOTH axes — §Perf optimization
    for small archs whose TP activation all-reduces dominate),
    either with "+vtiled" appended for the fused vocab-tiled CE loss.'''
    model = get_model(arch)
    cfg = model.cfg
    da, n_data, tp = _axes(mesh)
    fsdp_only = scheme.startswith("fsdp")
    vtiled = scheme.endswith("vtiled")
    sh = SHAPES["train_4k"]
    tcfg = TrainConfig(global_batch=sh["batch"], seq_len=sh["seq"], remat=True,
                       int8_moments=(arch in SERVE_FSDP),
                       loss_impl="vtiled" if vtiled else "chunked")
    if fsdp_only:
        tp = 1
        flat = tuple(da) + ("model",)
        rules = make_rules((flat,) if False else flat, "model", fsdp=True)
        # batch + fsdp over ALL axes; no tensor parallelism
        rules = dict(rules)
        for k in ("batch", "tokens", "pages", "fsdp"):
            rules[k] = flat
        for k in ("heads", "kv_heads", "ff", "vocab", "experts"):
            rules[k] = None
        policy = Policy(rules, mesh)
    else:
        policy = _policy(mesh, da, fsdp=True)
    moe_fn = (spmd.make_sharded_moe_fn(mesh, cfg, tp=tp, data=da,
                                       fsdp_gather=True)
              if cfg.is_moe and not fsdp_only else None)
    step = make_train_step(model, tcfg, tp=tp, policy=policy, moe_fn=moe_fn)

    state_shapes = jax.eval_shape(
        lambda: init_state(model, jax.random.PRNGKey(0), tcfg, BF16, tp=tp))
    if fsdp_only:
        flat = tuple(da) + ("model",)
        p_specs = param_pspecs(state_shapes["params"], cfg, tp=1,
                               fsdp_size=n_data * mesh.shape["model"],
                               fsdp=flat)
        o_specs = _opt_specs(state_shapes["opt"], p_specs, da, tp)
        state_specs = {"params": p_specs, "opt": o_specs}
    else:
        p_specs = param_pspecs(state_shapes["params"], cfg, tp=tp,
                               fsdp_size=mesh.shape["data"], fsdp="data")
        o_specs = _opt_specs(state_shapes["opt"], p_specs, da, tp)
        state_specs = {"params": p_specs, "opt": o_specs}

    B, S = sh["batch"], sh["seq"]
    d = (tuple(da) + ("model",)) if fsdp_only else _dspec(da)
    batch_shapes = {"tokens": sds((B, _text_len(cfg, S)), I32),
                    "labels": sds((B, _text_len(cfg, S)), I32)}
    batch_specs = {"tokens": P(d, None), "labels": P(d, None)}
    if cfg.family == "encdec":
        batch_shapes["frames"] = sds((B, cfg.encoder_seq, cfg.d_model), BF16)
        batch_specs["frames"] = P(d, None, None)
    if cfg.family == "vlm":
        batch_shapes["patches"] = sds((B, cfg.n_vision_patches, cfg.d_vision), BF16)
        batch_specs["patches"] = P(d, None, None)
    return dict(fn=step, args=(state_shapes, batch_shapes),
                in_shardings=(state_specs, batch_specs), donate=(0,),
                note=f"train_step B={B} S={S} remat scheme={scheme} "
                     f"int8_mom={tcfg.int8_moments}")


def _text_len(cfg, seq):
    """vlm text tokens = seq - vision prefix so total context == seq."""
    return seq - cfg.n_vision_patches if cfg.family == "vlm" else seq


def _opt_specs(opt_shapes, p_specs, da, tp):
    """Moment specs mirror the parameter specs. Q8 moments are
    shape-preserving (codes = param shape; scales = param shape with the
    last dim blocked), so they inherit the param spec with per-dim
    divisibility re-checked."""
    from repro.launch.shardings import _parts
    spec_map = {}
    def record(path, leaf):
        spec_map["/".join(_parts(path))] = leaf
        return leaf
    jax.tree_util.tree_map_with_path(record, p_specs,
                                     is_leaf=lambda x: isinstance(x, P))

    def fit(spec, shape):
        s = list(spec) + [None] * (len(shape) - len(spec))
        for i, ax in enumerate(s[: len(shape)]):
            if ax is None:
                continue
            size = 16 if not isinstance(ax, tuple) else 16 * len(ax)
            if shape[i] % size != 0:
                s[i] = None
        return P(*s[: len(shape)])

    def f(path, leaf):
        parts = _parts(path)
        if parts[0] == "count":
            return P()
        # Q8 moments flatten as NamedTuple attribute keys ('q'/'scale')
        # or positional digits depending on jax version — strip either
        last = parts[-1]
        strip = last.isdigit() or last in ("q", "scale", "0", "1")
        key = "/".join(parts[1:-1] if strip else parts[1:])
        base = spec_map.get(key, P())
        return fit(base, leaf.shape)

    return jax.tree_util.tree_map_with_path(f, opt_shapes)


# ---------------------------------------------------------------- prefill --
def build_prefill(arch, mesh, shape_name="prefill_32k"):
    model = get_model(arch)
    cfg = model.cfg
    da, n_data, tp = _axes(mesh)
    d = _dspec(da)
    sh = SHAPES[shape_name]
    B, S = sh["batch"], sh["seq"]
    fsdp = "data" if arch in SERVE_FSDP else None
    policy = _policy(mesh, da, fsdp=False)
    moe_fn = (spmd.make_sharded_moe_fn(mesh, cfg, tp=tp, data=da)
              if cfg.is_moe else None)

    params_shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                                      BF16, tp=tp))
    p_specs = param_pspecs(params_shapes, cfg, tp=tp,
                           fsdp_size=mesh.shape["data"], fsdp=fsdp)

    if cfg.family in ("dense", "moe"):
        def fn(params, tokens):
            return T.prefill(params, cfg, tokens, tp=tp, policy=policy,
                             moe_fn=moe_fn)
        args = (params_shapes, sds((B, S), I32))
        in_sh = (p_specs, P(d, None))
    elif cfg.family == "vlm":
        def fn(params, tokens, patches):
            return T.prefill(params, cfg, tokens, patches=patches, tp=tp,
                             policy=policy, moe_fn=moe_fn)
        args = (params_shapes, sds((B, _text_len(cfg, S)), I32),
                sds((B, cfg.n_vision_patches, cfg.d_vision), BF16))
        in_sh = (p_specs, P(d, None), P(d, None, None))
    elif cfg.family == "encdec":
        def fn(params, frames, tokens):
            return encdec.prefill(params, cfg, frames, tokens, tp=tp,
                                  policy=policy)
        args = (params_shapes, sds((B, cfg.encoder_seq, cfg.d_model), BF16),
                sds((B, S), I32))
        in_sh = (p_specs, P(d, None, None), P(d, None))
    elif cfg.family == "hybrid":
        def fn(params, tokens):
            return hybrid.prefill(params, cfg, tokens, tp=tp, policy=policy)
        args = (params_shapes, sds((B, S), I32))
        in_sh = (p_specs, P(d, None))
    else:  # ssm
        def fn(params, tokens):
            return rwkv.prefill(params, cfg, tokens, tp=tp, policy=policy,
                                chunk=64)
        args = (params_shapes, sds((B, S), I32))
        in_sh = (p_specs, P(d, None))
    return dict(fn=fn, args=args, in_shardings=in_sh, donate=(),
                note=f"prefill B={B} S={S}")


# ----------------------------------------------------------------- decode --
def _page_pools(cfg, tp, n_seqs, seq, n_data, n_layers=None, extra_seqs=0,
                kv_int8=False):
    """(abstract bf16 or int8 k/v page pools, as the engine lays them out,
    Pmax). N is data-divisible and includes per-shard trash pages."""
    per_seq = seq // PAGE + 1
    n_raw = (n_seqs + extra_seqs) * per_seq + n_data
    N = -(-n_raw // n_data) * n_data
    if kv_int8:
        pools = jax.eval_shape(lambda: init_pages_int8(
            cfg, N, PAGE, tp=tp, n_layers=n_layers))
    else:
        pools = jax.eval_shape(lambda: T.init_pages(
            cfg, N, PAGE, tp=tp, dtype=BF16, n_layers=n_layers))
    return pools, per_seq


def build_decode(arch, mesh, shape_name="decode_32k", scheme="zero3"):
    """scheme "zero3" (baseline): batch sharded over data; with FSDP'd
    weights (grok) GSPMD must all-gather each layer's weights per token
    step — measured collective-bound. scheme "2d": GEMM activations
    replicated over data (weights stay 2D-sharded, contraction partials
    psum'd; attention/pages stay data-sharded) — the §Perf fix."""
    model = get_model(arch)
    cfg = model.cfg
    da, n_data, tp = _axes(mesh)
    d = _dspec(da)
    sh = SHAPES[shape_name]
    B, S = sh["batch"], sh["seq"]
    shard_batch = (B % n_data == 0) and scheme != "2d"
    flat_f = scheme == "2d"
    fsdp = "data" if (arch in SERVE_FSDP and scheme != "2d") else None
    policy = _policy(mesh, da, fsdp=False) if shard_batch else None

    def _2d_overrides(p_specs):
        """Flat (data x model) sharding of the OUTPUT/F dims of every big
        weight: nothing big sits on a contraction dim, so GSPMD cannot
        choose weight all-gathers; activations stay replicated and the
        per-layer collective is one tiny [B, D] psum."""
        flat = tuple(da) + ("model",)
        from repro.launch.shardings import _parts
        def fix(path, spec_leaf):
            parts = _parts(path)
            name = parts[-1]
            if name in ("w_gate", "w_up") and "moe" in parts:
                return P(None, None, None, flat)
            if name == "w_down" and "moe" in parts:
                return P(None, None, flat, None)
            return spec_leaf
        return jax.tree_util.tree_map_with_path(
            fix, p_specs, is_leaf=lambda x: isinstance(x, P))
    moe_fn = (spmd.make_sharded_moe_fn(mesh, cfg, tp=tp, data=da,
                                       flat_f=flat_f)
              if cfg.is_moe else None)
    params_shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                                      BF16, tp=tp))
    p_specs = param_pspecs(params_shapes, cfg, tp=tp,
                           fsdp_size=mesh.shape["data"], fsdp=fsdp)
    if scheme == "2d":
        p_specs = _2d_overrides(p_specs)
    bspec = P(d) if shard_batch else P()
    bspec2 = P(d, None) if shard_batch else P(None, None)

    if cfg.family == "ssm":                   # rwkv: state cache only
        state_shapes = jax.eval_shape(
            lambda: rwkv.init_state(cfg, B, BF16))
        st_specs = jax.tree.map(
            lambda x: P(None, d if shard_batch else None, "model", None)
            if x.ndim == 4 else P(None, d if shard_batch else None, "model"),
            state_shapes)
        # x_tm/x_cm [L,B,D]: D sharded over model? keep replicated D
        st_specs = {
            "x_tm": P(None, d if shard_batch else None, None),
            "x_cm": P(None, d if shard_batch else None, None),
            "S": P(None, d if shard_batch else None, "model", None),
        }
        def fn(params, tokens, state):
            return rwkv.decode(params, cfg, tokens, state, policy=policy)
        return dict(fn=fn, args=(params_shapes, sds((B,), I32), state_shapes),
                    in_shardings=(p_specs, bspec, st_specs), donate=(2,),
                    flop_divisor=None if shard_batch else tp,
                    note=f"decode(state) B={B} ctx={S}")

    if cfg.family == "hybrid":
        n_attn, n_mamba, _, _, _ = hybrid.group_structure(cfg)
        (kpg, vpg), Pmax = _page_pools(cfg, tp, B, S, n_data,
                                       n_layers=n_attn)
        conv_sh, ssm_sh = None, None
        cs, ss = __import__("repro.models.ssm", fromlist=["x"]).mamba2_state_shapes(cfg, B)
        conv_shapes = {k: sds((n_mamba,) + v, BF16) for k, v in cs.items()}
        ssm_shapes = sds((n_mamba,) + ss, F32)
        db = d if shard_batch else None
        conv_specs = {"x": P(None, db, None, "model"),
                      "B": P(None, db, None, None),
                      "C": P(None, db, None, None)}
        ssm_specs = P(None, db, "model", None, None)
        pg_spec = (P(None, d, None, "model") if shard_batch
                   else P(None, None, None, "model"))
        attn = spmd.make_sharded_decode_attn(mesh, data=da, model="model",
                                             shard_batch=shard_batch)
        def fn(params, tokens, conv, ssm_st, kpg, vpg, bt, lens):
            return hybrid.decode(params, cfg, tokens, conv, ssm_st, kpg, vpg,
                                 bt, lens, attn_fn=attn, tp=tp, policy=policy)
        args = (params_shapes, sds((B,), I32), conv_shapes, ssm_shapes,
                kpg, vpg, sds((B, Pmax), I32), sds((B,), I32))
        in_sh = (p_specs, bspec, conv_specs, ssm_specs, pg_spec, pg_spec,
                 bspec2, bspec)
        return dict(fn=fn, args=args, in_shardings=in_sh, donate=(2, 3, 4, 5),
                    flop_divisor=None if shard_batch else tp,
                    note=f"decode(hybrid) B={B} ctx={S} attn_layers={n_attn}")

    if cfg.family == "encdec":
        (kpg, vpg), Pmax = _page_pools(cfg, tp, B, S, n_data)
        _, KV_p, _, _, _ = T.gqa_layout(cfg.n_heads, cfg.n_kv_heads, tp)
        cross_shape = (cfg.n_layers, B, cfg.encoder_seq, KV_p, cfg.head_dim)
        pg_spec = P(None, d, None, "model")
        cross_spec = P(None, d, None, "model", None)
        attn = spmd.make_sharded_decode_attn(mesh, data=da, model="model")
        def fn(params, tokens, kpg, vpg, xk, xv, bt, lens):
            return encdec.decode(params, cfg, tokens, kpg, vpg, xk, xv, bt,
                                 lens, attn_fn=attn, tp=tp, policy=policy)
        args = (params_shapes, sds((B,), I32), kpg, vpg,
                sds(cross_shape, BF16),
                sds(cross_shape, BF16), sds((B, Pmax), I32), sds((B,), I32))
        in_sh = (p_specs, bspec, pg_spec, pg_spec, cross_spec, cross_spec,
                 bspec2, bspec)
        return dict(fn=fn, args=args, in_shardings=in_sh, donate=(2, 3),
                    note=f"decode(encdec) B={B} ctx={S}")

    # transformer family
    (kpg, vpg), Pmax = _page_pools(cfg, tp, B, S, n_data)
    pages_data_sharded = shard_batch or scheme == "2d"
    pg_spec = (P(None, d, None, "model") if pages_data_sharded
               else P(None, None, None, "model"))
    attn = spmd.make_sharded_decode_attn(
        mesh, data=da, model="model", shard_batch=pages_data_sharded)
    def fn(params, tokens, kpg, vpg, bt, lens):
        return T.decode(params, cfg, tokens, kpg, vpg, bt, lens,
                        attn_fn=attn, tp=tp, policy=policy, moe_fn=moe_fn)
    args = (params_shapes, sds((B,), I32), kpg, vpg, sds((B, Pmax), I32),
            sds((B,), I32))
    in_sh = (p_specs, bspec, pg_spec, pg_spec, bspec2, bspec)
    # 2d scheme: GEMMs are replicated over data (outer_mult), islands exact
    return dict(fn=fn, args=args, in_shardings=in_sh, donate=(2, 3),
                flop_divisor=None if (shard_batch or flat_f) else tp,
                outer_mult=n_data if flat_f else 1,
                note=f"decode B={B} ctx={S} pool={kpg.shape} scheme={scheme}")


# ------------------------------------------------------------------ mixed --
def build_mixed(arch, mesh, shape_name="mixed_32k", scheme="baseline"):
    """The paper-technique cell: fused chunked-prefill + decode.
    scheme "kv8": int8-quantized KV pages (§Perf, halves KV traffic)."""
    model = get_model(arch)
    cfg = model.cfg
    da, n_data, tp = _axes(mesh)
    d = _dspec(da)
    sh = SHAPES[shape_name]
    B, S, C = sh["batch"], sh["seq"], sh["chunk"]
    # one (or more) prompt streams per data shard — the paper's #processes
    # knob scaled to the mesh
    Pstr = max(sh["streams"], n_data)
    Pstr = -(-Pstr // n_data) * n_data
    policy = _policy(mesh, da, fsdp=False)
    moe_fn = (spmd.make_sharded_moe_fn(mesh, cfg, tp=tp, data=da)
              if cfg.is_moe else None)
    params_shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                                      BF16, tp=tp))
    p_specs = param_pspecs(params_shapes, cfg, tp=tp,
                           fsdp_size=mesh.shape["data"],
                           fsdp="data" if arch in SERVE_FSDP else None)
    kv8 = scheme == "kv8"
    (kpg, vpg), Pmax = _page_pools(cfg, tp, B, S, n_data, extra_seqs=Pstr,
                                   kv_int8=kv8)
    pg_spec = P(None, d, None, "model")
    pg_sp = {"q": pg_spec, "s": pg_spec} if kv8 else pg_spec
    attn = {
        "decode": spmd.make_sharded_decode_attn(mesh, data=da, model="model",
                                                kv_int8=kv8),
        "chunk": spmd.make_sharded_chunk_attn(mesh, data=da, model="model",
                                              kv_int8=kv8),
    }

    def fn(params, mb, kpg, vpg):
        return T.mixed(params, cfg, mb, kpg, vpg, attn_fn=attn, tp=tp,
                       policy=policy, moe_fn=moe_fn)

    mb_shapes = dict(
        p_tokens=sds((Pstr, C), I32), p_table=sds((Pstr, Pmax), I32),
        p_start=sds((Pstr,), I32), p_lens=sds((Pstr,), I32),
        d_tokens=sds((B,), I32), d_table=sds((B, Pmax), I32),
        d_lens=sds((B,), I32), d_active=sds((B,), jnp.bool_),
    )
    mb_specs = dict(
        p_tokens=P(d, None), p_table=P(d, None), p_start=P(d), p_lens=P(d),
        d_tokens=P(d), d_table=P(d, None), d_lens=P(d), d_active=P(d),
    )
    args = (params_shapes, mb_shapes, kpg, vpg)
    in_sh = (p_specs, mb_specs, pg_sp, pg_sp)
    return dict(fn=fn, args=args, in_shardings=in_sh, donate=(2, 3),
                note=f"mixed(Splitwiser) streams={Pstr}x{C} + decode B={B} "
                     f"@ctx={S} scheme={scheme}")


def build_cell(arch, shape_name, mesh):
    cfg = get_config(arch)
    ok, why = cell_supported(cfg, shape_name)
    if not ok:
        return None, why
    kind = SHAPES[shape_name]["kind"]
    if kind == "train":
        return build_train(arch, mesh), ""
    if kind == "prefill":
        return build_prefill(arch, mesh, shape_name), ""
    if kind == "decode":
        return build_decode(arch, mesh, shape_name), ""
    if kind == "mixed":
        return build_mixed(arch, mesh, shape_name), ""
    raise ValueError(kind)
