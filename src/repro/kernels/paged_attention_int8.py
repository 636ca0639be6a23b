"""Pallas TPU kernel: ragged paged attention over int8 KV pages.

One kernel for both inference phases (decode C=1, chunked prefill
C=chunk), one page per grid step of a one-layer pool; the K/V page
pool streams through VMEM as int8 codes plus an f32 per-(token, head)
scale sidecar, and dequantization happens *inside* the kernel right
before the MXU matmuls.  HBM traffic per page drops from ``ps*KV_p*hd``
floats to ``ps*KV_p*hd`` bytes + ``ps*KV_p`` scales: on the
bandwidth-bound decode phase that is a ~2x (fp16) to ~3.2x (fp32)
reduction, on top of the equal-bytes capacity win the byte-denominated
allocator takes.

Layout:
  q         [B, KV_p, C, G, d]  fp (padded layout)
  k_pages   [N, ps, KV_p, d]    int8 codes
  k_scales  [N, ps, KV_p, 1]    f32 (sidecar rides the same page table;
                                on TPU the unit lane is tolerable — the
                                sidecar is 1/(d) of the code bytes)
  v_pages / v_scales            likewise
  block_table [B, Pmax] int32 (scalar-prefetched), kv_lens/q_pos [B]

The scale BlockSpecs reuse the code pages' index_map, so the DMA engine
follows one page table for all four operands.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(
    # scalar-prefetch refs
    block_table_ref,    # [B, Pmax]
    kv_lens_ref,        # [B]
    q_pos_ref,          # [B]
    # array refs
    q_ref,              # [1, 1, C, G, d]        fp
    k_ref,              # [1, ps, 1, d]          int8
    ks_ref,             # [1, ps, 1, 1]          f32
    v_ref,              # [1, ps, 1, d]          int8
    vs_ref,             # [1, ps, 1, 1]          f32
    o_ref,              # [1, 1, C, G, d]
    # scratch
    m_ref,              # [C*G, 128] f32
    l_ref,              # [C*G, 128] f32
    acc_ref,            # [C*G, d] f32
    *,
    scale: float,
    page_size: int,
    window: int | None,
    softcap: float | None,
):
    b = pl.program_id(0)
    i = pl.program_id(2)
    n_pages = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = kv_lens_ref[b]
    start = i * page_size

    @pl.when(start < kv_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # [C, G, d]
        C, G, d = q.shape
        # dequant in VMEM: int8 codes * per-token scale, fp never touches HBM
        k = k_ref[0, :, 0].astype(jnp.float32) * ks_ref[0, :, 0]   # [ps, d]
        v = v_ref[0, :, 0].astype(jnp.float32) * vs_ref[0, :, 0]
        q2 = q.reshape(C * G, d)
        logits = jax.lax.dot_general(
            q2, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # [C*G, ps]
        if softcap is not None:
            logits = jnp.tanh(logits / softcap) * softcap
        kv_pos = start + jax.lax.broadcasted_iota(jnp.int32, (C * G, page_size), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (C * G, page_size), 0)
        qp = q_pos_ref[b] + row // G                         # query position
        mask = (kv_pos < kv_len) & (kv_pos <= qp)
        if window is not None:
            mask &= kv_pos > qp - window
        logits = jnp.where(mask, logits, NEG_INF)

        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[:, None]) * mask
        corr = jnp.exp(m_prev - m_new)
        l_ref[:, 0] = l_ref[:, 0] * corr + p.sum(axis=-1)
        m_ref[:, 0] = m_new
        pv = jax.lax.dot_general(
            p.astype(jnp.float32), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [C*G, d]
        acc_ref[...] = acc_ref[...] * corr[:, None] + pv

    @pl.when(i == n_pages - 1)
    def _finalize():
        C, G = o_ref.shape[2], o_ref.shape[3]
        l = jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / l).reshape(C, G, -1).astype(o_ref.dtype)


def paged_attention_int8(
    q,                      # [B, KV_p, C, G, d] fp
    k_pages, k_scales,      # [N, ps, KV_p, d] int8 / [N, ps, KV_p, 1] f32
    v_pages, v_scales,
    block_table,            # [B, Pmax] int32
    kv_lens,                # [B] int32
    q_pos,                  # [B] int32 (position of first query row per seq)
    *,
    scale: float,
    window=None,
    softcap=None,
    interpret: bool = False,
):
    """Returns o [B, KV_p, C, G, d] in q's dtype."""
    # argument contract — same RPR008 discipline as the fp launcher: a
    # shape/dtype mistake dies here with a message, not as an opaque
    # Mosaic lowering error (all checks on static shapes: free once jitted)
    if q.ndim != 5:
        raise ValueError(f"q must be [B, KV_p, C, G, d], got shape {q.shape}")
    B, KV_p, C, G, d = q.shape
    if jnp.issubdtype(q.dtype, jnp.integer):
        raise ValueError(f"q must be floating-point, got {q.dtype}")
    if k_pages.ndim != 4 or k_pages.shape != v_pages.shape:
        raise ValueError(
            f"k_pages/v_pages must share shape [N, ps, KV_p, d], got "
            f"{k_pages.shape} vs {v_pages.shape}")
    if k_pages.dtype != jnp.int8 or v_pages.dtype != jnp.int8:
        raise ValueError(
            f"k_pages/v_pages must be int8 codes, got {k_pages.dtype}/"
            f"{v_pages.dtype}")
    N, ps, _, _ = k_pages.shape
    if k_pages.shape[2:] != (KV_p, d):
        raise ValueError(
            f"k_pages trailing dims {k_pages.shape[2:]} disagree with q's "
            f"(KV_p, d) = {(KV_p, d)}")
    if k_scales.shape != v_scales.shape or k_scales.shape != (N, ps, KV_p, 1):
        raise ValueError(
            f"k_scales/v_scales must be [N={N}, ps={ps}, KV_p={KV_p}, 1], "
            f"got {k_scales.shape} vs {v_scales.shape}")
    if k_scales.dtype != jnp.float32 or v_scales.dtype != jnp.float32:
        raise ValueError(
            f"scale sidecars must be float32, got {k_scales.dtype}/"
            f"{v_scales.dtype}")
    if block_table.ndim != 2 or block_table.shape[0] != B:
        raise ValueError(
            f"block_table must be [B={B}, Pmax], got {block_table.shape}")
    for name, arr in (("block_table", block_table), ("kv_lens", kv_lens),
                      ("q_pos", q_pos)):
        if not jnp.issubdtype(arr.dtype, jnp.integer):
            raise ValueError(f"{name} must be integer-typed, got {arr.dtype}")
    if kv_lens.shape != (B,) or q_pos.shape != (B,):
        raise ValueError(
            f"kv_lens/q_pos must be [B={B}], got {kv_lens.shape} / "
            f"{q_pos.shape}")
    Pmax = block_table.shape[1]

    grid = (B, KV_p, Pmax)

    def q_map(b, h, i, *_):
        return (b, h, 0, 0, 0)

    def kv_map(b, h, i, block_table_ref, kv_lens_ref, q_pos_ref):
        return (block_table_ref[b, i], 0, h, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, C, G, d), q_map),
            pl.BlockSpec((1, ps, 1, d), kv_map),
            pl.BlockSpec((1, ps, 1, 1), kv_map),
            pl.BlockSpec((1, ps, 1, d), kv_map),
            pl.BlockSpec((1, ps, 1, 1), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, C, G, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((C * G, 128), jnp.float32),
            pltpu.VMEM((C * G, 128), jnp.float32),
            pltpu.VMEM((C * G, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _kernel, scale=scale, page_size=ps,
        window=None if window is None else int(window),
        softcap=None if softcap is None else float(softcap))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(block_table, kv_lens, q_pos, q, k_pages, k_scales, v_pages, v_scales)
