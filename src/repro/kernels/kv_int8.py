"""int8 KV page quantization: codes + per-(token, head) scale sidecar.

Beyond-paper optimization (ROADMAP "Quantized (int8) KV pages"): KV pages
are stored as int8 codes plus one f32 scale per (token, kv-head) and
dequantized on the fly inside the attention kernel.  Page bytes drop from
``hd * itemsize`` to ``hd + 4`` per (token, head), so at equal pool bytes
the allocator carves out ~2x the pages (3.2x on the fp32 reduced models) —
which is exactly the Splitwiser lever: KV capacity, not FLOPs, is what
forces preemptions on the constrained device.

This module is the single entry point for the int8 path:

  * :func:`q8_kv` / :func:`paged_attention_int8` — the canonical quantizer
    and the jnp reference attention (dequant fused into the flash scan via
    ``k_scale``/``v_scale``); promoted here from ``launch/spmd.py``, which
    now re-exports them.
  * :func:`int8_decode_attn` / :func:`int8_chunk_attn` — drop-in
    ``default_decode_attn`` / ``default_chunk_attn`` replacements over
    page *dicts* ``{"q": int8 codes [L, N, ps, KV_p * hd], "s": f32
    scales [L, N, ps, KV_p]}``.  Same contract as the fp functions: they take
    the whole dict pools and the scan's layer index, scatter the new
    codes and scales at ``[layer, page, offset]`` (one scatter per leaf),
    gather that layer's pages by ``(layer, block_table)``, and return the
    whole dict pools.  ``transformer.decode`` / ``transformer.mixed``
    carry the dict pools through their layer scans like fp pools, so the
    engine flips paths by swapping ``attn_fn`` and the page pytree only.
  * the Pallas dequant-in-kernel variant lives in
    ``kernels/paged_attention_int8.py`` (TPU tiling; validated in
    interpret mode against :func:`paged_attention_int8` here).

Accuracy: per-(token, head) symmetric quantization keeps relative
attention-output error ~1e-3 (tests/test_int8_kv.py); greedy streams on
the tier-1 workloads match the fp oracle token-for-token (the gap only
matters when two logits sit closer than the attention perturbation —
``pressure_kv_int8`` reports the per-token fp agreement on longer runs).
Cross-MODE int8 streams are bit-identical by construction: every
attention path reads dequantized values for every key — chunked paths
re-read committed pages, the monolithic prefill applies
:func:`fake_quant_kv` — so chunk boundaries cancel out exactly as in fp.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.models.layers import flash_attention, gather_pages
from repro.models.transformer import write_kv_chunk, write_kv_token

# Floor on the stored scale: an all-zero (token, head) row — zero-init
# pool pages, padding tokens — must carry a positive finite scale so
# dequant is exactly 0.0, never 0/0 = NaN, and the sanitizer's sidecar
# checks can treat scale > 0 as "this entry is live".
SCALE_FLOOR = 1e-20


def q8_kv(t):
    """t [..., hd] -> (int8 codes, f32 scale [..., 1]).

    Symmetric per-(token, head) quantization: scale = maxabs/127, floored
    at :data:`SCALE_FLOOR` (all-zero rows stay exactly representable).
    """
    t32 = t.astype(jnp.float32)
    scale = jnp.maximum(
        jnp.max(jnp.abs(t32), axis=-1, keepdims=True) / 127.0, SCALE_FLOOR)
    q = jnp.round(t32 / scale)
    return q.astype(jnp.int8), scale


def paged_attention_int8(q, kpg, kps, vpg, vps, block_table, kv_lens,
                         q_positions, *, scale, window, attn_softcap,
                         layer=None):
    """paged attention over int8 pages (codes kpg/vpg + scales kps/vps).

    jnp reference path: gathers the sequence's pages and runs the flash
    scan with ``k_scale``/``v_scale``, so dequant happens inside the
    blockwise loop (codes travel through HBM, floats never materialize
    per-page).  The Pallas TPU variant mirrors this exactly.  Pages are
    whole pools (``init_pages_int8``) read at ``layer``, or with ``layer``
    None one layer in the Pallas kernel's layout (codes [N, ps, KV_p, hd],
    scales [N, ps, KV_p, 1]), for its oracle.
    """
    d = q.shape[-1]
    k = gather_pages(kpg, block_table, layer, d)
    v = gather_pages(vpg, block_table, layer, d)
    ks = gather_pages(kps, block_table, layer, 1)
    vs = gather_pages(vps, block_table, layer, 1)
    B, Tk = k.shape[:2]
    kv_pos = jnp.broadcast_to(jnp.arange(Tk, dtype=jnp.int32)[None], (B, Tk))
    return flash_attention(
        q, k, v, q_positions=q_positions, kv_positions=kv_pos,
        kv_valid_len=kv_lens, scale=scale, causal=True, window=window,
        attn_softcap=attn_softcap, block_kv=min(512, Tk),
        k_scale=ks, v_scale=vs)


def quant_kv(k, v):
    """fp K/V rows -> ({"q", "s"}, {"q", "s"}) int8 code+scale dicts."""
    kq, ks = q8_kv(k)
    vq, vs = q8_kv(v)
    return {"q": kq, "s": ks}, {"q": vq, "s": vs}


def fake_quant_kv(t):
    """Quantize-dequantize ``t`` through the page representation.

    Applied to K/V at the attention input of the MONOLITHIC prefill
    (sequential mode computes the whole prompt in one shot) so its
    numerics match the streamed/chunked paths, which re-read earlier
    chunks from quantized pages: with it, every key any query attends
    to is the dequantized value in EVERY mode, and greedy int8 streams
    become chunk-invariant — bit-identical across serve modes and
    ``prefill_chunk``/``chunk_tokens`` settings, exactly like fp.
    Commit still quantizes the fp values: :func:`q8_kv` is idempotent
    (the maxabs element always maps to code 127, so requantizing the
    dequantized row reproduces the same codes and scale).
    """
    q, s = q8_kv(t)
    return (q.astype(jnp.float32) * s).astype(t.dtype)


def int8_decode_attn(q, k_new, v_new, kpg, vpg, layer, block_table, seq_lens,
                     active, *, scale, window, attn_softcap):
    """``default_decode_attn`` over int8 page dicts.

    q [B,1,H_p,hd]; k_new/v_new [B,KV_p,hd] fp; kpg/vpg ``{"q", "s"}``
    whole pools.  Quantizes the new token at write, attends with in-scan
    dequant.
    """
    kn, vn = quant_kv(k_new, v_new)
    kc, vc = write_kv_token(kpg["q"], vpg["q"], layer, kn["q"], vn["q"],
                            block_table, seq_lens, active)
    ksc, vsc = write_kv_token(kpg["s"], vpg["s"], layer, kn["s"], vn["s"],
                              block_table, seq_lens, active)
    kpg = {"q": kc, "s": ksc}
    vpg = {"q": vc, "s": vsc}
    o = paged_attention_int8(q, kpg["q"], kpg["s"], vpg["q"], vpg["s"],
                             block_table, seq_lens + 1, seq_lens[:, None],
                             scale=scale, window=window,
                             attn_softcap=attn_softcap, layer=layer)
    return o, kpg, vpg


def int8_chunk_attn(q, k_new, v_new, kpg, vpg, layer, block_table, start,
                    lens, *, scale, window, attn_softcap):
    """``default_chunk_attn`` over int8 page dicts.

    q [P,C,H_p,hd]; k_new/v_new [P,C,KV_p,hd] fp; kpg/vpg ``{"q", "s"}``
    whole pools.
    """
    kn, vn = quant_kv(k_new, v_new)
    kc, vc = write_kv_chunk(kpg["q"], vpg["q"], layer, kn["q"], vn["q"],
                            block_table, start, lens)
    ksc, vsc = write_kv_chunk(kpg["s"], vpg["s"], layer, kn["s"], vn["s"],
                              block_table, start, lens)
    kpg = {"q": kc, "s": ksc}
    vpg = {"q": vc, "s": vsc}
    C = q.shape[1]
    q_pos = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
    o = paged_attention_int8(q, kpg["q"], kpg["s"], vpg["q"], vpg["s"],
                             block_table, start + lens, q_pos,
                             scale=scale, window=window,
                             attn_softcap=attn_softcap, layer=layer)
    return o, kpg, vpg


def init_pages_int8(cfg, n_pages, page_size, tp=1, n_layers=None):
    """int8 page pools: ({"q", "s"}, {"q", "s"}) zero-initialized.

    Codes [L, N, ps, KV_p * hd] int8; scales [L, N, ps, KV_p] f32: a
    token's heads in one row, as in ``transformer.init_pages``.  Scales
    are floored — a zero-filled scale plane would make the all-zero pool
    rows un-representable, see :data:`SCALE_FLOOR`.
    """
    from repro.models.transformer import gqa_layout
    _, KV_p, _, _, _ = gqa_layout(cfg.n_heads, cfg.n_kv_heads, tp)
    L = n_layers if n_layers is not None else cfg.n_layers
    shape = (L, n_pages, page_size)
    k = {"q": jnp.zeros(shape + (KV_p * cfg.head_dim,), jnp.int8),
         "s": jnp.full(shape + (KV_p,), SCALE_FLOOR, jnp.float32)}
    v = {"q": jnp.zeros(shape + (KV_p * cfg.head_dim,), jnp.int8),
         "s": jnp.full(shape + (KV_p,), SCALE_FLOOR, jnp.float32)}
    return k, v


def kv_page_bytes(cfg, page_size, fp_dtype, *, kv_dtype="fp", tp=1):
    """Bytes ONE page costs in device memory (K + V, all layers).

    fp pages: ``2 * L * ps * KV_p * hd * itemsize``; int8 pages add the
    f32 scale sidecar per (token, head): ``2 * L * ps * KV_p * (hd + 4)``.
    This is the denominator for the byte-denominated pool: at equal pool
    bytes the int8 path yields ``hd*itemsize / (hd+4)`` times the pages.
    """
    from repro.models.transformer import gqa_layout
    _, KV_p, _, _, _ = gqa_layout(cfg.n_heads, cfg.n_kv_heads, tp)
    per_tok_head = (cfg.head_dim + 4 if kv_dtype == "int8"
                    else cfg.head_dim * jnp.dtype(fp_dtype).itemsize)
    return 2 * cfg.n_layers * page_size * KV_p * per_tok_head
