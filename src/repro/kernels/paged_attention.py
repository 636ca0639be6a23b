"""Pallas TPU kernel: decode attention over the paged KV pool as stored.

One query token per decode row attends to that row's live pages, read
straight from the whole pools ``[L, N, ps, KV_p * hd]`` (``init_pages``)
at ``[layer, block_table[b, i]]``: a page is one ``(ps, KV_p * hd)``
block of a token's heads laid out as one flat row, so nothing is
gathered, sliced or relaid out first.

Layout:
  q           [B, H_p, KV_p * hd]  each query head's vector placed in the
                                   lanes of its KV head, zeros elsewhere
                                   (block-diagonal; ``ops`` builds it)
  k/v pools   [L, N, ps, KV_p * hd] left in HBM (``pl.ANY``)
  scalars     layer [1], block_table [B * Pmax] (flat), kv_lens [B],
              window [1], prefetched into SMEM
  o           [B, H_p, KV_p * hd]  P.V over the whole row; head h's output
                                   is its own KV head's lanes (``ops``
                                   keeps those)

Grid (B,): one step per decode row, in order. Inside a row the kernel
walks the blocks of pages that hold positions the query can see:
``cdiv(kv_len, ps)`` pages, from the first block the layer's window
reaches. Each block is ``pages_per_block`` pages copied page by page into
one of two VMEM buffers while the previous block computes; the last block
of a row starts the next row's first. A row with ``kv_len`` 0 copies and
computes nothing and returns zeros.

Heads: one MXU product of the block-diagonal query with the block's rows
gives every head's logits ``[H_p, tokens]`` at once, and one product of
the probabilities with the block gives ``[H_p, KV_p * hd]``. That is
``KV_p`` times the useful MXU work, which stays far below the ridge: each
byte read is used about once. Softmax is online, in f32; K and V enter
the MXU in their stored dtype with f32 accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# VMEM bytes of one block of K (and of V) pages: large enough that the
# per-block work (waits, softmax bookkeeping) is small beside its copy
BLOCK_BYTES = 512 * 1024


def pages_per_block(page_size: int, row: int, itemsize: int,
                    max_pages: int) -> int:
    """Pages of one block: the largest power of two whose K block fits
    ``BLOCK_BYTES``, at most a row's table."""
    n = max(1, BLOCK_BYTES // (page_size * row * itemsize))
    return min(1 << (n.bit_length() - 1), max_pages)


def _kernel(
    # scalar prefetch (SMEM)
    layer_ref,          # [1]
    table_ref,          # [B * Pmax]
    lens_ref,           # [B] visible KV length (0: dead row)
    window_ref,         # [1]
    # operands
    q_ref,              # [1, H_p, R] VMEM
    k_hbm, v_hbm,       # [L, N, ps, R] HBM
    o_ref,              # [1, H_p, R] VMEM
    # scratch
    kbuf, vbuf,         # [2, T, R] VMEM, T = pages_per_block * ps
    sems,               # DMA [2 (k, v), 2 (slot)]
    count_ref,          # SMEM [1]: blocks copied by earlier rows
    *,
    scale: float,
    softcap: float | None,
    page_size: int,
    block_pages: int,
    max_pages: int,
):
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    layer = layer_ref[0]
    window = window_ref[0]
    block_tokens = block_pages * page_size

    @pl.when(b == 0)
    def _init():
        count_ref[0] = 0
        # pages past a row's end are never copied into a block; what the
        # buffers hold there must be finite, as 0 x NaN is NaN
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    def extent(row):
        """(first block, number of blocks, live pages) of ``row``."""
        kv_len = lens_ref[row]
        n_pages = (kv_len + page_size - 1) // page_size
        first = jnp.maximum(kv_len - window, 0) // block_tokens
        last = (n_pages + block_pages - 1) // block_pages
        return first, jnp.maximum(last - first, 0), n_pages

    def page_copies(slot, j, page):
        return (pltpu.make_async_copy(
                    k_hbm.at[layer, page],
                    kbuf.at[slot, pl.ds(j * page_size, page_size)],
                    sems.at[0, slot]),
                pltpu.make_async_copy(
                    v_hbm.at[layer, page],
                    vbuf.at[slot, pl.ds(j * page_size, page_size)],
                    sems.at[1, slot]))

    def block_len(n_pages, blk):
        return jnp.minimum(block_pages, n_pages - blk * block_pages)

    def fetch(row, blk, n_pages, slot):
        base = row * max_pages + blk * block_pages

        def start(j, carry):
            for cp in page_copies(slot, j, table_ref[base + j]):
                cp.start()
            return carry
        jax.lax.fori_loop(0, block_len(n_pages, blk), start, 0)

    def wait(n, slot):
        def one(j, carry):
            for cp in page_copies(slot, 0, 0):
                cp.wait()
            return carry
        jax.lax.fori_loop(0, n, one, 0)

    first, n_blocks, n_pages = extent(b)
    _, prev_blocks, _ = extent(jnp.maximum(b - 1, 0))
    nxt = jnp.minimum(b + 1, n_rows - 1)
    next_first, next_blocks, next_pages = extent(nxt)
    next_live = (b + 1 < n_rows) & (next_blocks > 0)
    # the previous row started this row's first block if both are live
    prefetched = (b > 0) & (prev_blocks > 0)
    count = count_ref[0]

    @pl.when((n_blocks > 0) & jnp.logical_not(prefetched))
    def _first():
        fetch(b, first, n_pages, count % 2)

    q = q_ref[0]                                          # [H_p, R]
    kv_len = lens_ref[b]

    def body(i, carry):
        m, l, acc = carry
        blk = first + i
        slot = (count + i) % 2

        @pl.when(i + 1 < n_blocks)
        def _next_block():
            fetch(b, blk + 1, n_pages, 1 - slot)

        @pl.when((i + 1 == n_blocks) & next_live)
        def _next_row():
            fetch(nxt, next_first, next_pages, 1 - slot)

        wait(block_len(n_pages, blk), slot)
        k = kbuf[slot]                                    # [T, R]
        v = vbuf[slot]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [H_p, T]
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        pos = blk * block_tokens + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        # causal at q_pos = kv_len - 1, and the window's first position
        mask = (pos < kv_len) & (pos >= kv_len - window)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [H_p, R]
        return m_new, l, acc * corr + pv

    H, R = q.shape
    init = (jnp.full((H, 1), NEG_INF, jnp.float32),
            jnp.zeros((H, 1), jnp.float32),
            jnp.zeros((H, R), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, n_blocks, body, init)
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    count_ref[0] = count + n_blocks


def paged_decode_attention(
    q,                  # [B, H_p, R] block-diagonal, R = KV_p * hd
    k_pages, v_pages,   # [L, N, ps, R]
    layer,              # [] int32
    block_table,        # [B, Pmax] int32
    kv_lens,            # [B] int32: 0 for a dead row
    window,             # [] int32
    *,
    scale: float,
    softcap=None,
    interpret: bool = False,
):
    """Returns o [B, H_p, R] in q's dtype: P.V over the whole row."""
    # argument contract: shape/dtype mistakes die here with a message,
    # not as an opaque Mosaic lowering error
    if q.ndim != 3:
        raise ValueError(f"q must be [B, H_p, KV_p * hd], got {q.shape}")
    B, H, R = q.shape
    if k_pages.ndim != 4 or k_pages.shape != v_pages.shape:
        raise ValueError(
            f"k_pages/v_pages must share shape [L, N, ps, KV_p * hd], got "
            f"{k_pages.shape} vs {v_pages.shape}")
    L, N, ps, row = k_pages.shape
    if row != R:
        raise ValueError(f"pool row {row} disagrees with q's row {R}")
    if q.dtype != k_pages.dtype or k_pages.dtype != v_pages.dtype:
        raise ValueError(
            f"q/k_pages/v_pages dtypes must match, got {q.dtype}/"
            f"{k_pages.dtype}/{v_pages.dtype}")
    if block_table.ndim != 2 or block_table.shape[0] != B:
        raise ValueError(
            f"block_table must be [B={B}, Pmax], got {block_table.shape}")
    if kv_lens.shape != (B,) or jnp.shape(layer) or jnp.shape(window):
        raise ValueError(
            f"kv_lens must be [B={B}] and layer/window scalars, got "
            f"{kv_lens.shape} / {jnp.shape(layer)} / {jnp.shape(window)}")
    for name, arr in (("layer", layer), ("block_table", block_table),
                      ("kv_lens", kv_lens), ("window", window)):
        if not jnp.issubdtype(jnp.asarray(arr).dtype, jnp.integer):
            raise ValueError(f"{name} must be integer-typed")
    Pmax = block_table.shape[1]
    n = pages_per_block(ps, R, k_pages.dtype.itemsize, Pmax)

    def row_map(b, *_):
        return (b, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, R), row_map),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, R), row_map),
        scratch_shapes=[
            pltpu.VMEM((2, n * ps, R), k_pages.dtype),
            pltpu.VMEM((2, n * ps, R), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    kernel = functools.partial(
        _kernel, scale=float(scale),
        softcap=None if softcap is None else float(softcap),
        page_size=ps, block_pages=n, max_pages=Pmax)
    i32 = lambda a: jnp.asarray(a, jnp.int32)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, R), q.dtype),
        # rows run in order: a row's last block starts the next row's first
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(i32(layer).reshape(1), i32(block_table).reshape(-1), i32(kv_lens),
      i32(window).reshape(1), q, k_pages, v_pages)
