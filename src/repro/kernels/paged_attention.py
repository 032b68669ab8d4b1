"""Paged decode attention for TPU: K/V gathered through a block table.

The serving-side decode hot spot: each in-flight request (slot) owns a list
of fixed-size KV blocks (``serve/paged.py``'s ``BlockManager``) instead of a
contiguous ``max_len`` cache row. One query token per slot attends over the
blocks its table names.

Grid: (slot, kv-head, table-column) — one grid cell per (slot, kv-head), the
innermost dimension walking the slot's block table sequentially. Pallas TPU
executes grid steps in order on one core, so the running (m, l, acc) online-
softmax state lives in VMEM scratch and persists across table columns,
exactly like ``kernels/flash_attention.py``. The block table, per-slot
positions, and the sliding window are scalar-prefetch operands
(``pltpu.PrefetchScalarGridSpec``): the K/V ``BlockSpec`` index maps read the
table to DMA only the blocks the slot actually owns — unassigned entries
(-1 padding) are clamped to block 0 for the DMA and the cell is skipped via
``pl.when`` (online softmax over valid blocks only). GQA costs nothing extra:
the q-head group of each kv head rides along as the block's row dimension.

The pool is head-major, ``[NB, Hkv, BS, D]``, so one (block, kv head) tile
is the whole trailing ``(BS, D)`` slab. Mosaic requires the last two dims
of a block to be full or divisible by the (8, 128) tiling; a token-major
``[NB, BS, Hkv, D]`` pool would need a ``(BS, 1, D)`` block, whose size-1
head dim breaks that rule.

Two kernels share the scheme: the decode kernel (one query token per slot)
and the prefill kernel (a C-token chunk per slot at contiguous positions,
causal masking inside the chunk) — the latter is what lane-batched chunked
prefill dispatches instead of falling back to the jnp page gather.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(tables_ref, pos_ref, win_ref, q_ref, k_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref, *, scale: float, bs: int, nt: int,
            g: int):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    pos = pos_ref[b]
    # valid blocks only: the table column must be assigned AND start at or
    # before the row's current position.
    run = (tables_ref[b, j] >= 0) & (j * bs <= pos)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)              # [G, D]
        k = k_ref[0, 0].astype(jnp.float32)              # [BS, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (g, bs), 1)
        mask = k_pos <= pos
        win = win_ref[0]
        mask &= (win == 0) | (k_pos > pos - win)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        m_safe = jnp.where(m_cur <= NEG_INF / 2, 0.0, m_cur)
        p = jnp.exp(s - m_safe[:, None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0,
                          jnp.exp(m_prev - m_safe))
        l_cur = alpha * l_prev + jnp.sum(p, axis=1)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[:, 0] = m_cur
        l_ref[:, 0] = l_cur

    @pl.when(j == nt - 1)
    def _finish():
        l = l_ref[:, 0]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def _prefill_kernel(tables_ref, start_ref, win_ref, q_ref, k_ref, v_ref,
                    o_ref, acc_ref, m_ref, l_ref, *, scale: float, bs: int,
                    nt: int, g: int, c: int):
    """Multi-token sibling of ``_kernel``: one grid cell attends a whole
    [C, G] query chunk (C contiguous positions of one slot, every q head of
    one kv head) against one table column, with causal masking *inside* the
    chunk — query offset r // g at logical position start + r // g only sees
    k_pos <= its own position. The (m, l, acc) online-softmax state carries
    [C * G] rows across table columns."""
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    start = start_ref[b]
    # valid blocks only: assigned AND starting at or before the chunk's last
    # query position (later blocks hold nothing any query may attend).
    run = (tables_ref[b, j] >= 0) & (j * bs <= start + c - 1)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32).reshape(c * g, -1)   # [CG, D]
        k = k_ref[0, 0].astype(jnp.float32)                      # [BS, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_pos = start + jax.lax.broadcasted_iota(jnp.int32, (c * g, bs),
                                                 0) // g
        k_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (c * g, bs), 1)
        mask = k_pos <= q_pos
        win = win_ref[0]
        mask &= (win == 0) | (k_pos > q_pos - win)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        m_safe = jnp.where(m_cur <= NEG_INF / 2, 0.0, m_cur)
        p = jnp.exp(s - m_safe[:, None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0,
                          jnp.exp(m_prev - m_safe))
        l_cur = alpha * l_prev + jnp.sum(p, axis=1)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[:, 0] = m_cur
        l_ref[:, 0] = l_cur

    @pl.when(j == nt - 1)
    def _finish():
        l = l_ref[:, 0]
        l = jnp.where(l == 0.0, 1.0, l)
        d = acc_ref.shape[-1]
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).reshape(
            c, g, d).astype(o_ref.dtype)


def paged_prefill_bkgd(q, k_pages, v_pages, tables, start, window, *,
                       interpret: bool = True):
    """q: [B, Hkv, C, G, D] (a C-token prefill chunk per slot, q heads
    grouped per kv head); k_pages, v_pages: [NB, Hkv, BS, D]; tables:
    [B, MB] int32 (-1 = unassigned); start: [B] int32 — row b's chunk
    covers contiguous logical positions [start[b], start[b] + C); window:
    [1] int32 (0 = full attention). The chunk's K/V must already be written
    through the table (``layers.paged_kv_write`` runs first), so causal
    in-chunk attention reads it back from the pool like every earlier
    block. Returns [B, Hkv, C, G, D].
    """
    b, hkv, c, g, d = q.shape
    bs = k_pages.shape[2]
    mb = tables.shape[1]
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(_prefill_kernel, scale=scale, bs=bs, nt=mb,
                               g=g, c=c)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hkv, mb),
        in_specs=[
            pl.BlockSpec((1, 1, c, g, d),
                         lambda i, h, j, tables, start, win: (i, h, 0, 0, 0)),
            pl.BlockSpec((1, 1, bs, d),
                         lambda i, h, j, tables, start, win:
                         (jnp.maximum(tables[i, j], 0), h, 0, 0)),
            pl.BlockSpec((1, 1, bs, d),
                         lambda i, h, j, tables, start, win:
                         (jnp.maximum(tables[i, j], 0), h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, c, g, d),
                               lambda i, h, j, tables, start, win:
                               (i, h, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((c * g, d), jnp.float32),
            pltpu.VMEM((c * g, 1), jnp.float32),
            pltpu.VMEM((c * g, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, c, g, d), q.dtype),
        interpret=interpret,
        name="paged_prefill_attention",
    )(tables, start, window, q, k_pages, v_pages)


def paged_attention_bkgd(q, k_pages, v_pages, tables, pos, window, *,
                         interpret: bool = True):
    """q: [B, Hkv, G, D] (q heads grouped per kv head); k_pages, v_pages:
    [NB, Hkv, BS, D]; tables: [B, MB] int32 (-1 = unassigned); pos: [B]
    int32; window: [1] int32 (0 = full attention). Returns [B, Hkv, G, D].
    """
    b, hkv, g, d = q.shape
    bs = k_pages.shape[2]
    mb = tables.shape[1]
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(_kernel, scale=scale, bs=bs, nt=mb, g=g)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hkv, mb),
        in_specs=[
            pl.BlockSpec((1, 1, g, d),
                         lambda i, h, j, tables, pos, win: (i, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, d),
                         lambda i, h, j, tables, pos, win:
                         (jnp.maximum(tables[i, j], 0), h, 0, 0)),
            pl.BlockSpec((1, 1, bs, d),
                         lambda i, h, j, tables, pos, win:
                         (jnp.maximum(tables[i, j], 0), h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda i, h, j, tables, pos, win: (i, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(tables, pos, window, q, k_pages, v_pages)
