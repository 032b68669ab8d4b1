"""Blocked online-softmax attention (flash attention) for TPU.

TPU adaptation (vs. the CUDA original): the grid's innermost dimension walks
K/V blocks sequentially — Pallas TPU executes grid steps in order on one
core, so the running (m, l, acc) softmax state lives in VMEM scratch and
persists across K-blocks (no atomics / shared-memory tricks needed). Block
shapes are MXU-aligned (seq blocks multiples of 128, full head_dim per
block); the working set per step is q(bq x D) + k,v(bk x D) + acc(bq x D)
floats — sized to sit comfortably in the ~16 MB VMEM.

Supports causal masking, sliding windows (gemma3 local layers) and GQA (KV
block index maps q-head -> kv-head, no KV repetition in HBM).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: int, bq: int, bk: int,
            nk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    # whole-block skip conditions (causal / out-of-window)
    run = jnp.bool_(True)
    if causal:
        run &= ki * bk <= qi * bq + bq - 1
    if window:
        run &= (ki + 1) * bk - 1 > qi * bq - window

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= q_pos >= k_pos
        if window:
            mask &= q_pos - k_pos < window
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

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[:, 0]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal: bool = True, window: int = 0,
                         bq: int = 128, bk: int = 128,
                         interpret: bool = True):
    """q: [BHq, S, D]; k, v: [BHkv, S, D] — heads flattened into dim 0.

    Returns [BHq, S, D]. GQA handled via the KV index map (group = BHq/BHkv).
    """
    bh, s, d = q.shape
    bh_kv = k.shape[0]
    group = bh // bh_kv
    bq = min(bq, s)
    bk = min(bk, s)
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    nq, nk = s // bq, s // bk
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               window=window, bq=bq, bk=bk, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
