"""Grouped (per-expert) matmul kernel for MoE FFNs on TPU.

Computes out[g] = x[g] @ w[g] for G expert groups with capacity-layout
activations x: [G, C, K] and per-expert weights w: [G, K, N]. Blocked over
(C, N, K) with an f32 VMEM accumulator; K is the innermost grid dimension so
the accumulator persists across K-blocks (sequential TPU grid), exactly like
the flash-attention state carry.

``valid_rows`` (tokens actually routed to each expert, <= capacity) lets the
kernel skip fully-empty row blocks — the TPU analogue of megablocks' ragged
GEMM: instead of CUDA block-sparse tiles we prune whole grid steps with
pl.when, which the sequential grid makes free. It is a scalar-prefetch
operand (SMEM): Mosaic refuses a rank-1 ``(1,)`` VMEM block for it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(valid_ref, x_ref, w_ref, o_ref, acc_ref, *, bm: int, nk: int):
    mi = pl.program_id(1)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = mi * bm < valid_ref[pl.program_id(0)]   # any valid row here?

    @pl.when(run)
    def _body():
        x = x_ref[0]
        w = w_ref[0]
        acc_ref[...] += jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def grouped_matmul(x, w, valid_rows=None, *, bm: int = 128, bn: int = 128,
                   bk: int = 128, interpret: bool = True):
    """x: [G, C, K]; w: [G, K, N]; valid_rows: [G] int32 (None = all valid)."""
    g, c, k = x.shape
    n = w.shape[-1]
    bm, bn, bk = min(bm, c), min(bn, n), min(bk, k)
    assert c % bm == 0 and n % bn == 0 and k % bk == 0, (c, n, k, bm, bn, bk)
    if valid_rows is None:
        valid_rows = jnp.full((g,), c, jnp.int32)
    nk = k // bk

    kernel = functools.partial(_kernel, bm=bm, nk=nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(g, c // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda gi, mi, ni, ki, vr: (gi, mi, ki)),
            pl.BlockSpec((1, bk, bn), lambda gi, mi, ni, ki, vr: (gi, ki, ni)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn),
                               lambda gi, mi, ni, ki, vr: (gi, mi, ni)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((g, c, n), x.dtype),
        interpret=interpret,
        name="grouped_matmul",
    )(jnp.asarray(valid_rows, jnp.int32), x, w)
