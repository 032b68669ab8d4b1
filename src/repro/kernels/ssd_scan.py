"""Mamba2 SSD chunked scan kernel for TPU.

The SSD block decomposition (arXiv:2405.21060 §6) maps naturally onto the
Pallas TPU execution model: the grid's inner dimension walks chunks of the
sequence IN ORDER, so the inter-chunk state S in R^{N x P} is carried in VMEM
scratch between grid steps — the TPU-native replacement for the CUDA
kernel's warp-level state exchange. Per chunk (length Q):

    intra:  Y += ((C B^T) .* L) X        (dual/attention quadratic form, MXU)
    inter:  Y += (C * exp(lc)) S_prev    (read carried state)
    state:  S  = gamma * S_prev + (B * w)^T X

All math in f32: every matmul runs at ``Precision.HIGHEST``, since the MXU
would otherwise round the f32 operands (decay-weighted scores, carried
state) to bf16. Block shapes (Q x N), (Q x P) are MXU-aligned for Q,N,P in
{64,128,256}.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_F32 = dict(preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)


def _kernel(x_ref, a_ref, b_ref, c_ref, y_ref, state_ref, *, q: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0]                       # [Q, P] (dt-scaled inputs)
    a = a_ref[0, :, 0]                 # [Q]    (log decay)
    b = b_ref[0]                       # [Q, N]
    c = c_ref[0]                       # [Q, N]

    idx = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jdx = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    causal = idx >= jdx
    # within-chunk cumulative log decay, lc_i = sum_{j<=i} a_j, as a masked
    # lower-triangular row sum: Mosaic has no cumsum lowering, and an f32
    # reduction on the vector unit keeps full precision where a matmul on
    # the MXU would round the decays through bf16 passes.
    lc = jnp.sum(jnp.where(causal, a[None, :], 0.0), axis=1)
    l_last = lc[q - 1]

    # intra-chunk dual form
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 **_F32)                              # [Q,Q]
    diff = lc[:, None] - lc[None, :]
    decay = jnp.exp(jnp.minimum(diff, 0.0))
    m = jnp.where(causal, scores * decay, 0.0)
    y = jax.lax.dot_general(m, x, (((1,), (0,)), ((), ())), **_F32)  # [Q,P]

    # inter-chunk contribution from carried state
    c_in = c * jnp.exp(lc)[:, None]
    y += jax.lax.dot_general(c_in, state_ref[...], (((1,), (0,)), ((), ())),
                             **_F32)

    # state update: S = gamma * S_prev + sum_j w_j B_j x_j^T
    w = jnp.exp(l_last - lc)                                           # [Q]
    bw = b * w[:, None]
    state_ref[...] = (jnp.exp(l_last) * state_ref[...]
                      + jax.lax.dot_general(bw, x, (((0,), (0,)), ((), ())),
                                            **_F32))
    y_ref[0] = y.astype(y_ref.dtype)


def ssd_scan_bhsp(xdt, a_log, B, C, *, chunk: int = 128,
                  interpret: bool = True):
    """xdt: [BH, S, P]; a_log: [BH, S, 1]; B, C: [BH, S, N] -> y [BH, S, P].

    Heads flattened into dim 0; the wrapper in ops.py does the transpose.
    """
    bh, s, p = xdt.shape
    n = B.shape[-1]
    q = min(chunk, s)
    assert s % q == 0, (s, q)
    nc = s // q

    kernel = functools.partial(_kernel, q=q)
    return pl.pallas_call(
        kernel,
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, q, p), lambda b, c_: (b, c_, 0)),
            pl.BlockSpec((1, q, 1), lambda b, c_: (b, c_, 0)),
            pl.BlockSpec((1, q, n), lambda b, c_: (b, c_, 0)),
            pl.BlockSpec((1, q, n), lambda b, c_: (b, c_, 0)),
        ],
        out_specs=pl.BlockSpec((1, q, p), lambda b, c_: (b, c_, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, p), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
        name="ssd_scan",
    )(xdt, a_log, B, C)
