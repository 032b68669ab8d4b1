"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def attention(q, k, v, *, causal: bool = True, window: int = 0):
    """q: [B, Sq, Hq, D]; k, v: [B, Sk, Hkv, D] -> [B, Sq, Hq, D]."""
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, sq, hkv, g, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(d)
    sk = k.shape[1]
    q_pos = jnp.arange(sq)
    k_pos = jnp.arange(sk)
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window:
        mask &= q_pos[:, None] - k_pos[None, :] < window
    logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return out.reshape(b, sq, hq, d).astype(q.dtype)


def _gather_pages(pages, tables):
    """Head-major pages [NB, Hkv, BS, D] through tables [B, MB] -> f32
    [B, MB * BS, Hkv, D] in logical position order (-1 entries read block
    0; callers mask them)."""
    b = tables.shape[0]
    _, hkv, _, d = pages.shape
    g = pages[jnp.maximum(tables, 0)]                  # [B, MB, Hkv, BS, D]
    return g.transpose(0, 1, 3, 2, 4).reshape(b, -1, hkv, d).astype(
        jnp.float32)


def paged_attention(q, k_pages, v_pages, tables, pos, window=0):
    """Paged decode attention oracle (one query token per slot).

    q: [B, Hq, D]; k_pages, v_pages: [NB, Hkv, BS, D]; tables: [B, MB] int32
    block ids (-1 = unassigned); pos: [B] int32 — row b attends logical
    positions [0, pos[b]] gathered through its block table. -> [B, Hq, D].
    """
    nb, hkv, bs, d = k_pages.shape
    b, hq, _ = q.shape
    k = _gather_pages(k_pages, tables)
    v = _gather_pages(v_pages, tables)
    k_pos = jnp.arange(k.shape[1])[None, :]
    valid = jnp.repeat(tables >= 0, bs, axis=1) & (k_pos <= pos[:, None])
    if window:
        valid &= k_pos > pos[:, None] - window
    g = hq // hkv
    qg = q.reshape(b, hkv, g, d).astype(jnp.float32)
    logits = jnp.einsum("bhgd,bkhd->bhgk", qg, k) / math.sqrt(d)
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v)
    return out.reshape(b, hq, d).astype(q.dtype)


def paged_prefill_attention(q, k_pages, v_pages, tables, start, window=0):
    """Paged prefill-chunk attention oracle (C query tokens per slot).

    q: [B, C, Hq, D]; k_pages, v_pages: [NB, Hkv, BS, D]; tables: [B, MB]
    int32 block ids (-1 = unassigned); start: [B] int32 — row b's query c
    sits at logical position ``start[b] + c`` and attends positions
    [0, start[b] + c] gathered through its block table (causal inside the
    chunk). -> [B, C, Hq, D].
    """
    nb, hkv, bs, d = k_pages.shape
    b, c, hq, _ = q.shape
    k = _gather_pages(k_pages, tables)
    v = _gather_pages(v_pages, tables)
    k_pos = jnp.arange(k.shape[1])[None, None, :]                # [1, 1, K]
    q_pos = (start[:, None] + jnp.arange(c)[None, :])[:, :, None]  # [B, C, 1]
    valid = jnp.repeat(tables >= 0, bs, axis=1)[:, None, :] & (k_pos <= q_pos)
    if window:
        valid &= k_pos > q_pos - window
    g = hq // hkv
    qg = q.reshape(b, c, hkv, g, d).astype(jnp.float32)
    logits = jnp.einsum("bchgd,bkhd->bhgck", qg, k) / math.sqrt(d)
    logits = jnp.where(valid[:, None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgck,bkhd->bchgd", p, v)
    return out.reshape(b, c, hq, d).astype(q.dtype)


def ssd(xdt, a_log, B, C):
    """Naive sequential SSD recurrence (the semantic ground truth).

    xdt: [B, S, H, P]; a_log: [B, S, H]; B, C: [B, S, H, N] -> [B, S, H, P]
        h_t = exp(a_log_t) * h_{t-1} + B_t (x) xdt_t;   y_t = C_t . h_t
    """
    b, s, h, p = xdt.shape
    n = B.shape[-1]

    def step(state, inp):
        x_t, a_t, b_t, c_t = inp
        state = (jnp.exp(a_t)[..., None, None] * state
                 + jnp.einsum("bhn,bhp->bhnp", b_t, x_t))
        y_t = jnp.einsum("bhn,bhnp->bhp", c_t, state)
        return state, y_t

    init = jnp.zeros((b, h, n, p), jnp.float32)
    xs = (xdt.swapaxes(0, 1), a_log.swapaxes(0, 1),
          B.swapaxes(0, 1), C.swapaxes(0, 1))
    _, ys = jax.lax.scan(step, init, xs)
    return ys.swapaxes(0, 1)


def grouped_matmul(x, w, valid_rows=None):
    """x: [G, C, K]; w: [G, K, N] -> [G, C, N]; invalid rows zeroed."""
    out = jnp.einsum("gck,gkn->gcn", x, w)
    if valid_rows is not None:
        c = x.shape[1]
        mask = jnp.arange(c)[None, :] < valid_rows[:, None]
        out = out * mask[..., None].astype(out.dtype)
    return out
