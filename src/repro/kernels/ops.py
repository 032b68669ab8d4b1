"""Jitted public wrappers for the Pallas kernels.

Each wrapper handles layout (head flattening, padding to block multiples),
dtype promotion, and backend selection: on CPU the kernels execute in
``interpret=True`` mode (Python emulation of the kernel body — the
correctness path used by CI); on TPU they compile to Mosaic; any other
backend raises.

Every ``pallas_call`` carries an explicit ``name=`` (``paged_attention``,
``paged_prefill_attention``, ``ssd_scan``, ``flash_attention``,
``grouped_matmul``). On the chip it names the custom call, and so the op a
device trace shows (``%paged_attention.12 = ...``), whatever jitted function
the kernel is traced from.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import grouped_matmul as _gmm
from repro.kernels import paged_attention as _pa
from repro.kernels import ssd_scan as _ssd


def _interpret() -> bool:
    """Interpret on the CPU (the correctness path of the tests), compile
    to Mosaic on a TPU. Any other backend is refused rather than silently
    interpreted, which would time the interpreter as if it were the
    kernel."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"Pallas TPU kernels cannot run on backend "
                       f"{backend!r}; use cpu (interpret) or tpu")


def _pad_to(x, axis: int, mult: int):
    s = x.shape[axis]
    pad = (-s) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 128, bk: int = 128):
    """q: [B, S, Hq, D]; k, v: [B, S, Hkv, D] -> [B, S, Hq, D]."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    qf = q.transpose(0, 2, 1, 3).reshape(b * hq, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    blk = min(bq, bk, max(8, s))
    qf, pad = _pad_to(qf, 1, blk)
    kf, _ = _pad_to(kf, 1, blk)
    vf, _ = _pad_to(vf, 1, blk)
    # padded key rows must never be attended: causal masking covers q<=s rows
    # only when causal; otherwise mask via window? -> mask by slicing output
    # and padding k with -inf-free zeros is safe because padded q rows are
    # discarded and padded k rows get zero weight only under causal; for
    # non-causal inputs we require s % blk == 0 (wrapper asserts).
    if not causal and pad:
        raise ValueError("non-causal flash attention requires S % block == 0")
    out = _fa.flash_attention_bhsd(qf, kf, vf, causal=causal, window=window,
                                   bq=min(bq, qf.shape[1]),
                                   bk=min(bk, kf.shape[1]),
                                   interpret=_interpret())
    out = out[:, :s].reshape(b, hq, s, d).transpose(0, 2, 1, 3)
    return out


@jax.jit
def paged_attention(q, k_pages, v_pages, tables, pos, window=0):
    """q: [B, Hq, D]; k_pages, v_pages: [NB, Hkv, BS, D]; tables: [B, MB]
    int32 block ids (-1 = unassigned); pos: [B] int32; window: int32 scalar
    (0 = full attention; dynamic — gemma3's per-layer windows are traced).
    Returns [B, Hq, D]. Q heads are grouped per kv head (head h -> kv h//g,
    groups contiguous — the ``init_attention`` layout), so GQA needs no KV
    repetition in HBM.
    """
    b, hq, d = q.shape
    hkv = k_pages.shape[1]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, d)
    win = jnp.asarray(window, jnp.int32).reshape(1)
    out = _pa.paged_attention_bkgd(qg, k_pages, v_pages,
                                   jnp.asarray(tables, jnp.int32),
                                   jnp.asarray(pos, jnp.int32), win,
                                   interpret=_interpret())
    return out.reshape(b, hq, d)


@jax.jit
def paged_prefill_attention(q, k_pages, v_pages, tables, start, window=0):
    """q: [B, C, Hq, D] — one C-token prefill chunk per slot, row b's query
    c at logical position ``start[b] + c``; k_pages, v_pages:
    [NB, Hkv, BS, D]; tables: [B, MB] int32 block ids (-1 = unassigned);
    start: [B] int32; window: int32 scalar (0 = full; dynamic — gemma3's
    per-layer windows are traced). Returns [B, C, Hq, D]. The chunk's own
    K/V must already be written through the table (the layer writes before
    attending), so causal in-chunk attention reads it from the pool. Q
    heads group per kv head as in ``paged_attention``.
    """
    b, c, hq, d = q.shape
    hkv = k_pages.shape[1]
    g = hq // hkv
    qg = q.reshape(b, c, hkv, g, d).transpose(0, 2, 1, 3, 4)
    win = jnp.asarray(window, jnp.int32).reshape(1)
    out = _pa.paged_prefill_bkgd(qg, k_pages, v_pages,
                                 jnp.asarray(tables, jnp.int32),
                                 jnp.asarray(start, jnp.int32), win,
                                 interpret=_interpret())
    return out.transpose(0, 2, 1, 3, 4).reshape(b, c, hq, d)


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(xdt, a_log, B, C, *, chunk: int = 128):
    """xdt: [B, S, H, P]; a_log: [B, S, H]; B, C: [B, S, H, N].

    Any S: the sequence is zero-padded at its end to a multiple of the
    chunk (a prompt shorter than ``chunk`` uses one chunk of S rounded up
    to the 8-row sublane tile). Causality keeps the padding out of every
    real position, and Mosaic needs the chunk rows tile-aligned."""
    b, s, h, p = xdt.shape
    n = B.shape[-1]
    q = min(chunk, -(-s // 8) * 8)
    sp = -(-s // q) * q

    def heads_major(x, width):
        x = x.reshape(b, s, h, width).transpose(0, 2, 1, 3)
        x = jnp.pad(x, ((0, 0), (0, 0), (0, sp - s), (0, 0)))
        return x.reshape(b * h, sp, width).astype(jnp.float32)

    y = _ssd.ssd_scan_bhsp(heads_major(xdt, p), heads_major(a_log, 1),
                           heads_major(B, n), heads_major(C, n), chunk=q,
                           interpret=_interpret())
    return y.reshape(b, h, sp, p)[:, :, :s].transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def grouped_matmul(x, w, valid_rows=None, *, bm: int = 128, bn: int = 128,
                   bk: int = 128):
    """x: [G, C, K]; w: [G, K, N]; valid_rows: [G] int32 or None."""
    g, c, k = x.shape
    n = w.shape[-1]
    bm = _shrink(c, bm)
    bn = _shrink(n, bn)
    bk2 = _shrink(k, bk)
    out = _gmm.grouped_matmul(x, w, valid_rows, bm=bm, bn=bn, bk=bk2,
                              interpret=_interpret())
    if valid_rows is not None:
        mask = jnp.arange(c)[None, :] < valid_rows[:, None]
        out = out * mask[..., None].astype(out.dtype)
    return out


def _shrink(dim: int, blk: int) -> int:
    blk = min(blk, dim)
    while dim % blk != 0:
        blk //= 2
    return max(blk, 1)
