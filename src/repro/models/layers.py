"""Core layer library: GQA attention (RoPE / sinusoidal, sliding window, QKV
bias), SwiGLU MLP, RMSNorm / LayerNorm, embeddings.

All layers are pure functions over parameter dicts; initialization functions
return plain dict pytrees so layers can be stacked (``jax.lax.scan`` over a
leading layer axis) without framework machinery.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.dist.sharding import shard


def scan_layers(cfg, body, carry, xs):
    """lax.scan over stacked layers, or an unrolled Python loop when
    ``cfg.unroll`` (used by the dry-run's flop probes — XLA cost_analysis
    counts while-loop bodies exactly once, so probes must unroll)."""
    if not cfg.unroll:
        return jax.lax.scan(body, carry, xs)
    n = jax.tree_util.tree_leaves(xs)[0].shape[0]
    outs = []
    for i in range(n):
        sl = jax.tree_util.tree_map(lambda a: a[i], xs)
        carry, out = body(carry, sl)
        outs.append(out)
    if all(o is None for o in outs):
        return carry, None
    stacked = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *outs)
    return carry, stacked


def scan_paged_layers(cfg, body, x, pool, xs):
    """``scan_layers`` with the paged K/V pool (``{"k", "v"}`` leaves
    ``[L, NB, Hkv, BS, D]``) in the carry instead of the scanned inputs and
    outputs: ``body(x, pool, layer, xs_l) -> (x, pool, ys_l)``, ``layer``
    the int32 index of the layer that ``xs_l`` slices. Scanned as xs and
    ys, every pass would slice each layer's slab out and stack a new pool
    back up; carried, each layer writes its K/V into the one pool buffer
    in place (``paged_kv_write``). Returns (x, pool, ys)."""
    def step(carry, scanned):
        x, pool = carry
        layer, xs_l = scanned
        x, pool, ys_l = body(x, pool, layer, xs_l)
        return (x, pool), ys_l

    layers = jnp.arange(jax.tree_util.tree_leaves(xs)[0].shape[0],
                        dtype=jnp.int32)
    (x, pool), ys = scan_layers(cfg, step, (x, pool), (layers, xs))
    return x, pool, ys


def _init_dense(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def rmsnorm(x, weight, eps: float):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + weight.astype(jnp.float32))).astype(dtype)


def init_rmsnorm(d: int, dtype) -> jax.Array:
    return jnp.zeros((d,), dtype)          # stored as (1 + w) offset form


# ---------------------------------------------------------------------------
# Positional embeddings
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: [B, S, H, D]; positions: [B, S] (int)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                                 # [D/2]
    angles = positions[..., None].astype(jnp.float32) * freqs    # [B, S, D/2]
    cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos_emb(max_len: int, d: int) -> jax.Array:
    pos = jnp.arange(max_len, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, d, 2, dtype=jnp.float32) * (-math.log(10000.0) / d))
    pe = jnp.zeros((max_len, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------
def init_attention(key, cfg, dtype) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nh, nkv = cfg.n_heads, cfg.n_kv_heads
    nhe = cfg.n_heads_eff
    ks = jax.random.split(key, 4)
    wq = _init_dense(ks[0], (d, nh * hd), dtype)
    wo = _init_dense(ks[3], (nh * hd, d), dtype)
    if nhe > nh:
        # Head padding (perf knob): extra Q heads whose wo rows are zero, so
        # the function is unchanged at init while heads shard evenly. Padding
        # must go INSIDE each KV group (head h maps to kv h // g), so pad the
        # per-group head count g -> g_new and keep groups contiguous.
        assert nh % nkv == 0 and nhe % nkv == 0, (nh, nhe, nkv)
        g_old, g_new = nh // nkv, nhe // nkv
        wq4 = wq.reshape(d, nkv, g_old, hd)
        wq4 = jnp.pad(wq4, ((0, 0), (0, 0), (0, g_new - g_old), (0, 0)))
        wq = wq4.reshape(d, nhe * hd)
        wo4 = wo.reshape(nkv, g_old, hd, d)
        wo4 = jnp.pad(wo4, ((0, 0), (0, g_new - g_old), (0, 0), (0, 0)))
        wo = wo4.reshape(nhe * hd, d)
    p = {
        "wq": wq,
        "wk": _init_dense(ks[1], (d, nkv * hd), dtype),
        "wv": _init_dense(ks[2], (d, nkv * hd), dtype),
        "wo": wo,
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nhe * hd,), dtype)
        p["bk"] = jnp.zeros((nkv * hd,), dtype)
        p["bv"] = jnp.zeros((nkv * hd,), dtype)
    return p


#: sentinel for "derive the attention scheme locally" (legacy call sites);
#: layer entry points thread ONE scheme per layer instead (ROADMAP item #4).
_DERIVE = object()


def plan_attention_scheme(cfg, b: int, s: int, kv_len: int):
    """Derive the single attention scheme for one layer call.

    The head count handed to ``attention_scheme`` is the one the score einsum
    actually contracts over — pre-repeat KV heads under ``gqa_no_repeat``,
    effective (padded) Q heads otherwise — and ``kv_len`` is the attended
    length (cache length in decode, sequence length in prefill). Deriving
    once here and passing the scheme down guarantees the q/kv layouts agree
    at every constraint site within the layer.
    """
    from repro.dist.sharding import attention_scheme
    nh, nkv = cfg.n_heads_eff, cfg.n_kv_heads
    g = nh // max(nkv, 1)
    heads = nkv if (cfg.gqa_no_repeat and g > 1) else nh
    return attention_scheme(b, s, heads, kv_len)


def _qkv(p, cfg, x, scheme=_DERIVE):
    from repro.dist.sharding import attention_scheme, current_rules, shard_spec
    b, s, _ = x.shape
    hd, nh, nkv = cfg.resolved_head_dim, cfg.n_heads_eff, cfg.n_kv_heads
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, nh, hd)
    k = k.reshape(b, s, nkv, hd)
    v = v.reshape(b, s, nkv, hd)
    # Constrain IMMEDIATELY after the head reshape: downstream elementwise ops
    # (RoPE) must run on the final layout, or SPMD inserts replicate-reshard
    # pairs ("involuntary full rematerialization").
    if scheme is _DERIVE:
        scheme = attention_scheme(b, s, nh, s)
    rules = current_rules()
    if scheme is not None:
        q = shard_spec(q, scheme["q"])
        kv_spec = scheme["kv"]
        # pre-repeat KV: drop the head axis if nkv is not divisible
        parts = list(kv_spec)
        if parts[2] is not None and nkv % rules.axis_size(parts[2]) != 0:
            parts[2] = None
        k = shard_spec(k, jax.sharding.PartitionSpec(*parts))
        v = shard_spec(v, jax.sharding.PartitionSpec(*parts))
    return q, k, v


def attention_weights_mask(q_pos, k_pos, *, causal: bool,
                           window: int = 0):
    """Boolean mask [.., Sq, Sk]: True = attend."""
    mask = jnp.ones(q_pos.shape[-1:] + k_pos.shape[-1:], bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window:
        mask &= q_pos[:, None] - k_pos[None, :] < window
    return mask


def mha(q, k, v, mask, *, use_pallas: bool = False, causal: bool = False,
        window: int = 0, no_repeat: bool = False, scheme=_DERIVE):
    """Grouped-query attention core.

    q: [B, Sq, Hq, D], k/v: [B, Sk, Hkv, D], mask broadcastable to [Sq, Sk]
    (or [B, 1, 1, Sk] for per-row decode positions).

    KV heads are repeated to the full head count before the score einsum so
    the head dimension shards cleanly over the 'model' mesh axis (GQA head
    counts rarely divide it). The sharding scheme (heads / extra-batch /
    q-seq) is threaded in from the layer entry point (one scheme per layer);
    legacy callers that omit it get a locally derived one — see
    dist.sharding.attention_scheme.
    """
    from repro.dist.sharding import attention_scheme, shard_spec

    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    if use_pallas:
        from repro.kernels import ops as kops
        return kops.flash_attention(q, k, v, causal=causal, window=window)
    g = hq // hkv
    no_repeat = no_repeat and g > 1
    if g > 1 and not no_repeat:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    if scheme is _DERIVE:
        scheme = attention_scheme(b, sq, hkv if no_repeat else hq, k.shape[1])
    if scheme is not None:
        k = shard_spec(k, scheme["kv"])
        v = shard_spec(v, scheme["kv"])
    scale = 1.0 / math.sqrt(d)
    if no_repeat:
        # grouped einsum: KV stays at hkv heads (sharded over 'model'), no
        # repeat materialization/reshard of the cache (decode perf knob).
        qg = q.reshape(b, sq, hkv, g, d)
        if scheme is not None:
            qs = scheme["q"]
            qg = shard_spec(qg, jax.sharding.PartitionSpec(
                qs[0], qs[1], qs[2], None, None))
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32) * scale
        if mask is not None:
            if mask.ndim == 4:                      # [B, 1|H, 1|Q, K]
                m5 = mask[:, :, None]
            elif mask.ndim >= 3:
                m5 = mask
            else:
                m5 = mask[None]
            logits = jnp.where(m5, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
        out = out.reshape(b, sq, hq, d)
        if scheme is not None:
            out = shard_spec(out, scheme["q"])
        return out
    if scheme is not None:
        q = shard_spec(q, scheme["q"])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if scheme is not None:
        logits = shard_spec(logits, scheme["logits"])
    if mask is not None:
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    if scheme is not None:
        out = shard_spec(out, scheme["q"])
    return out


# ---------------------------------------------------------------------------
# Paged decode-attention backend
#
# The serving mirror of Synergy's memory-sensitivity claim: a request holds
# ceil(len / block_size) fixed-size KV blocks behind a per-request block
# table instead of a full max_len cache row (serve/paged.py manages the
# pool). The layer-level backend is selected per layer next to
# plan_attention_scheme: "contiguous" threads the classic (ck, cv) cache,
# "paged" threads a PagedKV and routes through paged_decode_attention.
# ---------------------------------------------------------------------------
DECODE_BACKENDS = ("contiguous", "paged")


class PagedKV(NamedTuple):
    """One layer's view of the paged decode cache: the whole block pool,
    the layer it addresses, and the block table.

    k, v: [L, n_blocks, Hkv, block_size, D] — the shared block pool of
    every layer, head-major so a (block, kv head) tile is one contiguous
    [block_size, D] slab (the block shape the Pallas paged kernels can DMA
    on a TPU). The pool travels whole so that a layer's write updates it in
    place (``scan_paged_layers``).
    tables: [B, max_blocks] int32 — row b's logical position p lives in block
    ``tables[b, p // block_size]`` at offset ``p % block_size``; -1 marks an
    unassigned table column (padding rows read nothing and write nowhere).
    layer: int32 scalar — the layer whose K/V this call writes and reads.
    """
    k: jax.Array
    v: jax.Array
    tables: jax.Array
    layer: jax.Array

    @property
    def kv_len(self) -> int:
        """Logical positions a row's table spans."""
        return self.tables.shape[1] * self.k.shape[3]


def plan_decode_backend(cfg, kv_cache) -> str:
    """Select the decode-attention backend for one layer call.

    The backend follows the cache representation the caller threads in and
    must agree with ``cfg.decode_attention`` — a paged cache reaching a layer
    whose config says contiguous (or vice versa) is a wiring bug, not a
    fallback case.
    """
    if cfg.decode_attention not in DECODE_BACKENDS:
        raise ValueError(
            f"unknown decode_attention {cfg.decode_attention!r}; "
            f"known: {DECODE_BACKENDS}")
    backend = "paged" if isinstance(kv_cache, PagedKV) else "contiguous"
    if kv_cache is not None and backend != cfg.decode_attention:
        raise ValueError(
            f"decode cache is {backend} but cfg.decode_attention is "
            f"{cfg.decode_attention!r}")
    return backend


def paged_kv_write(pkv: PagedKV, k, v, positions, valid=None) -> PagedKV:
    """Write k/v [B, C, Hkv, D] at logical ``positions`` [B, C] of layer
    ``pkv.layer`` through the block table. Rows whose table has no block
    for a position (padding rows, ``tables[b, p // bs] < 0``) are dropped,
    never scattered into a live block; ``valid`` [B, C] additionally drops
    padded lane positions of a batched prefill chunk (a short final chunk
    padded to block_size must not scatter garbage into its own — or,
    prefix-shared, anyone else's — blocks) and frozen decode rows."""
    _, nb, hkv, bs, _ = pkv.k.shape
    mb = pkv.tables.shape[1]
    p = jnp.asarray(positions, jnp.int32)
    col = jnp.clip(p // bs, 0, mb - 1)           # pad positions may overrun
    blk = jnp.take_along_axis(pkv.tables, col, axis=1)
    blk = jnp.where((blk >= 0) & (p // bs < mb), blk, nb)  # oob -> dropped
    if valid is not None:
        blk = jnp.where(valid, blk, nb)
    # Index all four leading dims (layer, block, head, offset) so each
    # update is one row of D: the scatter then keeps the pool's own layout
    # and XLA writes the carried buffer in place. A [Hkv, D] window
    # between the block and offset indices made the TPU compiler relayout
    # the pool around the scatter. Updates are k/v's own [B, C, Hkv, D].
    shape = blk.shape + (hkv,)
    idx = (jnp.broadcast_to(pkv.layer, shape),
           jnp.broadcast_to(blk[..., None], shape),
           jnp.broadcast_to(jnp.arange(hkv, dtype=jnp.int32), shape),
           jnp.broadcast_to((p % bs)[..., None], shape))
    nk = pkv.k.at[idx].set(k.astype(pkv.k.dtype), mode="drop")
    nv = pkv.v.at[idx].set(v.astype(pkv.v.dtype), mode="drop")
    return pkv._replace(k=nk, v=nv)


def paged_kv_gather(pkv: PagedKV):
    """Materialize each row's pages of layer ``pkv.layer``: -> (k [B,
    MB*BS, Hkv, D], v likewise, k_pos [B, MB*BS] logical positions, valid
    [B, MB*BS] assigned-block mask). Unassigned table entries gather block
    0 and are masked off."""
    _, _, hkv, bs, d = pkv.k.shape
    b, mb = pkv.tables.shape
    safe = jnp.maximum(pkv.tables, 0)
    # [B, MB, Hkv, BS, D] -> position-major [B, MB * BS, Hkv, D]
    kg = pkv.k[pkv.layer, safe].transpose(0, 1, 3, 2, 4).reshape(
        b, mb * bs, hkv, d)
    vg = pkv.v[pkv.layer, safe].transpose(0, 1, 3, 2, 4).reshape(
        b, mb * bs, hkv, d)
    k_pos = jnp.broadcast_to(jnp.arange(mb * bs, dtype=jnp.int32)[None],
                             (b, mb * bs))
    valid = jnp.repeat(pkv.tables >= 0, bs, axis=1)
    return kg, vg, k_pos, valid


def paged_decode_attention(cfg, q, k, v, pkv: PagedKV, positions, window,
                           scheme, valid=None):
    """The "paged" decode-attention backend: write this call's (post-RoPE)
    k/v [B, C, Hkv, D] at ``positions`` [B, C] through the block table, then
    attend q over the gathered pages with the same validity mask semantics as
    the contiguous path (k_pos <= pos, optional sliding window). Handles both
    decode (C == 1, per-row positions) and chunked prefill (lane-batched
    [P, C] chunks at per-lane position spans; ``valid`` [B, C] masks padded
    lane positions out of the K/V write — their query rows compute garbage
    that the caller discards). Returns (attn out [B, C, Hq, D],
    (new_k, new_v) whole block pools).

    ``cfg.use_pallas`` routes single-token decode through the Pallas
    block-table decode kernel and multi-token chunks through the paged
    *prefill* kernel (both in kernels/paged_attention.py — positions of a
    chunk are contiguous per row, which is what the prefill kernel assumes),
    each given the layer's [NB, Hkv, BS, D] slab of the written pool; the
    default path gathers pages and reuses ``mha`` so paged outputs stay
    token-identical to contiguous decode.
    """
    b, c = q.shape[:2]
    pkv = paged_kv_write(pkv, k, v, positions, valid)
    if cfg.use_pallas:
        from repro.kernels import ops as kops
        kl = jax.lax.dynamic_index_in_dim(pkv.k, pkv.layer, keepdims=False)
        vl = jax.lax.dynamic_index_in_dim(pkv.v, pkv.layer, keepdims=False)
        if c == 1:
            out = kops.paged_attention(q[:, 0], kl, vl, pkv.tables,
                                       positions[:, 0], window)[:, None]
        else:
            out = kops.paged_prefill_attention(q, kl, vl, pkv.tables,
                                               positions[:, 0], window)
        return out, (pkv.k, pkv.v)
    kg, vg, k_pos, assigned = paged_kv_gather(pkv)
    kg = shard(kg, "batch", "kv_seq", None, None)
    vg = shard(vg, "batch", "kv_seq", None, None)
    valid = assigned[:, None, :] & (k_pos[:, None, :] <= positions[:, :, None])
    if not (isinstance(window, int) and window == 0):
        valid &= (window == 0) | (k_pos[:, None, :]
                                  > positions[:, :, None] - window)
    out = mha(q, kg, vg, valid[:, None], no_repeat=cfg.gqa_no_repeat,
              scheme=scheme)
    return out, (pkv.k, pkv.v)


def decode_positions(b: int, pos) -> jax.Array:
    """[B, 1] position matrix for a decode step. ``pos`` is a scalar (all
    rows at the same position — static batching, the dry-run's serve step) or
    an int32 [B] vector (per-slot positions — continuous batching)."""
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        return jnp.full((b, 1), pos, jnp.int32)
    return pos[:, None]


def update_kv_cache(ck, cv, k, v, cache_pos, valid=None):
    """Write one decode step's k/v [B, 1, H, D] into the cache [B, S, H, D]
    at ``cache_pos`` (scalar, or [B] for per-row positions) and return the
    updated cache plus the validity mask over cache positions.

    ``valid`` ([B] bool, per-row positions only) drops rows from the write
    entirely: a frozen row of a multi-step decode horizon (finished budget /
    EOS) must stop writing KV. Masked rows are redirected to an
    out-of-bounds position and scattered with ``mode="drop"``, so the cache
    row is untouched rather than overwritten in place.
    """
    pos = jnp.asarray(cache_pos)
    k_pos = jnp.arange(ck.shape[1])
    if pos.ndim == 0:
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), pos, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), pos, axis=1)
        return ck, cv, k_pos, pos
    if valid is not None:
        rows = jnp.arange(ck.shape[0])
        pos_eff = jnp.where(valid, pos, ck.shape[1])      # oob -> dropped
        ck = ck.at[rows, pos_eff].set(k[:, 0].astype(ck.dtype), mode="drop")
        cv = cv.at[rows, pos_eff].set(v[:, 0].astype(cv.dtype), mode="drop")
        return ck, cv, k_pos[None, :], pos[:, None]
    upd = lambda c, u, p_: jax.lax.dynamic_update_slice_in_dim(c, u, p_, axis=0)
    ck = jax.vmap(upd)(ck, k.astype(ck.dtype), pos)
    cv = jax.vmap(upd)(cv, v.astype(cv.dtype), pos)
    return ck, cv, k_pos[None, :], pos[:, None]


def attention(p, cfg, x, positions, *, causal: bool = True,
              window: int = 0, kv_cache=None, cache_pos=None,
              cross_kv=None, kv_valid=None):
    """Full attention layer.

    Modes:
      * training / prefill: ``kv_cache is None`` — attend over x itself.
      * decode: ``kv_cache=(k, v)`` with static length S; the current token's
        k/v is written at ``cache_pos`` (scalar, or [B] per-row positions for
        continuous batching) and attention spans the cache.
      * cross attention: ``cross_kv=(k, v)`` precomputed from encoder output.
    ``kv_valid`` masks K/V writes: [B, C] chunk validity for paged prefill
    lanes, or a [B, 1] per-row freeze mask for decode (a finished row of a
    multi-step horizon stops writing KV on both cache backends).
    Returns (out, new_kv_cache_or_None).
    """
    b, s, _ = x.shape
    if isinstance(kv_cache, PagedKV):
        kv_len = kv_cache.kv_len
    else:
        kv_len = (kv_cache[0].shape[1] if kv_cache is not None
                  else cross_kv[0].shape[1] if cross_kv is not None else s)
    scheme = plan_attention_scheme(cfg, b, s, kv_len)
    backend = plan_decode_backend(cfg, kv_cache)
    q, k, v = _qkv(p, cfg, x, scheme=scheme)
    new_cache = None

    if cross_kv is not None:
        k, v = cross_kv
        mask = None
    elif backend == "paged":
        if cfg.pos_emb == "rope":
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        out, new_cache = paged_decode_attention(cfg, q, k, v, kv_cache,
                                                positions, window, scheme,
                                                valid=kv_valid)
        return out.reshape(b, s, -1) @ p["wo"], new_cache
    elif kv_cache is not None:
        ck, cv = kv_cache
        if cfg.pos_emb == "rope":
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        ck, cv, k_pos, cpos = update_kv_cache(
            ck, cv, k, v, cache_pos,
            valid=kv_valid[:, 0] if kv_valid is not None else None)
        new_cache = (ck, cv)
        k, v = ck, cv
        valid = k_pos <= cpos
        if window:
            valid &= k_pos > cpos - window
        # [1, Sk] shared-position mask, or [B, 1, 1, Sk] per-row mask
        mask = valid[None, :] if valid.ndim == 1 else valid[:, None, None, :]
        k = shard(k, "batch", "kv_seq", None, None)
        v = shard(v, "batch", "kv_seq", None, None)
    else:
        if cfg.pos_emb == "rope":
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        q_pos = jnp.arange(s)
        mask = attention_weights_mask(q_pos, q_pos, causal=causal, window=window)
        new_cache = (k, v)          # post-rope k/v, used by prefill to seed a cache

    use_pl = cfg.use_pallas and kv_cache is None and cross_kv is None and causal
    out = mha(q, k, v, None if use_pl else mask, use_pallas=use_pl,
              causal=causal, window=window, no_repeat=cfg.gqa_no_repeat,
              scheme=scheme)
    out = out.reshape(b, s, -1) @ p["wo"]
    return out, new_cache


# ---------------------------------------------------------------------------
# MLP (SwiGLU)
# ---------------------------------------------------------------------------
def init_mlp(key, d: int, d_ff: int, dtype) -> dict:
    ks = jax.random.split(key, 3)
    return {
        "w_gate": _init_dense(ks[0], (d, d_ff), dtype),
        "w_up": _init_dense(ks[1], (d, d_ff), dtype),
        "w_down": _init_dense(ks[2], (d_ff, d), dtype),
    }


def mlp(p, x):
    h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    h = shard(h, "batch", None, "ffn")
    return h @ p["w_down"]


# ---------------------------------------------------------------------------
# Embeddings / unembedding
# ---------------------------------------------------------------------------
def init_embeddings(key, cfg, dtype) -> dict:
    ks = jax.random.split(key, 2)
    # Tied embeddings use 1/sqrt(d) init (+ sqrt(d) input scaling, gemma-style)
    # so that tied logits come out unit-scale.
    emb_scale = cfg.d_model ** -0.5 if cfg.tie_embeddings else 1.0
    p = {"tok_emb": _init_dense(ks[0], (cfg.vocab_size, cfg.d_model), dtype,
                                scale=emb_scale)}
    if not cfg.tie_embeddings:
        p["lm_head"] = _init_dense(ks[1], (cfg.d_model, cfg.vocab_size), dtype)
    return p


def embed(p, cfg, tokens):
    x = jnp.take(p["tok_emb"], tokens, axis=0)
    if cfg.tie_embeddings:
        x = x * math.sqrt(cfg.d_model)
    return shard(x, "batch", None, None)


def unembed(p, cfg, x):
    if cfg.tie_embeddings:
        logits = x @ p["tok_emb"].T
    else:
        logits = x @ p["lm_head"]
    return shard(logits, "batch", None, "vocab")


def cross_entropy(logits, labels, mask=None):
    """Mean next-token cross entropy in f32. labels: int [B, S]."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
