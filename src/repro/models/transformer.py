"""Dense decoder-only transformer (llama3 / qwen2 / gemma3 / phi-3-vision).

Layers are stacked along a leading axis and executed with ``jax.lax.scan`` to
keep HLO size and 512-device compile times tractable. Gemma3's 5:1
local:global attention pattern is expressed as a per-layer window array that
is scanned alongside the parameters (window == 0 means global attention).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.dist.sharding import shard
from repro.models import layers as L


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_layer(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "attn": L.init_attention(k1, cfg, dtype),
        "mlp": L.init_mlp(k2, cfg.d_model, cfg.d_ff, dtype),
        "norm1": L.init_rmsnorm(cfg.d_model, dtype),
        "norm2": L.init_rmsnorm(cfg.d_model, dtype),
    }


def init_params(cfg, key):
    dtype = jnp.dtype(cfg.param_dtype)
    k_emb, k_layers = jax.random.split(key)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    params = {
        "emb": L.init_embeddings(k_emb, cfg, dtype),
        "layers": jax.vmap(lambda k: init_layer(k, cfg, dtype))(layer_keys),
        "final_norm": L.init_rmsnorm(cfg.d_model, dtype),
    }
    return params


def layer_windows(cfg) -> jnp.ndarray:
    """Per-layer sliding window (0 = full/global attention)."""
    idx = jnp.arange(cfg.n_layers)
    if cfg.sliding_window and cfg.global_every:
        is_global = (idx + 1) % cfg.global_every == 0
        return jnp.where(is_global, 0, cfg.sliding_window).astype(jnp.int32)
    if cfg.sliding_window:
        return jnp.full((cfg.n_layers,), cfg.sliding_window, jnp.int32)
    return jnp.zeros((cfg.n_layers,), jnp.int32)


# ---------------------------------------------------------------------------
# layer body
# ---------------------------------------------------------------------------
def _layer(cfg, p, x, positions, window, kv_cache=None, cache_pos=None,
           kv_valid=None):
    h = L.rmsnorm(x, p["norm1"], cfg.norm_eps)
    # window is a traced per-layer int32 — the mask builder must accept it.
    attn_out, new_cache = _attention_dyn_window(
        cfg, p["attn"], h, positions, window, kv_cache, cache_pos, kv_valid)
    x = x + attn_out
    h = L.rmsnorm(x, p["norm2"], cfg.norm_eps)
    x = x + L.mlp(p["mlp"], h)
    x = shard(x, "batch", None, None)
    return x, new_cache


def _attention_dyn_window(cfg, p, x, positions, window, kv_cache, cache_pos,
                          kv_valid=None):
    """Attention with a *traced* window size (for scanned local/global mix)."""
    b, s, _ = x.shape
    if isinstance(kv_cache, L.PagedKV):
        kv_len = kv_cache.kv_len
    else:
        kv_len = kv_cache[0].shape[1] if kv_cache is not None else s
    scheme = L.plan_attention_scheme(cfg, b, s, kv_len)
    backend = L.plan_decode_backend(cfg, kv_cache)
    q, k, v = L._qkv(p, cfg, x, scheme=scheme)
    if cfg.pos_emb == "rope":
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
    new_cache = None
    if backend == "paged":
        out, new_cache = L.paged_decode_attention(cfg, q, k, v, kv_cache,
                                                  positions, window, scheme,
                                                  valid=kv_valid)
        return out.reshape(b, s, -1) @ p["wo"], new_cache
    if kv_cache is not None:
        ck, cv = kv_cache
        ck, cv, k_pos, cpos = L.update_kv_cache(
            ck, cv, k, v, cache_pos,
            valid=kv_valid[:, 0] if kv_valid is not None else None)
        new_cache = (ck, cv)
        k, v = ck, cv
        mask = k_pos <= cpos
        mask &= (window == 0) | (k_pos > cpos - window)
        # [1, Sk] shared-position mask, or [B, 1, 1, Sk] per-row mask
        mask = mask[None, :] if mask.ndim == 1 else mask[:, None, None, :]
        k = shard(k, "batch", "kv_seq", None, None)
        v = shard(v, "batch", "kv_seq", None, None)
    else:
        pos = jnp.arange(s)
        mask = pos[:, None] >= pos[None, :]
        mask &= (window == 0) | (pos[:, None] - pos[None, :] < window)
        new_cache = (k, v)
    out = L.mha(q, k, v, mask, no_repeat=cfg.gqa_no_repeat, scheme=scheme)
    out = out.reshape(b, s, -1) @ p["wo"]
    return out, new_cache


# ---------------------------------------------------------------------------
# banded local attention (perf knob: cfg.local_banded, EXPERIMENTS.md §Perf)
#
# Sliding-window layers never need the full S x S score matrix: queries are
# blocked into W-sized chunks, each attending to its own and the previous
# chunk only — O(S * 2W) scores instead of O(S^2). Requires a STATIC window,
# so the layer stack is split into (local x (every-1), global) groups instead
# of scanning a traced per-layer window.
# ---------------------------------------------------------------------------
def _banded_attention(cfg, p, x, positions, window: int):
    from repro.dist.sharding import current_rules, shard_spec
    from jax.sharding import PartitionSpec as P_

    b, s, _ = x.shape
    w = window
    assert s % w == 0, (s, w)
    nb = s // w
    q, k, v = L._qkv(p, cfg, x)
    if cfg.pos_emb == "rope":
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
    h, hd = q.shape[2], q.shape[3]
    hkv = k.shape[2]
    if h != hkv:
        k = jnp.repeat(k, h // hkv, axis=2)
        v = jnp.repeat(v, h // hkv, axis=2)

    qb = q.reshape(b, nb, w, h, hd)
    pad = jnp.zeros((b, w, h, hd), k.dtype)
    kp = jnp.concatenate([pad, k], axis=1).reshape(b, nb + 1, w, h, hd)
    vp = jnp.concatenate([pad, v], axis=1).reshape(b, nb + 1, w, h, hd)
    k2 = jnp.concatenate([kp[:, :-1], kp[:, 1:]], axis=2)   # [b,nb,2w,h,hd]
    v2 = jnp.concatenate([vp[:, :-1], vp[:, 1:]], axis=2)

    rules = current_rules()
    if rules is not None:
        msize = rules.axis_size(rules.mesh_axes("heads_flat"))
        m_ax = rules.mesh_axes("heads_flat") if h % max(msize, 1) == 0 else None
        b_ax = rules.mesh_axes("batch")
        if b % max(rules.axis_size(b_ax), 1) != 0:
            b_ax = None
        spec = P_(b_ax, None, None, m_ax, None)
        qb, k2, v2 = (shard_spec(t, spec) for t in (qb, k2, v2))

    scale = 1.0 / (hd ** 0.5)
    logits = jnp.einsum("bnqhd,bnkhd->bnhqk", qb, k2).astype(jnp.float32) * scale
    a = jnp.arange(w)[:, None]
    c = jnp.arange(2 * w)[None, :]
    band = (a < c) & (c <= a + w)                            # causal + window
    blk = jnp.arange(nb)[:, None, None]
    mask = band[None] & ((blk > 0) | (c[None] >= w))         # exclude padding
    logits = jnp.where(mask[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bnhqk,bnkhd->bnqhd", probs, v2)
    out = out.reshape(b, s, h * hd) @ p["wo"]
    return out


def _local_layer_banded(cfg, p, x, positions, window: int):
    h = L.rmsnorm(x, p["norm1"], cfg.norm_eps)
    x = x + _banded_attention(cfg, p["attn"], h, positions, window)
    h = L.rmsnorm(x, p["norm2"], cfg.norm_eps)
    x = x + L.mlp(p["mlp"], h)
    return shard(x, "batch", None, None), None


def _grouped_layout(cfg):
    """(n_groups, group_size, n_trailing) for the local/global split."""
    every = cfg.global_every
    groups = cfg.n_layers // every
    trailing = cfg.n_layers - groups * every
    return groups, every, trailing


def forward_banded(cfg, params, tokens, patch_embeds=None):
    """Grouped forward: (every-1 banded-local layers + 1 global) x groups,
    then trailing local layers. Preserves exact layer order/semantics of the
    scanned path; only the local layers' score computation is banded."""
    x = L.embed(params["emb"], cfg, tokens)
    if patch_embeds is not None:
        np_ = patch_embeds.shape[1]
        x = jnp.concatenate([patch_embeds.astype(x.dtype), x[:, np_:]], axis=1)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    groups, every, trailing = _grouped_layout(cfg)
    w = cfg.sliding_window
    stacked = params["layers"]
    gparams = jax.tree_util.tree_map(
        lambda a: a[:groups * every].reshape(groups, every, *a.shape[1:]),
        stacked)
    tparams = (jax.tree_util.tree_map(lambda a: a[groups * every:], stacked)
               if trailing else None)

    def group_body(x, gp):
        locals_ = jax.tree_util.tree_map(lambda a: a[:every - 1], gp)
        glob = jax.tree_util.tree_map(lambda a: a[every - 1], gp)
        x, _ = L.scan_layers(
            cfg, lambda c, p: _local_layer_banded(cfg, p, c, positions, w),
            x, locals_)
        x, _ = _layer(cfg, glob, x, positions, jnp.int32(0))
        return x, None

    if cfg.remat != "none":
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if cfg.remat == "dots" else
                  jax.checkpoint_policies.nothing_saveable)
        group_body = jax.checkpoint(group_body, policy=policy)
    x, _ = L.scan_layers(cfg, group_body, x, gparams)
    if trailing:
        x, _ = L.scan_layers(
            cfg, lambda c, p: _local_layer_banded(cfg, p, c, positions, w),
            x, tparams)
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return L.unembed(params["emb"], cfg, x)


# ---------------------------------------------------------------------------
# forward (training / prefill)
# ---------------------------------------------------------------------------
def forward(cfg, params, tokens, patch_embeds=None, return_cache=False):
    """tokens: [B, S] int32. patch_embeds: [B, n_patches, D] (vlm stub).

    Returns logits [B, S, V] (and per-layer (k, v) stacks if return_cache).
    """
    if (cfg.local_banded and cfg.sliding_window and cfg.global_every
            and tokens.shape[1] % cfg.sliding_window == 0):
        out = forward_banded(cfg, params, tokens, patch_embeds)
        if return_cache:
            raise NotImplementedError("banded path has no prefill cache yet")
        return out
    x = L.embed(params["emb"], cfg, tokens)
    if patch_embeds is not None:
        # VLM stub frontend: image patch embeddings occupy the sequence prefix.
        np_ = patch_embeds.shape[1]
        x = jnp.concatenate([patch_embeds.astype(x.dtype), x[:, np_:]], axis=1)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    windows = layer_windows(cfg)

    def body(x, scanned):
        p, w = scanned
        return _layer(cfg, p, x, positions, w)

    if cfg.remat != "none":
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if cfg.remat == "dots" else
                  jax.checkpoint_policies.nothing_saveable)
        body = jax.checkpoint(body, policy=policy)

    x, caches = L.scan_layers(cfg, body, x, (params["layers"], windows))
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = L.unembed(params["emb"], cfg, x)
    if return_cache:
        return logits, caches
    return logits


def loss_fn(cfg, params, batch):
    """batch: {tokens, labels[, patch_embeds]}. Mean next-token CE."""
    logits = forward(cfg, params, batch["tokens"],
                     patch_embeds=batch.get("patch_embeds"))
    mask = batch.get("loss_mask")
    if cfg.family == "vlm" and mask is None:
        s = batch["labels"].shape[1]
        mask = jnp.broadcast_to(jnp.arange(s)[None, :] >= cfg.n_patches,
                                batch["labels"].shape)
    return L.cross_entropy(logits, batch["labels"], mask)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------
def init_cache(cfg, batch: int, max_len: int, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    hd, nkv = cfg.resolved_head_dim, cfg.n_kv_heads
    shape = (cfg.n_layers, batch, max_len, nkv, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_paged_cache(cfg, n_blocks: int, block_size: int, dtype=None):
    """Block-pool decode cache: ``n_blocks`` blocks of ``block_size`` KV
    positions shared by all requests (serve/paged.py's BlockManager carves
    them up); the per-request block tables live outside the pytree. Leaves
    are head-major ``[L, n_blocks, Hkv, block_size, D]`` (see
    ``layers.PagedKV``)."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    hd, nkv = cfg.resolved_head_dim, cfg.n_kv_heads
    shape = (cfg.n_layers, n_blocks, nkv, block_size, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def paged_prefill_state(cfg, batch: int = 1):
    """Cross-chunk prefill carry (none for dense attention)."""
    return None


def prefill_chunk_layout(start, n_valid, b: int, c: int):
    """Per-token (positions [B, C], valid [B, C] | None, last-index [B])
    for a (lane-batched) prefill chunk. ``start`` is a scalar (one request)
    or an int32 [B] vector of per-lane first positions; ``n_valid`` (int32
    [B] or None) counts the real tokens per lane — the tail of a short
    final chunk is padding whose K/V writes must be dropped and whose
    logits are discarded."""
    start = jnp.asarray(start, jnp.int32)
    if start.ndim == 0:
        start = jnp.full((b,), start, jnp.int32)
    positions = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    if n_valid is None:
        return positions, None, jnp.full((b,), c - 1, jnp.int32)
    n_valid = jnp.asarray(n_valid, jnp.int32)
    valid = jnp.arange(c, dtype=jnp.int32)[None, :] < n_valid[:, None]
    return positions, valid, jnp.clip(n_valid - 1, 0, c - 1)


def paged_prefill_chunk(cfg, params, cache, tokens, start, tables,
                        state=None, cap_tokens: int = 0, n_valid=None,
                        cap_rows=None):
    """Prefill one prompt chunk per lane into the paged cache.

    tokens: [P, C] — one ``block_size`` slice of P joining requests' prompts
    (one jitted dispatch covers a whole chunk-round; P == 1 is the
    single-request case); start: int32 scalar or [P] — each lane's first
    logical position; n_valid: int32 [P] or None — real tokens per lane
    (short final chunks are padded to C; pad positions write nothing and
    their logits are ignored); tables: [P, MB] — each request's block table
    (blocks covering its [0, start + n_valid) must already be assigned).
    ``cap_rows`` is accepted for signature parity with the MoE family and
    ignored. The chunk's K/V is appended through the table and attention
    spans every cached position, so chaining chunks reproduces the one-pass
    forward without ever materializing a contiguous max_len row. Returns
    (per-lane last-valid-position logits [P, 1, V], new cache, state).
    """
    x = L.embed(params["emb"], cfg, tokens)
    b, c, _ = x.shape
    positions, valid, last = prefill_chunk_layout(start, n_valid, b, c)
    windows = layer_windows(cfg)

    def body(x, pool, layer, scanned):
        p, w = scanned
        x, (k, v) = _layer(cfg, p, x, positions, w,
                           kv_cache=L.PagedKV(pool["k"], pool["v"], tables,
                                              layer),
                           kv_valid=valid)
        return x, {"k": k, "v": v}, None

    x, cache, _ = L.scan_paged_layers(cfg, body, x, cache,
                                      (params["layers"], windows))
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = L.unembed(params["emb"], cfg, x)
    logits = jnp.take_along_axis(logits, last[:, None, None], axis=1)
    return logits, cache, None


def paged_decode_step(cfg, params, cache, tokens, pos, tables,
                      write_valid=None):
    """One paged decode step. tokens: [B, 1]; pos: int32 [B] per-row
    positions; tables: [B, MB] block tables (padding rows are all -1 and
    decode inert garbage that is never read); write_valid: [B] bool or None
    — False rows compute but write no KV (frozen rows of a multi-step
    decode horizon). Returns (logits, new_cache)."""
    x = L.embed(params["emb"], cfg, tokens)
    b = x.shape[0]
    positions = L.decode_positions(b, pos)
    windows = layer_windows(cfg)
    kv_valid = None if write_valid is None else write_valid[:, None]

    def body(x, pool, layer, scanned):
        p, w = scanned
        x, (k, v) = _layer(cfg, p, x, positions, w,
                           kv_cache=L.PagedKV(pool["k"], pool["v"], tables,
                                              layer),
                           kv_valid=kv_valid)
        return x, {"k": k, "v": v}, None

    x, cache, _ = L.scan_paged_layers(cfg, body, x, cache,
                                      (params["layers"], windows))
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = L.unembed(params["emb"], cfg, x)
    return logits, cache


def decode_step(cfg, params, cache, tokens, pos, write_valid=None):
    """One decode step. tokens: [B, 1]; pos: scalar int32 (all rows at the
    same position) or int32 [B] (per-row positions, continuous batching);
    write_valid: [B] bool or None — False rows compute but write no KV
    (frozen rows of a multi-step decode horizon; needs vector pos).

    Returns (logits [B, 1, V], new_cache).
    """
    x = L.embed(params["emb"], cfg, tokens)
    b = x.shape[0]
    positions = L.decode_positions(b, pos)
    windows = layer_windows(cfg)
    kv_valid = None if write_valid is None else write_valid[:, None]

    def body(x, scanned):
        p, w, ck, cv = scanned
        x, new_kv = _layer(cfg, p, x, positions, w, kv_cache=(ck, cv),
                           cache_pos=pos, kv_valid=kv_valid)
        return x, new_kv

    x, (new_k, new_v) = L.scan_layers(
        cfg, body, x, (params["layers"], windows, cache["k"], cache["v"]))
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = L.unembed(params["emb"], cfg, x)
    return logits, {"k": new_k, "v": new_v}
