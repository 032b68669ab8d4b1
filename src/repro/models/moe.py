"""Mixture-of-Experts decoder (OLMoE 64e/top-8, Phi-3.5-MoE 16e/top-2).

Token-choice top-k routing with capacity-bounded gather/scatter dispatch:
the dispatch path uses integer gather/scatter (NOT one-hot einsums) so the
compiled HLO FLOPs stay close to the *active* FLOPs — this keeps the roofline
MODEL_FLOPS / HLO_FLOPs ratio honest. Expert FFNs run as a batched GEMM over
the expert axis ([E, C, D] x [E, D, F]) which shards cleanly over the 'model'
mesh axis (expert parallelism; XLA inserts the all-to-all at the sharding
boundary between token-sharded and expert-sharded layouts).

The Pallas ``grouped_matmul`` kernel is the TPU hot-spot implementation of the
same contraction (see repro/kernels/grouped_matmul.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.dist.sharding import shard
from repro.models import layers as L
from repro.models import transformer as T


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_moe_layer(key, cfg, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    return {
        "attn": L.init_attention(k1, cfg, dtype),
        "router": L._init_dense(k2, (d, e), dtype),
        "we_gate_up": L._init_dense(k3, (e, d, 2 * f), dtype),
        "we_down": L._init_dense(k4, (e, f, d), dtype),
        "norm1": L.init_rmsnorm(d, dtype),
        "norm2": L.init_rmsnorm(d, dtype),
    }


def init_params(cfg, key):
    dtype = jnp.dtype(cfg.param_dtype)
    k_emb, k_layers = jax.random.split(key)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    return {
        "emb": L.init_embeddings(k_emb, cfg, dtype),
        "layers": jax.vmap(lambda k: init_moe_layer(k, cfg, dtype))(layer_keys),
        "final_norm": L.init_rmsnorm(cfg.d_model, dtype),
    }


# ---------------------------------------------------------------------------
# MoE FFN
# ---------------------------------------------------------------------------
def capacity(cfg, n_tokens: int) -> int:
    c = int(n_tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(8, -(-c // 8) * 8)          # round up to 8


def moe_ffn(cfg, p, x, *, counts=None, cap_tokens=None, token_valid=None,
            cap_rows=None):
    """x: [B, S, D] -> ([B, S, D], aux_loss[, new_counts]).

    Dispatch is computed independently per batch row (vmap) so the dispatch
    buffers are [B, E, C, D]: batch shards over 'data', experts over 'model'.

    ``counts``/``cap_tokens`` make the layer chunkable (paged prefill):
    ``counts`` [B, E] int32 carries how many assignments each expert has
    already received from earlier chunks of the same sequence — the in-expert
    slot of a token is its global arrival order, so capacity drops land on
    exactly the same tokens as a one-pass forward — and ``cap_tokens`` pins
    the capacity to the full sequence length instead of the chunk length.
    When ``counts`` is given the updated counts are returned as a third
    output.

    ``token_valid``/``cap_rows`` make the layer *lane-batchable* (batched
    prefill): invalid tokens (the padded tail of a short final chunk) claim
    no expert slot, contribute no counts, and combine to zero, and
    ``cap_rows`` [B] int32 pins each lane's *effective* capacity to its own
    prompt's ``capacity(cfg, len)`` while the dispatch buffer is sized by
    the static ``cap_tokens`` bound — so every lane routes exactly like a
    solo one-pass forward over its own prompt.
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = capacity(cfg, cap_tokens if cap_tokens else s)

    logits = (x @ p["router"]).astype(jnp.float32)               # [B, S, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                       # [B, S, K]
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)       # renormalize

    # Load-balance auxiliary loss (Switch-style): E * sum(frac_e * mean_prob_e)
    onehot = jax.nn.one_hot(top_e, e, dtype=jnp.float32)         # [B, S, K, E]
    frac_tokens = jnp.mean(jnp.sum(onehot, axis=2), axis=(0, 1))  # [E]
    mean_prob = jnp.mean(probs, axis=(0, 1))                     # [E]
    aux = e * jnp.sum(frac_tokens / k * mean_prob)

    if token_valid is None:
        token_valid = jnp.ones((b, s), bool)
    if cap_rows is None:
        cap_rows = jnp.full((b,), cap, jnp.int32)

    def dispatch_row(xt, row_e, row_p, cnt, tv, cap_row):
        """xt: [S, D]; row_e/row_p: [S, K]; cnt: [E] carried assignment
        counts; tv: [S] token validity; cap_row: scalar effective capacity
        -> ([E, C, D], combine meta, updated counts)."""
        flat_e = row_e.reshape(-1)                               # [S*K]
        flat_p = row_p.reshape(-1)
        flat_tok = jnp.repeat(jnp.arange(s), k)
        flat_tv = jnp.repeat(tv, k)
        one = jax.nn.one_hot(flat_e, e, dtype=jnp.int32) * flat_tv[:, None]
        pos_in_e = (cnt[flat_e]
                    + jnp.cumsum(one, axis=0)[jnp.arange(s * k), flat_e] - 1)
        keep = (pos_in_e < cap_row) & flat_tv
        safe_pos = jnp.where(keep, pos_in_e, cap - 1)
        if cfg.moe_gather_dispatch:
            # Scatter only int32 slot->token indices (E*C ints), then gather
            # features locally: avoids XLA's f32 partial-sum all-reduce of
            # the whole [E, C, D] buffer over the expert-sharded axis.
            slot_tok = jnp.full((e, cap), -1, jnp.int32)
            slot_tok = slot_tok.at[flat_e, safe_pos].max(
                jnp.where(keep, flat_tok, -1).astype(jnp.int32))
            buf = jnp.where(slot_tok[..., None] >= 0,
                            jnp.take(xt, jnp.maximum(slot_tok, 0), axis=0),
                            jnp.zeros((), xt.dtype))
        else:
            buf = jnp.zeros((e, cap, d), xt.dtype)
            buf = buf.at[flat_e, safe_pos].add(
                jnp.where(keep[:, None], xt[flat_tok], 0.0))
        return (buf, (flat_e, safe_pos, flat_tok,
                      jnp.where(keep, flat_p, 0.0)),
                cnt + jnp.sum(one, axis=0))

    cnt0 = counts if counts is not None else jnp.zeros((b, e), jnp.int32)
    buf, meta, new_counts = jax.vmap(dispatch_row)(x, top_e, top_p, cnt0,
                                                   token_valid, cap_rows)
    buf = shard(buf, "batch", "experts", None, None)              # [B, E, C, D]

    # expert computation: batched swiglu over the expert axis
    gu = jnp.einsum("becd,edf->becf", buf, p["we_gate_up"])
    g, u = jnp.split(gu, 2, axis=-1)
    h = jax.nn.silu(g) * u
    out_buf = jnp.einsum("becf,efd->becd", h, p["we_down"])
    out_buf = shard(out_buf, "batch", "experts", None, None)

    def combine_row(out_b, m):
        flat_e, safe_pos, flat_tok, w = m
        y = out_b[flat_e, safe_pos] * w[:, None].astype(out_b.dtype)
        return jax.ops.segment_sum(y, flat_tok, num_segments=s)

    y = jax.vmap(combine_row)(out_buf, meta)                     # [B, S, D]
    if counts is not None:
        return y, aux, new_counts
    return y, aux


# ---------------------------------------------------------------------------
# forward / loss / decode
# ---------------------------------------------------------------------------
def _layer(cfg, p, x, positions, kv_cache=None, cache_pos=None,
           kv_valid=None):
    h = L.rmsnorm(x, p["norm1"], cfg.norm_eps)
    attn_out, new_cache = L.attention(p["attn"], cfg, h, positions,
                                      kv_cache=kv_cache, cache_pos=cache_pos,
                                      kv_valid=kv_valid)
    x = x + attn_out
    h = L.rmsnorm(x, p["norm2"], cfg.norm_eps)
    ffn_out, aux = moe_ffn(cfg, p, h)
    x = x + ffn_out
    return shard(x, "batch", None, None), new_cache, aux


def forward(cfg, params, tokens, return_aux=False, return_cache=False):
    """tokens: [B, S] int32 -> logits [B, S, V].

    ``return_cache`` captures the per-layer post-rope (k, v) stacks so serving
    can prefill MoE in ONE forward pass (like dense/vlm) instead of the
    O(S)-step decode scan.
    """
    x = L.embed(params["emb"], cfg, tokens)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))

    def body(carry, p):
        x, aux_sum = carry
        x, kv, aux = _layer(cfg, p, x, positions)
        return (x, aux_sum + aux), kv

    if cfg.remat != "none":
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if cfg.remat == "dots" else
                  jax.checkpoint_policies.nothing_saveable)
        body = jax.checkpoint(body, policy=policy)

    (x, aux_sum), caches = L.scan_layers(cfg, body, (x, jnp.float32(0.0)),
                                         params["layers"])
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = L.unembed(params["emb"], cfg, x)
    if return_aux and return_cache:
        return logits, aux_sum / cfg.n_layers, caches
    if return_aux:
        return logits, aux_sum / cfg.n_layers
    if return_cache:
        return logits, caches
    return logits


def loss_fn(cfg, params, batch):
    logits, aux = forward(cfg, params, batch["tokens"], return_aux=True)
    ce = L.cross_entropy(logits, batch["labels"], batch.get("loss_mask"))
    return ce + cfg.router_aux_coef * aux


init_cache = T.init_cache
init_paged_cache = T.init_paged_cache


def paged_prefill_state(cfg, batch: int = 1):
    """Per-layer expert assignment counts carried across prefill chunks, so
    capacity drops match the one-pass forward (see moe_ffn)."""
    return jnp.zeros((cfg.n_layers, batch, cfg.n_experts), jnp.int32)


def paged_prefill_chunk(cfg, params, cache, tokens, start, tables,
                        state=None, cap_tokens: int = 0, n_valid=None,
                        cap_rows=None):
    """MoE chunked prefill (lane-batched like the dense path): attention
    pages through each lane's block table; the expert FFN routes with the
    carried per-layer counts, drops lane-padding tokens from dispatch, and
    pins each lane's effective capacity to ``cap_rows`` (its own prompt's
    ``capacity(cfg, len)``; the static ``cap_tokens`` only sizes the
    dispatch buffers) so chunked lane-batched routing equals one-pass
    routing token for token."""
    x = L.embed(params["emb"], cfg, tokens)
    b, c, _ = x.shape
    positions, valid, last = T.prefill_chunk_layout(start, n_valid, b, c)
    if state is None:
        state = paged_prefill_state(cfg, b)

    def body(x, pool, layer, scanned):
        p, cnt = scanned
        h = L.rmsnorm(x, p["norm1"], cfg.norm_eps)
        attn_out, (k, v) = L.attention(
            p["attn"], cfg, h, positions,
            kv_cache=L.PagedKV(pool["k"], pool["v"], tables, layer),
            kv_valid=valid)
        x = x + attn_out
        h = L.rmsnorm(x, p["norm2"], cfg.norm_eps)
        ffn_out, _aux, new_cnt = moe_ffn(cfg, p, h, counts=cnt,
                                         cap_tokens=cap_tokens,
                                         token_valid=valid,
                                         cap_rows=cap_rows)
        x = shard(x + ffn_out, "batch", None, None)
        return x, {"k": k, "v": v}, new_cnt

    x, cache, new_counts = L.scan_paged_layers(cfg, body, x, cache,
                                               (params["layers"], state))
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = L.unembed(params["emb"], cfg, x)
    logits = jnp.take_along_axis(logits, last[:, None, None], axis=1)
    return logits, cache, new_counts


def paged_decode_step(cfg, params, cache, tokens, pos, tables,
                      write_valid=None):
    """One paged decode step (see transformer.paged_decode_step)."""
    x = L.embed(params["emb"], cfg, tokens)
    b = x.shape[0]
    positions = L.decode_positions(b, pos)
    kv_valid = None if write_valid is None else write_valid[:, None]

    def body(x, pool, layer, p):
        x, (k, v), _aux = _layer(
            cfg, p, x, positions,
            kv_cache=L.PagedKV(pool["k"], pool["v"], tables, layer),
            kv_valid=kv_valid)
        return x, {"k": k, "v": v}, None

    x, cache, _ = L.scan_paged_layers(cfg, body, x, cache, params["layers"])
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = L.unembed(params["emb"], cfg, x)
    return logits, cache


def decode_step(cfg, params, cache, tokens, pos, write_valid=None):
    x = L.embed(params["emb"], cfg, tokens)
    b = x.shape[0]
    positions = L.decode_positions(b, pos)
    kv_valid = None if write_valid is None else write_valid[:, None]

    def body(x, scanned):
        p, ck, cv = scanned
        x, new_kv, _aux = _layer(cfg, p, x, positions, kv_cache=(ck, cv),
                                 cache_pos=pos, kv_valid=kv_valid)
        return x, new_kv

    x, (new_k, new_v) = L.scan_layers(cfg, body, x, (params["layers"], cache["k"], cache["v"]))
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = L.unembed(params["emb"], cfg, x)
    return logits, {"k": new_k, "v": new_v}
