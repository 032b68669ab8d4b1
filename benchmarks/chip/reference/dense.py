"""Plain float32 reference of the dense decoder (Qwen2 layout: grouped-query
attention with QKV bias, rotary positions, SwiGLU MLP, RMSNorm, tied
embeddings).

Two details follow the served program rather than the published model, and
hold for random weights alike: the tied input embedding is scaled by
sqrt(hidden_size), and every RMSNorm gain is stored as an offset from one.
"""
import math

import jax
import jax.numpy as jnp

from .common import HIGHEST, matmul, rmsnorm

#: configuration-file key -> the program's ArchConfig field
PROGRAM_KEYS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "qkv_bias": "qkv_bias",
}


#: Random weights at the usual scales make a model whose residual stream is
#: dominated by the input token's own (tied) embedding, so greedy decoding
#: repeats one token whatever the context holds, and a check of the served
#: tokens could not see a lost K/V write. The embeddings are drawn at a
#: tenth of the usual scale (the final norm's gain is ten, so the logits
#: stay unit-scale) and the attention and MLP output projections at three
#: times, so that each token depends on its context.
EMB_SCALE = 0.1
OUT_GAIN = 3.0


def param_spec(m):
    L, d, v = m["num_hidden_layers"], m["hidden_size"], m["vocab_size"]
    hq, hkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    ff = m["intermediate_size"]

    def dense(*shape, gain=1.0):
        return (shape, ("normal", gain / math.sqrt(shape[-2]), 0.0))

    def small(*shape):
        return (shape, ("normal", 0.1, 0.0))

    return {
        "emb": {"tok_emb": ((v, d), ("normal", EMB_SCALE * d ** -0.5, 0.0))},
        "layers": {
            "attn": {"wq": dense(L, d, hq * hd), "wk": dense(L, d, hkv * hd),
                     "wv": dense(L, d, hkv * hd),
                     "wo": dense(L, hq * hd, d, gain=OUT_GAIN),
                     "bq": small(L, hq * hd), "bk": small(L, hkv * hd),
                     "bv": small(L, hkv * hd)},
            "mlp": {"w_gate": dense(L, d, ff), "w_up": dense(L, d, ff),
                    "w_down": dense(L, ff, d, gain=OUT_GAIN)},
            "norm1": small(L, d),
            "norm2": small(L, d),
        },
        "final_norm": ((d,), ("normal", 0.1, 1.0 / EMB_SCALE - 1.0)),
    }


def _rope(x, pos, theta):
    """x [S, H, D]; rotate-half convention."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None, None].astype(jnp.float32) * freqs
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def logits(m, params, tokens, score_pos, quant=None):
    """tokens [S] int32, score_pos [n] int32 -> float32 logits [n, V] of the
    next token after each scored position."""
    hq, hkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    g = hq // hkv
    s = tokens.shape[0]
    emb = params["emb"]["tok_emb"].astype(jnp.float32)
    pos = jnp.arange(s)
    causal = pos[:, None] >= pos[None, :]

    def layer(x, p):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
        a = p["attn"]
        h = rmsnorm(x, p["norm1"], eps)
        q = (matmul(h, a["wq"], quant) + a["bq"]).reshape(s, hq, hd)
        k = (matmul(h, a["wk"], quant) + a["bk"]).reshape(s, hkv, hd)
        v = (matmul(h, a["wv"], quant) + a["bv"]).reshape(s, hkv, hd)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        q = q.reshape(s, hkv, g, hd)      # head h reads kv head h // g
        sc = jnp.einsum("qkgd,skd->kgqs", q, k,
                        precision=HIGHEST) / math.sqrt(hd)
        sc = jnp.where(causal, sc, -jnp.inf)
        o = jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(sc, axis=-1), v,
                       precision=HIGHEST)
        x = x + matmul(o.reshape(s, hq * hd), a["wo"], quant)
        h = rmsnorm(x, p["norm2"], eps)
        mp = p["mlp"]
        u = jax.nn.silu(matmul(h, mp["w_gate"], quant)) * matmul(
            h, mp["w_up"], quant)
        return x + matmul(u, mp["w_down"], quant), None

    x = emb[tokens] * math.sqrt(m["hidden_size"])
    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = rmsnorm(x[score_pos], params["final_norm"].astype(jnp.float32), eps)
    return matmul(x, emb.T, quant)
