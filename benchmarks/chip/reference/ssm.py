"""Plain float32 reference of the Mamba-2 (SSD) language model: per layer
RMSNorm, input projection to (z, x, B, C, dt), depthwise causal conv with
SiLU, the selective state-space recurrence, gated RMSNorm, output
projection; tied embeddings.

The recurrence is computed in its quadratic (attention-like) form over the
whole sequence, in blocks of query rows,

    y_t = sum_{s <= t} (C_t . B_s) exp(sum_{s < r <= t} dt_r A) dt_s x_s
          + D x_t,

which needs no chunking and no carried state, so it shares nothing with the
chunked algorithm it checks. As in the served program, the tied input
embedding is scaled by sqrt(d_model) and every RMSNorm gain is stored as an
offset from one.
"""
import math

import jax
import jax.numpy as jnp

from .common import HIGHEST, matmul, rmsnorm

PROGRAM_KEYS = {
    "n_layer": "n_layers",
    "d_model": "d_model",
    "vocab_size": "vocab_size",
    "d_state": "ssm_state",
    "expand": "ssm_expand",
    "headdim": "ssm_headdim",
    "d_conv": "ssm_conv",
    "ngroups": "ssm_groups",
    "chunk_size": "ssm_chunk",
    "norm_eps": "norm_eps",
    "tie_embeddings": "tie_embeddings",
}

#: query rows per block of the quadratic form
ROW_BLOCK = 512


def _dims(m):
    d, n, p, g = m["d_model"], m["d_state"], m["headdim"], m["ngroups"]
    di = m["expand"] * d
    return d, di, di // p, p, n, g, di + 2 * g * n


#: The tied embeddings are drawn at a tenth of the usual scale (the final
#: norm's gain is ten, so the logits stay unit-scale), so that the residual
#: stream is not dominated by the input token's own embedding and each
#: greedy token depends on its context (see reference/dense.py).
EMB_SCALE = 0.1


def param_spec(m):
    L, v = m["n_layer"], m["vocab_size"]
    d, di, h, _, n, g, conv = _dims(m)

    def small(*shape):
        return (shape, ("normal", 0.1, 0.0))

    return {
        "emb": {"tok_emb": ((v, d), ("normal", EMB_SCALE * d ** -0.5, 0.0))},
        "layers": {
            "in_proj": ((L, d, 2 * di + 2 * g * n + h),
                        ("normal", 1.0 / math.sqrt(d), 0.0)),
            "conv_w": ((L, m["d_conv"], conv), ("normal", 0.3, 0.0)),
            "conv_b": small(L, conv),
            "A_log": ((L, h), ("log_uniform", 1.0, 16.0)),
            "dt_bias": ((L, h), ("dt_bias", 1e-3, 1e-1)),
            "D": ((L, h), ("normal", 0.1, 1.0)),
            "gate_norm": small(L, di),
            "out_proj": ((L, di, d), ("normal", 1.0 / math.sqrt(di), 0.0)),
            "norm": small(L, d),
        },
        "final_norm": ((d,), ("normal", 0.1, 1.0 / EMB_SCALE - 1.0)),
    }


def _ssd(xdt, a, B, C):
    """xdt [S, H, P], a [S, H] (log decay per step), B, C [S, G, N] ->
    y [S, H, P], by the quadratic form in blocks of ROW_BLOCK rows (S must
    be a multiple of it)."""
    s, h, p = xdt.shape
    g = B.shape[1]
    lc = jnp.cumsum(a, axis=0)                         # [S, H]
    src = jnp.arange(s)
    heads_per_group = h // g

    def block(i):
        t = i * ROW_BLOCK + jnp.arange(ROW_BLOCK)
        ct = jax.lax.dynamic_slice_in_dim(C, i * ROW_BLOCK, ROW_BLOCK)
        lt = jax.lax.dynamic_slice_in_dim(lc, i * ROW_BLOCK, ROW_BLOCK)
        cb = jnp.einsum("tgn,sgn->gts", ct, B, precision=HIGHEST)
        cb = jnp.repeat(cb, heads_per_group, axis=0)   # [H, T, S]
        expo = lt.T[:, :, None] - lc.T[:, None, :]
        expo = jnp.where(src[None, None, :] <= t[None, :, None], expo,
                         -jnp.inf)
        return jnp.einsum("hts,shp->thp", cb * jnp.exp(expo), xdt,
                          precision=HIGHEST)

    y = jax.lax.map(block, jnp.arange(s // ROW_BLOCK))
    return y.reshape(s, h, p)


def logits(m, params, tokens, score_pos, quant=None):
    """tokens [S] int32 (S a multiple of ROW_BLOCK), score_pos [n] int32 ->
    float32 logits [n, V] of the next token after each scored position."""
    d, di, h, p, n, g, conv = _dims(m)
    k = m["d_conv"]
    eps = m["norm_eps"]
    s = tokens.shape[0]
    emb = params["emb"]["tok_emb"].astype(jnp.float32)

    def layer(x, w):
        w = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), w)
        u = matmul(rmsnorm(x, w["norm"], eps), w["in_proj"], quant)
        z, xbc, dt = u[:, :di], u[:, di:di + conv], u[:, di + conv:]
        padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
        xbc = sum(padded[j:j + s] * w["conv_w"][j] for j in range(k))
        xbc = jax.nn.silu(xbc + w["conv_b"])
        xs = xbc[:, :di].reshape(s, h, p)
        B = xbc[:, di:di + g * n].reshape(s, g, n)
        C = xbc[:, di + g * n:].reshape(s, g, n)
        dt = jax.nn.softplus(dt + w["dt_bias"])
        a = dt * -jnp.exp(w["A_log"])
        y = _ssd(xs * dt[:, :, None], a, B, C) + w["D"][:, None] * xs
        y = rmsnorm(y.reshape(s, di) * jax.nn.silu(z), w["gate_norm"], eps)
        return x + matmul(y, w["out_proj"], quant), None

    x = emb[tokens] * math.sqrt(d)
    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = rmsnorm(x[score_pos], params["final_norm"].astype(jnp.float32), eps)
    return matmul(x, emb.T, quant)
