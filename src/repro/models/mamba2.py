"""Mamba2 (SSD — state-space duality, arXiv:2405.21060).

Training/prefill uses the chunked SSD algorithm: the sequence is split into
chunks of length Q; within a chunk the dual (attention-like) quadratic form
is used, across chunks a low-rank state [H, N, P] is carried by a scan. This
is exactly the block decomposition the paper derives, and it is what the
Pallas ``ssd_scan`` kernel implements on TPU (grid iterates chunks, carrying
the inter-chunk state in VMEM scratch).

Decode carries the recurrent state directly: h <- a*h + dt*(B (x) x),
y = C.h + D*x — O(1) per token, which is why mamba2 runs the long_500k shape.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.dist.sharding import shard
from repro.models import layers as L


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _dims(cfg):
    di = cfg.d_inner
    g, n = cfg.ssm_groups, cfg.ssm_state
    h, p = cfg.n_ssm_heads, cfg.ssm_headdim
    conv_ch = di + 2 * g * n
    return di, g, n, h, p, conv_ch


def init_block(key, cfg, dtype):
    di, g, n, h, p, conv_ch = _dims(cfg)
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    d_in_proj = 2 * di + 2 * g * n + h
    return {
        "in_proj": L._init_dense(ks[0], (d, d_in_proj), dtype),
        "conv_w": L._init_dense(ks[1], (cfg.ssm_conv, conv_ch), dtype, scale=0.3),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h)).astype(dtype),
        "dt_bias": jnp.zeros((h,), dtype),
        "D": jnp.ones((h,), dtype),
        "gate_norm": L.init_rmsnorm(di, dtype),
        "out_proj": L._init_dense(ks[3], (di, d), dtype),
        "norm": L.init_rmsnorm(d, dtype),
    }


def init_params(cfg, key):
    dtype = jnp.dtype(cfg.param_dtype)
    k_emb, k_layers = jax.random.split(key)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    return {
        "emb": L.init_embeddings(k_emb, cfg, dtype),
        "layers": jax.vmap(lambda k: init_block(k, cfg, dtype))(layer_keys),
        "final_norm": L.init_rmsnorm(cfg.d_model, dtype),
    }


# ---------------------------------------------------------------------------
# pieces
# ---------------------------------------------------------------------------
def causal_conv1d(x, w, b):
    """Depthwise causal conv. x: [B, S, C]; w: [K, C]."""
    k = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = sum(pad[:, i:i + x.shape[1], :] * w[i] for i in range(k))
    return y + b


def _project(cfg, p, x):
    """Shared input projection/split for both train and decode paths.

    Returns z [.., di], xBC [.., conv_ch] (pre-conv), dt [.., H].
    """
    di, g, n, h, _, conv_ch = _dims(cfg)
    zxbcdt = x @ p["in_proj"]
    z = zxbcdt[..., :di]
    xBC = zxbcdt[..., di:di + conv_ch]
    dt = zxbcdt[..., di + conv_ch:]
    return z, xBC, dt


def _split_xbc(cfg, xBC):
    di, g, n, h, ph, _ = _dims(cfg)
    x = xBC[..., :di]
    B = xBC[..., di:di + g * n]
    C = xBC[..., di + g * n:]
    shp = x.shape[:-1]
    x = x.reshape(*shp, h, ph)
    B = B.reshape(*shp, g, n)
    C = C.reshape(*shp, g, n)
    # broadcast groups -> heads
    rep = h // g
    B = jnp.repeat(B, rep, axis=-2)
    C = jnp.repeat(C, rep, axis=-2)
    return x, B, C


#: the SSD math is f32 (log decays, carried state); on a TPU the MXU would
#: round f32 einsum operands to bf16 at the default precision
_F32 = jax.lax.Precision.HIGHEST


def ssd_chunked(xdt, a_log, B, C, chunk: int = 256):
    """Chunked SSD scan (pure-jnp reference path used by the model).

    xdt: [B, S, H, P] (dt-scaled inputs);  a_log: [B, S, H] (log decay);
    B, C: [B, S, H, N].  Returns y: [B, S, H, P].
    """
    b, s, h, p = xdt.shape
    n = B.shape[-1]
    q = chunk if (s % chunk == 0 and s >= chunk) else _best_chunk(s)
    nc = s // q
    xdt = xdt.reshape(b, nc, q, h, p)
    a_log = a_log.reshape(b, nc, q, h)
    Bm = B.reshape(b, nc, q, h, n)
    Cm = C.reshape(b, nc, q, h, n)

    lc = jnp.cumsum(a_log, axis=2)                   # [b,nc,q,h] within-chunk
    l_last = lc[:, :, -1:, :]                        # total chunk decay

    # intra-chunk (dual/attention form)
    scores = jnp.einsum("bcihn,bcjhn->bchij", Cm, Bm, precision=_F32)
    li = lc.transpose(0, 1, 3, 2)                    # [b,nc,h,q]
    # valid (j <= i) exponents are <= 0; clamp the masked ones to avoid
    # inf * 0 -> NaN in the backward pass of the where().
    decay = jnp.exp(jnp.minimum(li[..., :, None] - li[..., None, :], 0.0))
    # decay[b,c,h,i,j] = exp(l_i - l_j), mask j<=i
    idx = jnp.arange(q)
    mask = idx[:, None] >= idx[None, :]
    m = jnp.where(mask, scores * decay, 0.0)
    y_intra = jnp.einsum("bchij,bcjhp->bcihp", m, xdt, precision=_F32)

    # chunk states: S_c = sum_j exp(l_last - l_j) B_j (x) xdt_j
    w = jnp.exp(l_last - lc)                         # [b,nc,q,h]
    states = jnp.einsum("bcjhn,bcjh,bcjhp->bchnp", Bm, w, xdt,
                        precision=_F32)

    # inter-chunk recurrence: T_c = gamma_c * T_{c-1} + S_c
    gamma = jnp.exp(l_last[:, :, 0, :])              # [b,nc,h]

    def scan_fn(t_prev, inp):
        g_c, s_c = inp
        t_new = g_c[:, :, None, None] * t_prev + s_c
        return t_new, t_prev                          # emit state *entering* chunk

    t0 = jnp.zeros((b, h, n, p), xdt.dtype)
    _, t_in = jax.lax.scan(scan_fn,
                           t0,
                           (gamma.swapaxes(0, 1), states.swapaxes(0, 1)))
    t_in = t_in.swapaxes(0, 1)                       # [b,nc,h,n,p]

    y_inter = jnp.einsum("bcihn,bcih,bchnp->bcihp", Cm, jnp.exp(lc), t_in,
                         precision=_F32)
    y = (y_intra + y_inter).reshape(b, s, h, p)
    return y


def _best_chunk(s: int) -> int:
    for q in (256, 128, 64, 32, 16, 8, 4, 2, 1):
        if s % q == 0:
            return q
    return 1


# ---------------------------------------------------------------------------
# block forward
# ---------------------------------------------------------------------------
def ssd_final_state(xdt, a_log, B):
    """The recurrent state after the last position of an SSD pass:
    h_S = sum_j exp(sum_{i>j} a_i) B_j (x) xdt_j -> [B, H, N, P] — what
    ``S`` decode steps from a zero state would carry.

    xdt: [B, S, H, P]; a_log: [B, S, H]; B: [B, S, H, N]."""
    later = jnp.cumsum(a_log[:, ::-1], axis=1)[:, ::-1] - a_log
    return jnp.einsum("bshn,bsh,bshp->bhnp", B, jnp.exp(later), xdt,
                      precision=_F32)


def block_fwd(cfg, p, x, return_state: bool = False):
    """x: [B, S, D] -> [B, S, D] (pre-norm residual applied by caller).
    ``return_state`` also returns the decode state after the last position,
    (conv [B, K-1, conv_ch], ssm [B, H, N, P]), as ``block_decode`` keeps
    it."""
    di, g, n, h, ph, conv_ch = _dims(cfg)
    z, xBC_in, dt = _project(cfg, p, x)
    xBC = jax.nn.silu(causal_conv1d(xBC_in, p["conv_w"], p["conv_b"]))
    xs, B, C = _split_xbc(cfg, xBC)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    a_log = (dt * A).astype(jnp.float32)             # log decay, [B,S,H]
    xdt = (xs.astype(jnp.float32) * dt[..., None])

    if cfg.use_pallas:
        from repro.kernels import ops as kops
        y = kops.ssd_scan(xdt, a_log, B.astype(jnp.float32), C.astype(jnp.float32),
                          chunk=cfg.ssm_chunk)
    else:
        y = ssd_chunked(xdt, a_log, B.astype(jnp.float32), C.astype(jnp.float32),
                        chunk=cfg.ssm_chunk)
    y = y + p["D"].astype(jnp.float32)[None, None, :, None] * xs.astype(jnp.float32)
    y = y.astype(x.dtype).reshape(*x.shape[:-1], di)
    y = shard(y, "batch", None, "inner_flat")

    y = L.rmsnorm(y * jax.nn.silu(z), p["gate_norm"], cfg.norm_eps)
    out = y @ p["out_proj"]
    if not return_state:
        return out
    k = cfg.ssm_conv - 1
    conv = jnp.pad(xBC_in, ((0, 0), (k, 0), (0, 0)))[:, -k:]
    return out, (conv, ssd_final_state(xdt, a_log, B.astype(jnp.float32)))


def block_decode(cfg, p, x, conv_state, ssm_state):
    """Single-token recurrent step.

    x: [B, 1, D]; conv_state: [B, K-1, conv_ch]; ssm_state: [B, H, N, P].
    """
    di, g, n, h, ph, conv_ch = _dims(cfg)
    z, xBC, dt = _project(cfg, p, x)                 # [B,1,...]
    # conv via state buffer
    full = jnp.concatenate([conv_state, xBC], axis=1)        # [B, K, C]
    y_conv = jnp.einsum("bkc,kc->bc", full, p["conv_w"]) + p["conv_b"]
    new_conv = full[:, 1:, :]
    xBC = jax.nn.silu(y_conv)[:, None, :]
    xs, B, C = _split_xbc(cfg, xBC)                  # [B,1,H,P] / [B,1,H,N]

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    a = jnp.exp(dt * A)[:, 0]                        # [B,H]
    xdt = (xs.astype(jnp.float32) * dt[..., None])[:, 0]      # [B,H,P]
    Bv, Cv = B.astype(jnp.float32)[:, 0], C.astype(jnp.float32)[:, 0]  # [B,H,N]

    new_state = (a[..., None, None] * ssm_state
                 + jnp.einsum("bhn,bhp->bhnp", Bv, xdt))
    y = jnp.einsum("bhn,bhnp->bhp", Cv, new_state)
    y = y + p["D"].astype(jnp.float32)[None, :, None] * xs.astype(jnp.float32)[:, 0]
    y = y.astype(x.dtype).reshape(x.shape[0], 1, di)

    y = L.rmsnorm(y * jax.nn.silu(z), p["gate_norm"], cfg.norm_eps)
    return y @ p["out_proj"], new_conv, new_state.astype(ssm_state.dtype)


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------
def forward(cfg, params, tokens):
    x = L.embed(params["emb"], cfg, tokens)

    def body(x, p):
        h = L.rmsnorm(x, p["norm"], cfg.norm_eps)
        x = x + block_fwd(cfg, p, h)
        return shard(x, "batch", None, None), None

    if cfg.remat != "none":
        body = jax.checkpoint(body)

    x, _ = L.scan_layers(cfg, body, x, params["layers"])
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return L.unembed(params["emb"], cfg, x)


def prefill(cfg, params, tokens):
    """Prompt pass for serving: the chunked SSD forward (the Pallas
    ``ssd_scan`` kernel under ``cfg.use_pallas``) over ``tokens`` [B, S]
    -> (last-position logits [B, 1, V], the decode cache ``init_cache``
    describes, holding the state after the last prompt token)."""
    x = L.embed(params["emb"], cfg, tokens)

    def body(x, p):
        h = L.rmsnorm(x, p["norm"], cfg.norm_eps)
        out, state = block_fwd(cfg, p, h, return_state=True)
        return shard(x + out, "batch", None, None), state

    x, (conv, ssm) = L.scan_layers(cfg, body, x, params["layers"])
    x = L.rmsnorm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    return (L.unembed(params["emb"], cfg, x),
            {"conv": conv.astype(jnp.dtype(cfg.dtype)), "ssm": ssm})


def loss_fn(cfg, params, batch):
    logits = forward(cfg, params, batch["tokens"])
    return L.cross_entropy(logits, batch["labels"], batch.get("loss_mask"))


def init_cache(cfg, batch: int, max_len: int, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    di, g, n, h, p, conv_ch = _dims(cfg)
    return {
        "conv": jnp.zeros((cfg.n_layers, batch, cfg.ssm_conv - 1, conv_ch), dtype),
        "ssm": jnp.zeros((cfg.n_layers, batch, h, n, p), jnp.float32),
    }


def decode_step(cfg, params, cache, tokens, pos):
    x = L.embed(params["emb"], cfg, tokens)

    def body(x, scanned):
        p, conv_s, ssm_s = scanned
        h = L.rmsnorm(x, p["norm"], cfg.norm_eps)
        out, new_conv, new_ssm = block_decode(cfg, p, h, conv_s, ssm_s)
        return x + out, (new_conv, new_ssm)

    x, (new_conv, new_ssm) = L.scan_layers(
        cfg, body, x, (params["layers"], cache["conv"], cache["ssm"]))
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = L.unembed(params["emb"], cfg, x)
    return logits, {"conv": new_conv, "ssm": new_ssm}
