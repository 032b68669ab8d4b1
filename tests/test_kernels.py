"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret mode) + hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                      # no new deps: deterministic shim
    from tests._compat import given, settings, st

from repro.kernels import ops, ref


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape).astype(dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("s", [64, 128, 320])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2), (6, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(s, hq, hkv, dtype):
    ks = jax.random.split(jax.random.key(s * hq + hkv), 3)
    d, b = 64, 2
    q = _rand(ks[0], (b, s, hq, d), dtype)
    k = _rand(ks[1], (b, s, hkv, d), dtype)
    v = _rand(ks[2], (b, s, hkv, d), dtype)
    out = ops.flash_attention(q, k, v, causal=True, bq=64, bk=64)
    exp = ref.attention(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [16, 64, 100])
def test_flash_attention_sliding_window(window):
    ks = jax.random.split(jax.random.key(window), 3)
    b, s, h, d = 1, 128, 4, 64
    q, k, v = (_rand(ks[i], (b, s, h, d)) for i in range(3))
    out = ops.flash_attention(q, k, v, causal=True, window=window, bq=64, bk=64)
    exp = ref.attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(out, exp, atol=2e-5, rtol=2e-5)


@settings(max_examples=10, deadline=None)
@given(sq=st.sampled_from([32, 64, 96]),
       seed=st.integers(0, 2 ** 16))
def test_flash_attention_property_rowsum(sq, seed):
    """Softmax invariance: attention output of constant V is constant."""
    ks = jax.random.split(jax.random.key(seed), 2)
    b, h, d = 1, 2, 32
    q = _rand(ks[0], (b, sq, h, d))
    k = _rand(ks[1], (b, sq, h, d))
    v = jnp.ones((b, sq, h, d))
    out = ops.flash_attention(q, k, v, causal=True, bq=32, bk=32)
    np.testing.assert_allclose(out, jnp.ones_like(out), atol=1e-5)


# ---------------------------------------------------------------------------
# paged attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2), (6, 1)])
@pytest.mark.parametrize("window", [0, 5])
def test_paged_attention_kernel_vs_ref(hq, hkv, window):
    """Block-table gather + online softmax over valid blocks only == the
    pure-jnp paged oracle, across GQA group sizes and sliding windows."""
    ks = jax.random.split(jax.random.key(hq * 31 + hkv + window), 3)
    nb, bs, d, b, mb = 10, 8, 32, 3, 4
    kp = _rand(ks[0], (nb, hkv, bs, d))
    vp = _rand(ks[1], (nb, hkv, bs, d))
    q = _rand(ks[2], (b, hq, d))
    tables = jnp.array([[3, 7, -1, -1], [0, 1, 2, 9], [5, -1, -1, -1]],
                       jnp.int32)
    pos = jnp.array([12, 30, 2], jnp.int32)
    out = ops.paged_attention(q, kp, vp, tables, pos, window)
    exp = ref.paged_attention(q, kp, vp, tables, pos, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)


def test_paged_attention_matches_contiguous_layout():
    """Paging a contiguous K/V prefix through an arbitrary block table gives
    the same answer as dense decode attention over that prefix."""
    ks = jax.random.split(jax.random.key(11), 3)
    bs, h, d, s = 4, 2, 16, 13
    mb = 4
    kc = _rand(ks[0], (1, mb * bs, h, d))
    vc = _rand(ks[1], (1, mb * bs, h, d))
    q = _rand(ks[2], (1, h, d))
    perm = jnp.array([5, 0, 3, 7], jnp.int32)        # scattered block homes
    kp = jnp.zeros((8, h, bs, d)).at[perm].set(
        kc[0].reshape(mb, bs, h, d).transpose(0, 2, 1, 3))
    vp = jnp.zeros((8, h, bs, d)).at[perm].set(
        vc[0].reshape(mb, bs, h, d).transpose(0, 2, 1, 3))
    out = ops.paged_attention(q, kp, vp, perm[None], jnp.array([s], jnp.int32))
    logits = jnp.einsum("bhd,bkhd->bhk", q, kc) / np.sqrt(d)
    logits = jnp.where(jnp.arange(mb * bs)[None, None] <= s, logits, -1e30)
    exp = jnp.einsum("bhk,bkhd->bhd", jax.nn.softmax(logits, axis=-1), vc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2 ** 16))
def test_paged_attention_property_rowsum(seed):
    """Softmax invariance: paged attention over constant V is constant, no
    matter how the blocks are scattered or how much padding the table has."""
    ks = jax.random.split(jax.random.key(seed), 2)
    nb, bs, h, d, b = 6, 4, 2, 16, 2
    kp = _rand(ks[0], (nb, h, bs, d))
    vp = jnp.ones((nb, h, bs, d))
    q = _rand(ks[1], (b, 2 * h, d))
    tables = jnp.array([[2, 4, -1], [1, -1, -1]], jnp.int32)
    pos = jnp.array([6, 1], jnp.int32)
    out = ops.paged_attention(q, kp, vp, tables, pos)
    np.testing.assert_allclose(np.asarray(out), 1.0, atol=1e-5)


# ---------------------------------------------------------------------------
# ssd scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("s,chunk", [(64, 16), (128, 32), (96, 32), (256, 128),
                                     (100, 32), (20, 64)])
@pytest.mark.parametrize("n,p", [(16, 32), (64, 64)])
def test_ssd_scan_sweep(s, chunk, n, p):
    ks = jax.random.split(jax.random.key(s + n), 4)
    b, h = 2, 3
    xdt = _rand(ks[0], (b, s, h, p))
    a_log = -jax.nn.softplus(_rand(ks[1], (b, s, h)))
    B = _rand(ks[2], (b, s, h, n)) * 0.5
    C = _rand(ks[3], (b, s, h, n)) * 0.5
    y = ops.ssd_scan(xdt, a_log, B, C, chunk=chunk)
    ye = ref.ssd(xdt, a_log, B, C)
    np.testing.assert_allclose(y, ye, atol=5e-4, rtol=5e-4)


def test_ssd_matches_model_chunked_path():
    """Kernel == the model's jnp chunked implementation == naive recurrence."""
    from repro.models.mamba2 import ssd_chunked
    ks = jax.random.split(jax.random.key(0), 4)
    b, s, h, p, n = 1, 64, 2, 16, 8
    xdt = _rand(ks[0], (b, s, h, p))
    a_log = -jax.nn.softplus(_rand(ks[1], (b, s, h)))
    B = _rand(ks[2], (b, s, h, n))
    C = _rand(ks[3], (b, s, h, n))
    naive = ref.ssd(xdt, a_log, B, C)
    chunked = ssd_chunked(xdt, a_log, B, C, chunk=16)
    kern = ops.ssd_scan(xdt, a_log, B, C, chunk=16)
    np.testing.assert_allclose(chunked, naive, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(kern, naive, atol=2e-4, rtol=2e-4)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2 ** 16))
def test_ssd_property_decay_zero_state(seed):
    """With a_log = -inf-ish (full decay), output reduces to C.B x per step."""
    ks = jax.random.split(jax.random.key(seed), 3)
    b, s, h, p, n = 1, 32, 1, 8, 4
    xdt = _rand(ks[0], (b, s, h, p))
    B = _rand(ks[1], (b, s, h, n))
    C = _rand(ks[2], (b, s, h, n))
    a_log = jnp.full((b, s, h), -40.0)
    y = ops.ssd_scan(xdt, a_log, B, C, chunk=8)
    exp = jnp.einsum("bshn,bshn,bshp->bshp",
                     C, B, xdt)                      # memoryless
    np.testing.assert_allclose(y, exp, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# grouped matmul
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("g,c,k,n", [(2, 64, 64, 64), (4, 96, 32, 80),
                                     (8, 128, 256, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_matmul_sweep(g, c, k, n, dtype):
    ks = jax.random.split(jax.random.key(g * c), 2)
    x = _rand(ks[0], (g, c, k), dtype)
    w = _rand(ks[1], (g, k, n), dtype)
    out = ops.grouped_matmul(x, w)
    exp = ref.grouped_matmul(x, w)
    tol = 1e-1 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2 ** 16),
       valid=st.lists(st.integers(0, 64), min_size=3, max_size=3))
def test_grouped_matmul_property_valid_rows(seed, valid):
    """Rows beyond valid_rows never contribute to the output."""
    ks = jax.random.split(jax.random.key(seed), 2)
    g, c, k, n = 3, 64, 32, 16
    x = _rand(ks[0], (g, c, k))
    w = _rand(ks[1], (g, k, n))
    vr = jnp.asarray(valid, jnp.int32)
    out = ops.grouped_matmul(x, w, vr)
    exp = ref.grouped_matmul(x, w, vr)
    np.testing.assert_allclose(out, exp, atol=1e-4, rtol=1e-4)
    for gi, v in enumerate(valid):
        assert bool(jnp.all(out[gi, v:] == 0.0))


# ---------------------------------------------------------------------------
# kernels inside models (use_pallas=True path)
# ---------------------------------------------------------------------------
def test_model_with_pallas_attention_matches():
    from repro.configs import get_config
    from repro.models.api import build_model, make_batch
    cfg = get_config("llama3.2-1b", smoke=True)
    batch = make_batch(cfg, 2, 64, jax.random.key(1))
    m0 = build_model(cfg)
    params = m0.init(jax.random.key(0))
    l0 = m0.forward(params, batch)
    m1 = build_model(cfg.replace(use_pallas=True))
    l1 = m1.forward(params, batch)
    np.testing.assert_allclose(l0, l1, atol=2e-3, rtol=2e-3)


def test_model_with_pallas_ssd_matches():
    from repro.configs import get_config
    from repro.models.api import build_model, make_batch
    cfg = get_config("mamba2-780m", smoke=True)
    batch = make_batch(cfg, 2, 64, jax.random.key(1))
    m0 = build_model(cfg)
    params = m0.init(jax.random.key(0))
    l0 = m0.forward(params, batch)
    m1 = build_model(cfg.replace(use_pallas=True))
    l1 = m1.forward(params, batch)
    np.testing.assert_allclose(l0, l1, atol=2e-3, rtol=2e-3)


# ---------------------------------------------------------------------------
# paged prefill (multi-token chunk through the block table)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2), (6, 1)])
@pytest.mark.parametrize("window", [0, 5])
def test_paged_prefill_kernel_vs_ref(hq, hkv, window):
    """C-token chunk attention with causal masking inside the chunk == the
    pure-jnp paged prefill oracle, across GQA group sizes and windows."""
    ks = jax.random.split(jax.random.key(hq * 37 + hkv + window), 3)
    nb, bs, d, b, mb, c = 10, 8, 32, 3, 4, 6
    kp = _rand(ks[0], (nb, hkv, bs, d))
    vp = _rand(ks[1], (nb, hkv, bs, d))
    q = _rand(ks[2], (b, c, hq, d))
    tables = jnp.array([[3, 7, -1, -1], [0, 1, 2, 9], [5, 6, -1, -1]],
                       jnp.int32)
    start = jnp.array([8, 24, 2], jnp.int32)     # chunks mid-table
    out = ops.paged_prefill_attention(q, kp, vp, tables, start, window)
    exp = ref.paged_prefill_attention(q, kp, vp, tables, start, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)


def test_paged_prefill_kernel_causal_inside_chunk():
    """Each query position in the chunk must ignore later in-chunk K/V: the
    chunk's first query row equals single-token decode at that position."""
    ks = jax.random.split(jax.random.key(5), 3)
    nb, bs, h, d, c = 6, 4, 2, 16, 4
    kp = _rand(ks[0], (nb, h, bs, d))
    vp = _rand(ks[1], (nb, h, bs, d))
    q = _rand(ks[2], (1, c, h, d))
    tables = jnp.array([[2, 0, -1]], jnp.int32)
    start = jnp.array([4], jnp.int32)
    chunk = ops.paged_prefill_attention(q, kp, vp, tables, start)
    single = ops.paged_attention(q[:, 0], kp, vp, tables, start)
    np.testing.assert_allclose(np.asarray(chunk[:, 0]), np.asarray(single),
                               atol=2e-5, rtol=2e-5)
