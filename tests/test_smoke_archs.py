"""Per-architecture smoke tests.

For every assigned architecture: instantiate the REDUCED variant of the same
family (<=2 layers, d_model<=512, <=4 experts), run one forward pass + one
train step on CPU, and assert output shapes + finiteness. Decode paths get a
smoke test too (3 decode steps match the prefill logits trajectory loosely).
"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models.api import build_model, make_batch

BATCH, SEQ = 2, 64


@pytest.fixture(scope="module", params=ARCH_IDS)
def arch_setup(request):
    cfg = get_config(request.param, smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def test_smoke_config_is_reduced(arch_setup):
    cfg, _, _ = arch_setup
    assert cfg.n_layers <= 2
    assert cfg.d_model <= 512
    assert cfg.n_experts <= 4


def test_forward_shapes_and_finite(arch_setup):
    cfg, model, params = arch_setup
    batch = make_batch(cfg, BATCH, SEQ, jax.random.key(1))
    logits = jax.jit(model.forward)(params, batch)
    assert logits.shape == (BATCH, SEQ, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_train_step_no_nans(arch_setup):
    cfg, model, params = arch_setup
    batch = make_batch(cfg, BATCH, SEQ, jax.random.key(2))

    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, batch)
    assert jnp.isfinite(loss), f"{cfg.arch_id}: loss={loss}"
    flat = jax.tree_util.tree_leaves(grads)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in flat)
    # loss near ln(vocab) at init (random labels)
    assert 0.1 * jnp.log(cfg.vocab_size) < loss < 3.0 * jnp.log(cfg.vocab_size)


def test_decode_step_shapes(arch_setup):
    cfg, model, params = arch_setup
    cache = model.init_cache(BATCH, SEQ)
    tok = jnp.zeros((BATCH, 1), jnp.int32)
    step = jax.jit(model.decode_step)
    logits, cache2 = step(params, cache, tok, jnp.int32(0))
    assert logits.shape == (BATCH, 1, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))
    # structure preserved
    assert jax.tree_util.tree_structure(cache) == jax.tree_util.tree_structure(cache2)
    logits, _ = step(params, cache2, tok, jnp.int32(1))
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_decode_matches_forward_prefix():
    """Greedy decode logits must match teacher-forced forward logits (dense)."""
    cfg = get_config("llama3.2-1b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(3), (1, 8), 0, cfg.vocab_size)
    full = model.forward(params, {"tokens": toks})

    cache = model.init_cache(1, 8)
    step = jax.jit(model.decode_step)
    for t in range(8):
        logits, cache = step(params, cache, toks[:, t:t + 1], jnp.int32(t))
        assert jnp.allclose(logits[0, 0], full[0, t], atol=2e-3), f"pos {t}"


def test_decode_matches_forward_prefix_ssm():
    """Recurrent decode must match the chunked-SSD training forward (mamba2)."""
    cfg = get_config("mamba2-780m", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(4), (1, 8), 0, cfg.vocab_size)
    full = model.forward(params, {"tokens": toks})

    cache = model.init_cache(1, 8)
    step = jax.jit(model.decode_step)
    for t in range(8):
        logits, cache = step(params, cache, toks[:, t:t + 1], jnp.int32(t))
        assert jnp.allclose(logits[0, 0], full[0, t], atol=2e-3), f"pos {t}"


@pytest.mark.parametrize("use_pallas", [False, True])
def test_ssm_prefill_state_matches_decode_steps(use_pallas):
    """The serve prefill's one chunked-SSD pass (the Pallas kernel or the
    jnp path) seeds the same decode state and last logits as scanning the
    prompt through decode steps, at a length that is not a chunk multiple."""
    from repro.models import mamba2
    cfg = get_config("mamba2-780m", smoke=True, use_pallas=use_pallas)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    s = 45
    toks = jax.random.randint(jax.random.key(5), (2, s), 0, cfg.vocab_size)
    logits, cache = jax.jit(lambda p, t: mamba2.prefill(cfg, p, t))(params,
                                                                    toks)

    want = model.init_cache(2, s)
    step = jax.jit(model.decode_step)
    for t in range(s):
        last, want = step(params, want, toks[:, t:t + 1], jnp.int32(t))
    assert logits.shape == last.shape
    assert jnp.allclose(logits, last, atol=2e-3)
    for name in ("conv", "ssm"):
        assert cache[name].shape == want[name].shape
        assert cache[name].dtype == want[name].dtype
        assert jnp.allclose(cache[name], want[name], atol=2e-4), name
