"""Paged KV subsystem tests: BlockManager mechanics, watermark admission,
block-table reuse without leaks, chunked prefill == one-pass prefill,
paged-vs-contiguous exactness across attention families, preemption,
admission density vs the contiguous pool, sampling lanes, and sharded
(host-mesh) paged decode parity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.api import build_model
from repro.serve import BlockManager, ServeEngine, ServeRequest, sharded_engine

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs --xla_force_host_platform_device_count=8")

PAGED_ARCHS = ("llama3.2-1b", "olmoe-1b-7b", "phi-3-vision-4.2b")


def _model(arch="llama3.2-1b", **over):
    return build_model(get_config(arch, smoke=True).replace(**over))


def _requests(cfg, lengths, arrivals=None, max_new=5, seed=5):
    rng = np.random.default_rng(seed)
    arrivals = arrivals or [0.0] * len(lengths)
    return [ServeRequest(rng.integers(1, cfg.vocab_size, size=s)
                         .astype(np.int32),
                         max_new_tokens=max_new, arrival_time=a)
            for s, a in zip(lengths, arrivals)]


# ---------------------------------------------------------------------------
# BlockManager mechanics
# ---------------------------------------------------------------------------
def test_block_manager_length_proportional_alloc():
    pool = BlockManager(_model(), n_slots=4, max_len=32, block_size=8,
                        n_blocks=8, watermark=0.0)
    assert pool.blocks_for(40) == 5
    r = ServeRequest(np.zeros(17, np.int32), max_new_tokens=4)  # 3 blocks
    slot = pool.alloc_for(r)
    assert slot == 0
    assert (pool.tables[0] >= 0).sum() == 3          # ceil(17/8), not max_len
    assert pool.free_blocks == 5
    # growth appends one block when a boundary is crossed
    assert pool.ensure(slot, 24)
    assert (pool.tables[0] >= 0).sum() == 3          # 24 = 3*8 exactly
    assert pool.ensure(slot, 25)
    assert (pool.tables[0] >= 0).sum() == 4
    pool.free(slot)
    assert pool.free_blocks == 8
    assert (pool.tables[0] == -1).all()              # stale table cleared


def test_block_manager_fifo_reuse_and_guards():
    pool = BlockManager(_model(), n_slots=2, max_len=16, block_size=8,
                        n_blocks=3, watermark=0.0)
    a = pool.alloc_for(ServeRequest(np.zeros(8, np.int32), max_new_tokens=1))
    b = pool.alloc_for(ServeRequest(np.zeros(16, np.int32), max_new_tokens=0))
    assert (a, b) == (0, 1)
    first_blocks = list(pool.tables[0][pool.tables[0] >= 0])
    pool.free(a)
    with pytest.raises(ValueError):
        pool.free(a)                                 # double-free guard
    pool.free(b)
    # freed blocks recycle FIFO: slot 0's block returns before slot 1's
    c = pool.alloc_for(ServeRequest(np.zeros(8, np.int32), max_new_tokens=1))
    assert list(pool.tables[c][pool.tables[c] >= 0]) == first_blocks
    with pytest.raises(ValueError):
        pool.ensure(5, 1)                            # unallocated slot


def test_block_manager_watermark_admission():
    pool = BlockManager(_model(), n_slots=4, max_len=32, block_size=8,
                        n_blocks=6, watermark=0.34)    # reserve = 3 blocks
    assert pool.watermark_blocks == 3
    assert pool.can_admit(16)                          # 2 blocks, 4 - 2 >= 3?
    assert not pool.can_admit(32)                      # 4 blocks violates
    r = ServeRequest(np.zeros(16, np.int32), max_new_tokens=4)
    slot = pool.alloc_for(r)
    assert slot is not None and pool.free_blocks == 4
    # decode growth may eat the reserve...
    assert pool.ensure(slot, 40 - 8)
    assert pool.free_blocks == 2
    # ...but admission never does
    assert pool.alloc_for(r) is None


def test_block_manager_validate_request():
    pool = BlockManager(_model(), n_slots=2, max_len=16, block_size=4,
                        n_blocks=4, watermark=0.0)
    with pytest.raises(ValueError):                    # table span
        pool.validate_request(ServeRequest(np.zeros(14, np.int32),
                                           max_new_tokens=4))
    with pytest.raises(ValueError):                    # total blocks
        BlockManager(_model(), n_slots=2, max_len=32, block_size=4,
                     n_blocks=4, watermark=0.0).validate_request(
            ServeRequest(np.zeros(20, np.int32), max_new_tokens=4))
    with pytest.raises(ValueError):                    # watermark-infeasible
        BlockManager(_model(), n_slots=2, max_len=16, block_size=4,
                     n_blocks=4, watermark=0.5).validate_request(
            ServeRequest(np.zeros(12, np.int32), max_new_tokens=2))


def test_block_manager_report_occupancy_and_fragmentation():
    pool = BlockManager(_model(), n_slots=2, max_len=16, block_size=8,
                        n_blocks=4, watermark=0.0)
    pool.alloc_for(ServeRequest(np.zeros(9, np.int32), max_new_tokens=1))
    rep = pool.report()
    assert rep["used_blocks"] == 2 and rep["occupancy"] == 0.5
    assert rep["used_tokens"] == 9 and rep["allocated_tokens"] == 16
    assert rep["internal_fragmentation"] == pytest.approx(7 / 16)


def test_block_manager_rejects_recurrent_family():
    with pytest.raises(ValueError):
        BlockManager(_model("mamba2-780m"), n_slots=2, max_len=16)
    with pytest.raises(ValueError):
        ServeEngine(get_config("mamba2-780m", smoke=True), cache="paged")


# ---------------------------------------------------------------------------
# chunked prefill == one-pass prefill
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", PAGED_ARCHS)
def test_chunked_prefill_matches_one_pass(arch):
    cfg = get_config(arch, smoke=True).replace(decode_attention="paged")
    ccfg = cfg.replace(decode_attention="contiguous")
    model, cmodel = build_model(cfg), build_model(ccfg)
    params = model.init(jax.random.key(0))
    s, bs = 11, 4
    prompt = jax.random.randint(jax.random.key(1), (1, s), 0, cfg.vocab_size)

    full_logits, (k_full, v_full) = cmodel.module.forward(
        ccfg, params, prompt, return_cache=True)

    cache = model.init_paged_cache(8, bs)
    tables = np.full((1, 6), -1, np.int32)
    nblk = -(-s // bs)
    tables[0, :nblk] = np.arange(nblk)
    tables = jnp.asarray(tables)
    state = model.paged_prefill_state(1)
    for i0 in range(0, s, bs):
        logits, cache, state = model.paged_prefill_chunk(
            params, cache, prompt[:, i0:i0 + bs], jnp.int32(i0), tables,
            state, s)
    np.testing.assert_allclose(np.asarray(logits[0, -1]),
                               np.asarray(full_logits[0, -1]),
                               atol=2e-4, rtol=2e-4)
    # the paged cache holds the same K/V at every valid logical position
    paged_k = np.asarray(cache["k"])[:, tables[0, :nblk]]    # [L,NB,H,BS,D]
    paged_k = paged_k.transpose(0, 1, 3, 2, 4).reshape(
        cfg.n_layers, 1, nblk * bs, cfg.n_kv_heads, -1)
    np.testing.assert_allclose(paged_k[:, :, :s],
                               np.asarray(k_full), atol=1e-5, rtol=1e-5)


def test_paged_prefill_ignores_stale_blocks():
    """A dirty block pool (a previous tenant's K/V everywhere) must produce
    the same outputs as a fresh pool: the gather mask can never reach beyond
    a request's own written positions."""
    cfg = get_config("llama3.2-1b", smoke=True).replace(
        decode_attention="paged")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    s, bs = 7, 4
    prompt = jax.random.randint(jax.random.key(1), (1, s), 0, cfg.vocab_size)
    tables = jnp.asarray(np.array([[3, 1, -1]], np.int32))

    def run(cache):
        state = model.paged_prefill_state(1)
        for i0 in range(0, s, bs):
            logits, cache, state = model.paged_prefill_chunk(
                params, cache, prompt[:, i0:i0 + bs], jnp.int32(i0), tables,
                state, s)
        tok = jnp.argmax(logits[0, -1])[None, None].astype(jnp.int32)
        dl, _ = model.paged_decode_step(params, cache, tok,
                                        jnp.full((1,), s, jnp.int32), tables)
        return logits, dl

    clean = model.init_paged_cache(6, bs)
    dirty = jax.tree_util.tree_map(lambda l: jnp.ones_like(l) * 37.0, clean)
    for a, b in zip(run(clean), run(dirty)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-6)


def test_paged_kv_write_updates_only_its_layer_and_valid_rows():
    """The carried-pool write puts each valid (row, position) at its layer,
    block and offset, and touches nothing else: other layers, a -1 table
    entry, a position past the table, a padded lane position (``valid``
    False) and every block no row names (a prefix-shared or foreign block)
    keep their content. Two rows writing other offsets of one block (block
    4 here) both land."""
    from repro.models import layers as L
    n_layers, nb, hkv, bs, d = 3, 6, 2, 4, 8
    rng = np.random.default_rng(3)
    pool = rng.normal(size=(n_layers, nb, hkv, bs, d)).astype(np.float32)
    tables = np.array([[4, 1, -1], [0, 4, -1]], np.int32)
    positions = np.array([[2, 3, 4, 5], [4, 5, 8, 12]], np.int32)
    valid = np.array([[True, True, True, False], [True, False, True, True]])
    k = rng.normal(size=(2, 4, hkv, d)).astype(np.float32)
    out = L.paged_kv_write(
        L.PagedKV(jnp.asarray(pool), jnp.asarray(-pool), jnp.asarray(tables),
                  jnp.int32(1)),
        jnp.asarray(k), jnp.asarray(-k), jnp.asarray(positions),
        jnp.asarray(valid))
    want = pool.copy()
    for b in range(2):
        for c in range(4):
            p = positions[b, c]
            col = p // bs
            if valid[b, c] and col < tables.shape[1] and tables[b, col] >= 0:
                want[1, tables[b, col], :, p % bs] = k[b, c]
    np.testing.assert_array_equal(np.asarray(out.k), want)
    np.testing.assert_array_equal(np.asarray(out.v), -want)


# ---------------------------------------------------------------------------
# paged continuous == contiguous static, per request
# ---------------------------------------------------------------------------
def _identity_case(cfg, case):
    """(cfg, requests(with_arrivals), max_len, paged engine kwargs) of one
    paged-vs-contiguous identity case. Every case but ``staggered`` runs
    several decode horizons."""
    kw = dict(n_slots=3, block_size=4)
    lengths, arrivals, budgets = [5, 3, 8, 2, 6], [0.0, 0.0, 1.0, 3.0, 4.0], None
    common = None
    max_len = 32
    if case == "prefix":        # later arrivals hit the donor's two blocks
        rng = np.random.default_rng(21)
        common = rng.integers(1, cfg.vocab_size, size=8).astype(np.int32)
        lengths, arrivals = [1, 2, 3, 4], [0.0, 2.0, 4.0, 6.0]
        budgets, kw = [9] * 4, dict(n_slots=2, block_size=4, decode_horizon=4)
    elif case == "padded":      # every prompt ends in a padded chunk
        lengths, arrivals = [7, 10, 13, 6], [0.0] * 4
        budgets = [9] * 4
        kw = dict(n_slots=4, block_size=4, prefill_lanes=2, decode_horizon=4)
    elif case == "frozen":      # rows finish and freeze mid-horizon
        lengths, arrivals = [6, 4, 9, 5], [0.0] * 4
        budgets, kw = [2, 11, 5, 8], dict(n_slots=4, block_size=4)
    elif case == "windowed":    # one local (window 8) and one global layer
        cfg = cfg.replace(sliding_window=8, global_every=2)
        lengths, arrivals = [12, 19, 9], [0.0, 0.0, 2.0]
        budgets, max_len = [10, 10, 12], 48
        kw = dict(n_slots=2, block_size=4, decode_horizon=4)

    def reqs(with_arrivals):
        rs = _requests(cfg, lengths, arrivals if with_arrivals else None)
        for i, r in enumerate(rs):
            if common is not None:
                r.prompt = np.concatenate([common, r.prompt])
            if budgets is not None:
                r.max_new_tokens = budgets[i]
        return rs
    return cfg, reqs, max_len, kw


IDENTITY_CASES = (
    [pytest.param(a, "staggered", id=a) for a in PAGED_ARCHS]
    + [pytest.param(a, c, id=f"{a}-{c}")
       for a in ("llama3.2-1b", "olmoe-1b-7b")
       for c in ("prefix", "padded", "frozen")]
    + [pytest.param("gemma3-27b", "windowed", id="gemma3-27b-windowed")])


@pytest.mark.parametrize("arch,case", IDENTITY_CASES)
def test_paged_matches_contiguous_static_per_request(arch, case):
    """Mixed lengths, staggered arrivals, block reuse — paged continuous
    outputs must be token-for-token identical to one contiguous static batch
    (the acceptance invariant, also checked by launch.serve --verify).
    Further cases: prefix-cache hits, padded final prefill chunks, rows
    frozen mid-horizon, a sliding-window config, on dense and MoE."""
    cfg, reqs, max_len, kw = _identity_case(get_config(arch, smoke=True),
                                            case)
    params = build_model(cfg).init(jax.random.key(0))

    static, _ = ServeEngine(cfg, params=params, max_len=max_len).run(
        reqs(False))
    paged, stats = ServeEngine(cfg, params=params, max_len=max_len,
                               cache="paged", **kw).run(reqs(True))

    for a, b in zip(static, paged):
        assert a.output == b.output
    assert all(r.finished_at is not None for r in paged)
    assert stats.block_report["block_size"] == 4
    if case == "staggered":
        # idle-slot compaction: the paged engine decoded fewer rows than
        # steps * n_slots would have
        assert stats.decode_rows_saved > 0.0
    else:
        assert stats.decode_dispatches > 1
    if case == "prefix":
        assert stats.prefix_blocks_hit > 0
    if case == "frozen":
        assert stats.decode_dispatches < stats.steps


def test_paged_programs_donate_the_pool():
    """The paged prefill round and decode horizon are given the K/V pool
    to keep: every pool a dispatch was handed is deleted after it, and the
    compiled program aliases at least the pool's bytes to its output. The
    contiguous horizon donates nothing."""
    cfg = get_config("llama3.2-1b", smoke=True)
    params = build_model(cfg).init(jax.random.key(0))
    struct = lambda t: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
    for cache, names in (("paged", ("_prefill", "_horizon")),
                         ("contiguous", ("_horizon",))):
        eng = ServeEngine(cfg, params=params, max_len=32, n_slots=2,
                          cache=cache, block_size=4)
        seen, jitted = {}, {}
        for name in names:
            jitted[name] = getattr(eng, name)

            def record(params, buffers, *a, _fn=jitted[name],
                       _log=seen.setdefault(name, []), **kw):
                _log.append((buffers, a, kw))
                return _fn(params, buffers, *a, **kw)
            setattr(eng, name, record)
        eng.run(_requests(cfg, [5, 6], max_new=6))
        donated = cache == "paged"
        for name in names:
            assert seen[name], name
            for buffers, _, _ in seen[name]:
                assert all(leaf.is_deleted() == donated
                           for leaf in jax.tree_util.tree_leaves(buffers))
            buffers, a, kw = seen[name][0]
            pool_bytes = sum(leaf.nbytes
                             for leaf in jax.tree_util.tree_leaves(buffers))
            alias = jitted[name].lower(params, struct(buffers), *struct(a),
                                       **kw).compile().memory_analysis() \
                .alias_size_in_bytes
            assert (alias >= pool_bytes) if donated else alias == 0, \
                (cache, name, alias, pool_bytes)


def test_recorded_prompt_logits_match_across_backends():
    """``record_logits`` keeps each request's own prompt logits: the paged
    engine's lane-batched chunks hand every request the logits of its last
    prompt position, equal to the contiguous one-pass prefill's, and the
    first greedy token is their argmax."""
    cfg = get_config("qwen2-0.5b", smoke=True)
    lengths, arrivals = [9, 3, 14, 6], [0.0, 0.0, 1.0, 2.0]
    contig, _ = ServeEngine(cfg, max_len=32, record_logits=True).run(
        _requests(cfg, lengths, max_new=2))
    paged, _ = ServeEngine(cfg, max_len=32, n_slots=2, cache="paged",
                           block_size=4, prefill_lanes=2,
                           record_logits=True).run(
        _requests(cfg, lengths, arrivals, max_new=2))
    for a, b in zip(contig, paged):
        assert b.prefill_logits.shape == (cfg.vocab_size,)
        np.testing.assert_allclose(b.prefill_logits, a.prefill_logits,
                                   atol=2e-4, rtol=2e-4)
        assert b.output[0] == int(np.argmax(b.prefill_logits))
    assert ServeEngine(cfg, max_len=32).run(
        _requests(cfg, lengths))[0][0].prefill_logits is None


def test_paged_block_reuse_never_leaks_prior_kv():
    """A freed request's blocks are re-issued to a new tenant (the pool is
    sized so reuse is forced) and the tenant's outputs equal a run on a
    fresh pool — the block-granular mirror of the slot-recycle test."""
    cfg = get_config("llama3.2-1b", smoke=True)
    params = build_model(cfg).init(jax.random.key(0))
    lengths = [6, 7, 5]
    # 4 blocks of 4 = 16 positions: each request needs 2-3 blocks, so with
    # one slot every later request reuses the earlier tenants' blocks.
    shared, _ = ServeEngine(cfg, params=params, max_len=16, n_slots=1,
                            cache="paged", block_size=4, n_blocks=4,
                            watermark=0.0).run(_requests(cfg, lengths))
    for r in shared:
        fresh, _ = ServeEngine(cfg, params=params, max_len=16,
                               cache="paged", block_size=4).run(
            [ServeRequest(r.prompt.copy(),
                          max_new_tokens=r.max_new_tokens)])
        assert fresh[0].output == r.output


def test_paged_preemption_regenerates_identically():
    """Under block pressure the engine preempts the most recently admitted
    request; after re-admission its tokens regenerate identically."""
    cfg = get_config("llama3.2-1b", smoke=True)
    params = build_model(cfg).init(jax.random.key(0))
    reqs = _requests(cfg, [8, 8], max_new=8)
    static, _ = ServeEngine(cfg, params=params, max_len=32).run(
        _requests(cfg, [8, 8], max_new=8))
    # each request grows to 16 tokens = 4 blocks; 6 blocks cannot hold both
    paged, stats = ServeEngine(cfg, params=params, max_len=32, n_slots=2,
                               cache="paged", block_size=4, n_blocks=6,
                               watermark=0.0).run(reqs)
    assert stats.preemptions >= 1
    for a, b in zip(static, paged):
        assert a.output == b.output


def test_paged_admits_where_contiguous_refuses():
    """Equal token budgets: the contiguous pool rejects a prompt longer than
    its per-slot max_len outright, and serves fewer requests concurrently at
    mixed lengths — the admission-density acceptance criterion."""
    cfg = get_config("llama3.2-1b", smoke=True)
    params = build_model(cfg).init(jax.random.key(0))
    budget = 128                                     # cache positions

    # (a) hard refusal: one 40-token prompt. Contiguous spends the budget as
    # 4 slots x 32 positions -> submit raises; paged spans 64 positions of
    # table while spending the same 128 pooled positions -> serves it.
    long_req = [ServeRequest(np.arange(1, 41, dtype=np.int32),
                             max_new_tokens=4)]
    with pytest.raises(ValueError):
        ServeEngine(cfg, params=params, max_len=32, n_slots=4).run(
            [ServeRequest(long_req[0].prompt.copy(), max_new_tokens=4)])
    out, _ = ServeEngine(cfg, params=params, max_len=64, n_slots=4,
                         cache="paged", block_size=8, n_blocks=16,
                         watermark=0.0).run(long_req)
    assert len(out[0].output) == 4

    # (b) density: 8 mixed-length requests. Contiguous: 128/32 = 4 slots.
    # Paged: same 128 positions as 16 blocks of 8 serve all 8 at once.
    lengths = [4, 6, 5, 7, 4, 6, 5, 7]
    cont, cs = ServeEngine(cfg, params=params, max_len=32, n_slots=4).run(
        _requests(cfg, lengths, max_new=4))
    paged, ps = ServeEngine(cfg, params=params, max_len=32, n_slots=8,
                            cache="paged", block_size=8, n_blocks=16,
                            watermark=0.0).run(_requests(cfg, lengths,
                                                         max_new=4))
    assert cs.max_active == 4
    assert ps.max_active == 8
    assert ps.steps < cs.steps
    for a, b in zip(cont, paged):
        assert a.output == b.output


# ---------------------------------------------------------------------------
# sampling lanes (per-slot RNG)
# ---------------------------------------------------------------------------
def test_sampling_lanes_deterministic_and_greedy_default():
    cfg = get_config("llama3.2-1b", smoke=True)
    params = build_model(cfg).init(jax.random.key(0))
    lengths = [5, 3, 6]

    greedy, _ = ServeEngine(cfg, params=params, max_len=32).run(
        _requests(cfg, lengths))
    # top-k=1 sampling degenerates to greedy whatever the temperature
    top1, _ = ServeEngine(cfg, params=params, max_len=32, temperature=0.9,
                          top_k=1).run(_requests(cfg, lengths))
    for a, b in zip(greedy, top1):
        assert a.output == b.output

    eng = ServeEngine(cfg, params=params, max_len=32, temperature=8.0,
                      sample_seed=7)
    s1, _ = eng.run(_requests(cfg, lengths))
    s2, _ = eng.run(_requests(cfg, lengths))
    for a, b in zip(s1, s2):                 # same lanes -> same samples
        assert a.output == b.output
    assert any(a.output != g.output for a, g in zip(s1, greedy))


def test_sampling_lanes_work_with_paged_cache():
    cfg = get_config("llama3.2-1b", smoke=True)
    params = build_model(cfg).init(jax.random.key(0))
    eng = ServeEngine(cfg, params=params, max_len=32, n_slots=2,
                      cache="paged", block_size=4, temperature=0.8,
                      sample_seed=3)
    out, _ = eng.run(_requests(cfg, [5, 4, 6], max_new=4))
    assert all(len(r.output) == 4 for r in out)


# ---------------------------------------------------------------------------
# Pallas kernel path inside the model
# ---------------------------------------------------------------------------
def test_paged_decode_step_pallas_matches_gather():
    cfg = get_config("llama3.2-1b", smoke=True).replace(
        decode_attention="paged")
    model = build_model(cfg)
    pmodel = build_model(cfg.replace(use_pallas=True))
    params = model.init(jax.random.key(0))
    s, bs = 6, 4
    prompt = jax.random.randint(jax.random.key(1), (1, s), 0, cfg.vocab_size)
    cache = model.init_paged_cache(6, bs)
    tables = jnp.asarray(np.array([[0, 1, -1, -1]], np.int32))
    state = model.paged_prefill_state(1)
    for i0 in range(0, s, bs):
        logits, cache, state = model.paged_prefill_chunk(
            params, cache, prompt[:, i0:i0 + bs], jnp.int32(i0), tables,
            state, s)
    tok = jnp.argmax(logits[0, -1])[None, None].astype(jnp.int32)
    pos = jnp.full((1,), s, jnp.int32)
    ref_logits, _ = model.paged_decode_step(params, cache, tok, pos, tables)
    pal_logits, _ = pmodel.paged_decode_step(params, cache, tok, pos, tables)
    np.testing.assert_allclose(np.asarray(ref_logits), np.asarray(pal_logits),
                               atol=2e-3, rtol=2e-3)


# ---------------------------------------------------------------------------
# sharded (host-mesh) paged serving
# ---------------------------------------------------------------------------
@needs_mesh
def test_sharded_paged_matches_single_device_contiguous():
    cfg = get_config("qwen2-0.5b", smoke=True)
    lengths, arrivals = [5, 3, 8, 2, 6, 4], [0.0] * 3 + [2.0] * 3

    single, _ = ServeEngine(cfg, max_len=32).run(_requests(cfg, lengths))
    eng = sharded_engine(cfg, n_slots=4, max_len=32, cache="paged",
                         block_size=8)
    sharded, stats = eng.run(_requests(cfg, lengths, arrivals))

    for a, b in zip(single, sharded):
        assert a.output == b.output
    assert stats.block_report is not None
    # the paged pool's K/V leaves really are laid out sharded
    shardings = jax.tree_util.tree_leaves(eng.sharding.cache_sharding)
    assert shardings and all(not s.is_fully_replicated for s in shardings)


# ---------------------------------------------------------------------------
# prefix cache: refcounting, copy-on-write tail, token identity
# ---------------------------------------------------------------------------
def _prefix_pool(**over):
    kw = dict(n_slots=4, max_len=32, block_size=4, n_blocks=16,
              watermark=0.0, prefix_cache=True)
    kw.update(over)
    return BlockManager(_model(), **kw)


def _commit_full_blocks(pool, slot, prompt_len):
    """Simulate the engine's prefill marking each full block written."""
    for j in range(prompt_len // pool.block_size):
        pool.commit_block(slot, j, None)


def test_prefix_cache_shares_full_blocks_and_defers_unready():
    pool = _prefix_pool()
    prompt = np.arange(1, 15, dtype=np.int32)          # 14 tokens: 3F + 1P
    a = pool.alloc_for(ServeRequest(prompt, max_new_tokens=2))
    # same prompt while the donor has not prefilled yet: deferred, not raced
    assert pool.alloc_for(ServeRequest(prompt.copy(), max_new_tokens=2)) \
        is None
    _commit_full_blocks(pool, a, len(prompt))
    b = pool.alloc_for(ServeRequest(prompt.copy(), max_new_tokens=2))
    assert b is not None
    # the three full prefix blocks alias; the partial tail never does
    assert list(pool.tables[b][:3]) == list(pool.tables[a][:3])
    assert pool.tables[b][3] != pool.tables[a][3]
    assert pool.cached_tokens(b) == 3 * pool.block_size
    assert pool.cached_tokens(a) == 0
    # shared blocks are counted once: 4 (donor) + 1 (tail) blocks in use
    assert pool.free_blocks == pool.n_blocks - 5


def test_prefix_cache_last_chunk_never_served_from_cache():
    """A block-aligned prompt keeps its final chunk out of the hit range —
    its logits seed the first generated token, so it must be computed."""
    pool = _prefix_pool()
    prompt = np.arange(1, 13, dtype=np.int32)          # 12 tokens: 3 full
    a = pool.alloc_for(ServeRequest(prompt, max_new_tokens=2))
    _commit_full_blocks(pool, a, len(prompt))
    b = pool.alloc_for(ServeRequest(prompt.copy(), max_new_tokens=2))
    assert pool.cached_tokens(b) == 2 * pool.block_size   # not 3
    assert pool.tables[b][2] != pool.tables[a][2]


def test_prefix_cache_refcount_free_preempt_cycles_leak_no_blocks():
    pool = _prefix_pool()
    prompt = np.arange(1, 15, dtype=np.int32)
    for cycle in range(3):
        a = pool.alloc_for(ServeRequest(prompt, max_new_tokens=2))
        _commit_full_blocks(pool, a, len(prompt))
        b = pool.alloc_for(ServeRequest(prompt.copy(), max_new_tokens=2))
        pool.free(a)                                   # donor leaves first
        pool.free(b)                                   # then the sharer
        # every block is reclaimable; the prefix blocks stay cached
        assert pool.free_blocks == pool.n_blocks
        assert pool.evictable_blocks == 3
    # a re-arrival revives the evictable blocks instead of recomputing
    c = pool.alloc_for(ServeRequest(prompt.copy(), max_new_tokens=2))
    assert pool.cached_tokens(c) == 3 * pool.block_size
    pool.free(c)
    assert pool.free_blocks == pool.n_blocks


def test_prefix_cache_eviction_reclaims_cached_blocks():
    pool = _prefix_pool(n_blocks=4)
    prompt = np.arange(1, 15, dtype=np.int32)          # needs all 4 blocks
    a = pool.alloc_for(ServeRequest(prompt, max_new_tokens=2))
    _commit_full_blocks(pool, a, len(prompt))
    pool.free(a)
    assert pool.evictable_blocks == 3
    other = np.arange(100, 114, dtype=np.int32)        # distinct content
    b = pool.alloc_for(ServeRequest(other, max_new_tokens=2))
    assert b is not None and pool.cached_tokens(b) == 0
    assert pool.evictable_blocks == 0                  # cache was evicted
    pool.free(b)
    assert pool.free_blocks == pool.n_blocks


@pytest.mark.parametrize("arch", PAGED_ARCHS)
def test_prefix_hit_prefill_token_identical_to_cold(arch):
    """Shared-prefix requests served with the prefix cache on must be
    token-for-token identical to cold contiguous-static serving, while a
    majority of their prompt blocks come from the cache (dense / moe — the
    carried expert-counts snapshot — / vlm)."""
    cfg = get_config(arch, smoke=True)
    params = build_model(cfg).init(jax.random.key(0))
    rng = np.random.default_rng(11)
    common = rng.integers(1, cfg.vocab_size, size=12).astype(np.int32)

    def reqs():
        r = np.random.default_rng(12)
        return [ServeRequest(
            np.concatenate([common,
                            r.integers(1, cfg.vocab_size,
                                       size=3 + i).astype(np.int32)]),
            max_new_tokens=4) for i in range(4)]

    cold, _ = ServeEngine(cfg, params=params, max_len=32).run(reqs())
    warm, stats = ServeEngine(cfg, params=params, max_len=32, n_slots=4,
                              cache="paged", block_size=4).run(reqs())
    for a, b in zip(cold, warm):
        assert a.output == b.output
    assert stats.prefix_blocks_hit > 0
    assert stats.prefix_hit_rate >= 0.5


def test_prefix_cache_off_is_hit_free_and_identical():
    cfg = get_config("llama3.2-1b", smoke=True)
    params = build_model(cfg).init(jax.random.key(0))
    prompt = np.arange(1, 14, dtype=np.int32)
    reqs = lambda: [ServeRequest(prompt.copy(), max_new_tokens=4)
                    for _ in range(3)]
    on, s_on = ServeEngine(cfg, params=params, max_len=32, n_slots=3,
                           cache="paged", block_size=4).run(reqs())
    off, s_off = ServeEngine(cfg, params=params, max_len=32, n_slots=3,
                             cache="paged", block_size=4,
                             prefix_cache=False).run(reqs())
    for a, b in zip(on, off):
        assert a.output == b.output
    assert s_on.prefix_blocks_hit > 0
    assert s_off.prefix_blocks_hit == 0 and s_off.prefix_blocks_total == 0


# ---------------------------------------------------------------------------
# batched prefill lanes
# ---------------------------------------------------------------------------
def test_batched_prefill_one_dispatch_per_chunk_round():
    """N equal-length requests joining together must prefill in
    O(chunk-rounds) dispatches at N lanes — not O(N x chunks) — and still
    match single-lane serving token for token."""
    cfg = get_config("llama3.2-1b", smoke=True)
    params = build_model(cfg).init(jax.random.key(0))
    lengths = [12, 12, 12, 12]                       # 3 chunks each at bs=4
    reqs = lambda: _requests(cfg, lengths, max_new=3)

    wide, sw = ServeEngine(cfg, params=params, max_len=32, n_slots=4,
                           cache="paged", block_size=4, prefix_cache=False,
                           prefill_lanes=4).run(reqs())
    narrow, sn = ServeEngine(cfg, params=params, max_len=32, n_slots=4,
                             cache="paged", block_size=4, prefix_cache=False,
                             prefill_lanes=1).run(reqs())
    for a, b in zip(wide, narrow):
        assert a.output == b.output
    assert sw.prefill_dispatches == 3                # one per chunk round
    assert sn.prefill_dispatches == 12               # one per request-chunk


def test_batched_prefill_mixed_lengths_lane_refill():
    """Lanes refill from the queue as short prompts finish, and padded tail
    chunks never perturb outputs (pad positions write no K/V)."""
    cfg = get_config("llama3.2-1b", smoke=True)
    params = build_model(cfg).init(jax.random.key(0))
    lengths = [13, 2, 7, 5, 11, 3]
    static, _ = ServeEngine(cfg, params=params, max_len=32).run(
        _requests(cfg, lengths))
    lanes, st = ServeEngine(cfg, params=params, max_len=32, n_slots=6,
                            cache="paged", block_size=4,
                            prefill_lanes=2).run(_requests(cfg, lengths))
    for a, b in zip(static, lanes):
        assert a.output == b.output
    assert st.prefill_dispatches < sum(-(-s // 4) for s in lengths)


# ---------------------------------------------------------------------------
# dispatch/time split accounting
# ---------------------------------------------------------------------------
def test_stats_phase_split_and_dispatch_counts():
    cfg = get_config("llama3.2-1b", smoke=True)
    _, st = ServeEngine(cfg, max_len=32, n_slots=2, cache="paged",
                        block_size=4).run(_requests(cfg, [5, 6], max_new=3))
    assert st.prefill_dispatches > 0 and st.decode_dispatches > 0
    assert st.prefill_s > 0.0 and st.decode_s > 0.0
    # multi-step horizons: one jitted dispatch covers up to K decode steps
    assert st.decode_horizon == 8
    assert st.decode_dispatches <= st.steps
    assert st.host_syncs > 0
    one, s1 = ServeEngine(cfg, max_len=32, n_slots=2, cache="paged",
                          block_size=4, decode_horizon=1).run(
        _requests(cfg, [5, 6], max_new=3))
    assert s1.decode_dispatches == s1.steps      # K=1 is the classic loop


@pytest.mark.parametrize("k", [1, 3, 8])
def test_paged_horizon_token_identity_under_churn(k):
    """Paged K-step horizons with admission churn, mid-horizon finishes,
    and block growth across horizon boundaries must stay token-identical
    to the contiguous static reference."""
    cfg = get_config("llama3.2-1b", smoke=True)
    params = build_model(cfg).init(jax.random.key(0))
    lengths, arrivals = [5, 3, 8, 2, 6], [0.0, 0.0, 1.0, 3.0, 4.0]
    budgets = [2, 9, 4, 7, 1]

    def reqs(with_arrivals):
        rs = _requests(cfg, lengths, arrivals if with_arrivals else None)
        for r, b in zip(rs, budgets):
            r.max_new_tokens = b
        return rs

    static, _ = ServeEngine(cfg, params=params, max_len=32,
                            decode_horizon=1).run(reqs(False))
    paged, st = ServeEngine(cfg, params=params, max_len=32, n_slots=3,
                            cache="paged", block_size=4,
                            decode_horizon=k).run(reqs(True))
    for a, b in zip(static, paged):
        assert a.output == b.output
    if k > 1:
        assert st.decode_dispatches < st.steps


def test_paged_horizon_shrinks_before_preempting():
    """A pool too tight to pre-allocate K=8 steps of growth must shrink the
    horizon (down to the classic one-step loop) rather than thrash through
    avoidable preemptions — and still match the static reference."""
    cfg = get_config("llama3.2-1b", smoke=True)
    params = build_model(cfg).init(jax.random.key(0))
    reqs = lambda: _requests(cfg, [8, 8], max_new=8)
    static, _ = ServeEngine(cfg, params=params, max_len=32,
                            decode_horizon=1).run(reqs())
    # 6 blocks of 4 cannot hold both requests at 16 tokens: the K=1 engine
    # preempts; the K=8 engine must behave identically at the same pool.
    paged, st = ServeEngine(cfg, params=params, max_len=32, n_slots=2,
                            cache="paged", block_size=4, n_blocks=6,
                            watermark=0.0, decode_horizon=8).run(reqs())
    assert st.preemptions >= 1
    for a, b in zip(static, paged):
        assert a.output == b.output


def test_deferred_sharer_does_not_block_unrelated_admission():
    """A request deferred behind a mid-prefill donor parks only itself:
    unrelated admissible requests behind it in FCFS order still admit in
    the same round (deferral is not pool exhaustion)."""
    from repro.serve import ContinuousScheduler
    pool = _prefix_pool()
    sched = ContinuousScheduler(pool)
    x = np.arange(1, 15, dtype=np.int32)
    y = np.arange(50, 64, dtype=np.int32)
    a = ServeRequest(x, max_new_tokens=2)
    b = ServeRequest(x.copy(), max_new_tokens=2)     # shares a's prefix
    c = ServeRequest(y, max_new_tokens=2)            # unrelated
    for r in (a, b, c):
        sched.submit(r)
    admitted = sched.admit()
    assert a in admitted and c in admitted and b not in admitted
    _commit_full_blocks(pool, a.slot, len(x))
    assert sched.admit() == [b]
    assert pool.cached_tokens(b.slot) == 3 * pool.block_size
