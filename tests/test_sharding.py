"""Unit tests for the repro.dist sharding subsystem.

Run on the forced multi-device host platform (conftest.py sets
XLA_FLAGS=--xla_force_host_platform_device_count=8 before jax init), so
every constraint is exercised against a real (4, 2) ("data", "model") mesh.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist import sharding as shd
from repro.launch.mesh import make_host_mesh, make_mesh

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs --xla_force_host_platform_device_count=8")


def host_mesh():
    return make_host_mesh(model_axis=2)


# ---------------------------------------------------------------------------
# off-mesh no-op contract
# ---------------------------------------------------------------------------
def test_off_mesh_everything_is_noop():
    assert shd.current_rules() is None
    x = jnp.ones((4, 8, 16))
    assert shd.shard(x, "batch", None, "ffn") is x
    assert shd.shard_spec(x, P("data", None, "model")) is x
    assert shd.attention_scheme(4, 64, 8, 64) is None


def test_rules_pop_on_exit_and_nest():
    mesh = make_mesh((jax.device_count(),), ("data",))
    with shd.axis_rules(mesh, {"batch": "data"}) as outer:
        assert shd.current_rules() is outer
        with shd.axis_rules(mesh, {"batch": None}) as inner:
            assert shd.current_rules() is inner
        assert shd.current_rules() is outer
    assert shd.current_rules() is None


# ---------------------------------------------------------------------------
# rule-table lookup
# ---------------------------------------------------------------------------
@needs_mesh
def test_rule_table_lookup_and_axis_sizes():
    mesh = host_mesh()
    table = shd.production_rules_table(False)
    with shd.axis_rules(mesh, table) as rules:
        assert rules.mesh_axes("batch") == "data"
        assert rules.mesh_axes("ffn") == "model"
        assert rules.mesh_axes("nonexistent") is None
        assert rules.mesh_axes(None) is None
        assert rules.axis_size("data") == 4
        assert rules.axis_size("model") == 2
        assert rules.axis_size(("data", "model")) == 8
        assert rules.axis_size(None) == 1
    # the table is copied at install time
    with shd.axis_rules(mesh, table) as rules:
        table["ffn"] = None
        assert rules.mesh_axes("ffn") == "model"


def test_production_table_variants():
    t = shd.production_rules_table(True)
    assert t["batch"] == ("pod", "data")
    assert t["kv_seq"] is None
    t = shd.production_rules_table(False, seq_shard=True)
    assert t["batch"] == "data"
    assert t["kv_seq"] == "data"
    assert t["vocab"] == t["experts"] == t["heads"] == "model"


# ---------------------------------------------------------------------------
# constraint helpers
# ---------------------------------------------------------------------------
@needs_mesh
def test_shard_applies_named_constraint():
    mesh = host_mesh()
    with shd.axis_rules(mesh, shd.production_rules_table(False)):
        out = jax.jit(lambda x: shd.shard(x, "batch", None, "ffn"))(
            jnp.ones((8, 4, 16)))
        assert out.sharding.is_equivalent_to(
            NamedSharding(mesh, P("data", None, "model")), 3)


@needs_mesh
def test_shard_drops_non_divisible_and_unknown_axes():
    mesh = host_mesh()
    with shd.axis_rules(mesh, shd.production_rules_table(False)):
        # batch 6 % 4 != 0 -> batch axis dropped, ffn kept
        out = jax.jit(lambda x: shd.shard(x, "batch", None, "ffn"))(
            jnp.ones((6, 4, 16)))
        assert out.sharding.is_equivalent_to(
            NamedSharding(mesh, P(None, None, "model")), 3)
    # a multi-pod table on a pod-less mesh: "pod" silently dropped
    with shd.axis_rules(mesh, shd.production_rules_table(True)):
        out = jax.jit(lambda x: shd.shard(x, "batch", None, None))(
            jnp.ones((8, 4, 16)))
        assert out.sharding.is_equivalent_to(
            NamedSharding(mesh, P(None, None, None)), 3)


@needs_mesh
def test_shard_spec_dedups_mesh_axes():
    mesh = host_mesh()
    with shd.axis_rules(mesh, shd.production_rules_table(False)):
        # "model" requested twice: first dim wins, second replicates
        out = jax.jit(lambda x: shd.shard_spec(x, P("model", "model")))(
            jnp.ones((4, 8)))
        assert out.sharding.is_equivalent_to(
            NamedSharding(mesh, P("model", None)), 2)


# ---------------------------------------------------------------------------
# attention scheme selection
# ---------------------------------------------------------------------------
@needs_mesh
def test_attention_scheme_head_sharded():
    with shd.axis_rules(host_mesh(), shd.production_rules_table(False)):
        s = shd.attention_scheme(8, 64, 8, 64)      # heads divide 'model'(2)
        assert s["q"] == P("data", None, "model", None)
        assert s["kv"] == P("data", None, "model", None)
        assert s["logits"] == P("data", "model", None, None)


@needs_mesh
def test_attention_scheme_q_seq_sharded():
    with shd.axis_rules(host_mesh(), shd.production_rules_table(False)):
        s = shd.attention_scheme(8, 64, 3, 64)      # 3 heads don't divide
        assert s["q"] == P("data", "model", None, None)
        assert s["kv"] == P("data", None, None, None)
        assert s["logits"] == P("data", None, "model", None)


@needs_mesh
def test_attention_scheme_decode_kv_seq_sharded():
    with shd.axis_rules(host_mesh(), shd.production_rules_table(False)):
        s = shd.attention_scheme(8, 1, 3, 64)       # decode, awkward heads
        assert s["q"] == P("data", None, None, None)
        assert s["kv"] == P("data", "model", None, None)
        assert s["logits"] == P("data", None, None, "model")


@needs_mesh
def test_attention_scheme_batch_fallbacks():
    with shd.axis_rules(host_mesh(), shd.production_rules_table(False)):
        s = shd.attention_scheme(3, 1, 3, 63)       # nothing fits but...
        assert s["q"] == P(None, None, None, None)  # ...batch-only scheme
        sh = shd.attention_scheme(4, 1, 3, 63)
        assert sh["q"] == P("data", None, None, None)
    with shd.axis_rules(host_mesh(), {"batch": None}):
        assert shd.attention_scheme(8, 64, 8, 64) is None   # empty table


# ---------------------------------------------------------------------------
# param pspecs
# ---------------------------------------------------------------------------
@needs_mesh
def test_param_pspecs_nested_pytree():
    mesh = host_mesh()
    S = jax.ShapeDtypeStruct
    pshape = {
        "emb": {"tok_emb": S((512, 256), jnp.float32)},
        "layers": {
            "attn": {"wq": S((2, 256, 512), jnp.float32),
                     "wo": S((2, 512, 256), jnp.float32)},
            "mlp": {"w_gate": S((2, 256, 512), jnp.float32),
                    "w_down": S((2, 512, 256), jnp.float32)},
            "we_gate_up": S((2, 4, 256, 512), jnp.float32),
            "norm1": S((2, 256), jnp.float32),
        },
        "final_norm": S((256,), jnp.float32),
    }
    with shd.axis_rules(mesh, shd.production_rules_table(False)) as rules:
        spec = shd.param_pspecs(pshape, rules)
    assert spec["emb"]["tok_emb"] == P("model", None)
    assert spec["layers"]["attn"]["wq"] == P(None, None, "model")
    assert spec["layers"]["attn"]["wo"] == P(None, "model", None)
    assert spec["layers"]["mlp"]["w_gate"] == P(None, None, "model")
    assert spec["layers"]["mlp"]["w_down"] == P(None, "model", None)
    # experts and ffn both map to 'model': expert parallelism wins
    assert spec["layers"]["we_gate_up"] == P(None, "model", None, None)
    assert spec["layers"]["norm1"] == P(None, None)
    assert spec["final_norm"] == P(None)
    # structure preserved leaf-for-leaf
    assert (jax.tree_util.tree_structure(spec,
                is_leaf=lambda x: isinstance(x, P)).num_leaves
            == jax.tree_util.tree_structure(pshape).num_leaves)


@needs_mesh
def test_param_pspecs_real_model_and_named():
    from repro.configs import get_config
    from repro.models.api import params_specs
    mesh = host_mesh()
    cfg = get_config("llama3.2-1b", smoke=True)
    pshape = params_specs(cfg)
    with shd.axis_rules(mesh, shd.production_rules_table(False)) as rules:
        pspec = shd.param_pspecs(pshape, rules)
        psharding = shd.named(pspec, mesh)
    leaves = jax.tree_util.tree_leaves(
        psharding, is_leaf=lambda x: isinstance(x, NamedSharding))
    assert leaves and all(isinstance(l, NamedSharding) for l in leaves)
    # every spec is full-rank and valid for its leaf
    for (path, leaf) in jax.tree_util.tree_flatten_with_path(pshape)[0]:
        spec = psharding
        for k in path:
            spec = spec[k.key]
        assert len(spec.spec) == len(leaf.shape), path


# ---------------------------------------------------------------------------
# semantics: sharding must not change results
# ---------------------------------------------------------------------------
@needs_mesh
def test_on_mesh_forward_matches_off_mesh():
    from repro.configs import get_config
    from repro.models.api import build_model, make_batch
    cfg = get_config("llama3.2-1b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = make_batch(cfg, 4, 64, jax.random.key(1))
    ref = jax.jit(model.forward)(params, batch)
    with shd.axis_rules(host_mesh(), shd.production_rules_table(False)):
        out = jax.jit(model.forward)(params, batch)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# dry-run flow (the acceptance smoke): named shardings on the host mesh
# ---------------------------------------------------------------------------
@needs_mesh
def test_dryrun_host_mesh_smoke():
    from repro.launch.dryrun import lower_combo
    rec, compiled = lower_combo("qwen2-0.5b", "decode_32k", False,
                                probe=False, extra_cfg={"smoke": True},
                                mesh_kind="host")
    assert rec["mesh"] == "host"
    assert rec["n_chips"] == jax.device_count()
    assert rec["bottleneck"] in ("compute", "memory", "collective")
    assert compiled is not None
