"""MeshRules / logical-axis sharding unit tests (single device: specs only)."""
import jax.numpy as jnp
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.sharding import MeshRules, logical
from repro.train.steps import INNER_RULES, outer_rules, serving_rules


def _mesh(shape=(1, 1), names=("data", "model")):
    # AbstractMesh: spec construction without real devices
    return AbstractMesh(tuple(shape), tuple(names))


def test_spec_basic_mapping():
    rules = MeshRules(_mesh(), {"batch": "data", "mlp": "model"})
    assert rules.spec(("batch", None, "mlp"), (8, 4, 16)) == \
        P("data", None, "model")


def test_spec_divisibility_fallback():
    rules = MeshRules(_mesh((2, 4)), {"heads": "model"})
    # 6 heads % 4 != 0 -> replicated
    assert rules.spec(("heads",), (6,)) == P()
    assert rules.spec(("heads",), (8,)) == P("model")


def test_spec_each_mesh_axis_used_once():
    rules = MeshRules(_mesh((2, 4)), {"a": "model", "b": "model"})
    # second use of 'model' in one spec must fall back to None
    assert rules.spec(("a", "b"), (8, 8)) == P("model")


def test_spec_tuple_axes():
    rules = MeshRules(_mesh((2, 2, 2), ("pod", "data", "model")),
                      {"batch": ("pod", "data")})
    assert rules.spec(("batch",), (8,)) == P(("pod", "data"))
    # non-divisible by 4 -> replicate
    assert rules.spec(("batch",), (6,)) == P()


def test_missing_mesh_axis_is_ignored():
    rules = MeshRules(_mesh((2,), ("data",)), {"mlp": "model"})
    assert rules.spec(("mlp",), (8,)) == P()


def test_logical_noop_without_rules():
    x = jnp.ones((4, 4))
    assert logical(x, "batch", "embed") is x


def test_rule_tables_cover_model_axes():
    for name in ("heads_flat", "kv_flat", "mlp", "vocab", "experts"):
        assert INNER_RULES[name] == "model"
    r = outer_rules(("pod", "data"))
    assert r["batch"] == ("pod", "data")
    r1 = serving_rules(("data",), shard_cache_seq=False, decode=True)
    assert r1["cache_seq"] == "model"
    r2 = serving_rules(("data",), shard_cache_seq=True, decode=True)
    assert r2["cache_seq"] == ("data", "model") and r2["batch"] is None
