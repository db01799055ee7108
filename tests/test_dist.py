"""repro.dist subsystem tests beyond test_sharding.py: scan-stacked
tagging, mesh-context constrain scoping, 3-axis wus Rules, shard_map."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.dist import (
    Axes,
    Rules,
    constrain,
    current_rules,
    opt_state_specs,
    p,
    param_specs,
    retag_tree,
    split_tree,
    stack_axes,
    use_rules,
)
from repro.launch.mesh import single_device_mesh


class FakeMesh:
    def __init__(self, shape_map):
        self.shape = shape_map
        self.axis_names = tuple(shape_map)


POD_MESH = {"pod": 2, "data": 16, "model": 16}


# --------------------------------------------------------------------------- #
# stack_axes on a scan-stacked layer tree (the models' init idiom).
# --------------------------------------------------------------------------- #
def test_stack_axes_scan_stacked_layer_tree():
    def init_layer(key):
        k1, k2 = jax.random.split(key)
        return {
            "wu": p(jax.random.normal(k1, (8, 32)), "fsdp", "mlp"),
            "wd": p(jax.random.normal(k2, (32, 8)), "mlp", "fsdp"),
            "norm": {"scale": p(jnp.ones((8,)), None)},
        }

    proto_vals, proto_axes = split_tree(init_layer(jax.random.PRNGKey(0)))

    def one(k):
        return split_tree(init_layer(k))[0]

    n_layers = 3
    stacked = jax.vmap(one)(jax.random.split(jax.random.PRNGKey(0), n_layers))
    tagged = retag_tree(stacked, stack_axes(proto_axes))

    vals, axes = split_tree(tagged)
    assert vals["wu"].shape == (n_layers, 8, 32)
    assert axes["wu"].names == ("layer", "fsdp", "mlp")
    assert axes["norm"]["scale"].names == ("layer", None)

    # 'layer' is structural: never mapped to a mesh axis, so the leading
    # dim is replicated regardless of divisibility.
    r = Rules(FakeMesh({"data": 16, "model": 16}), "fsdp")
    spec = r.spec_for(axes["wu"].names, vals["wu"].shape)
    assert spec[0] is None

    # round-trip preserves values exactly
    v2, a2 = split_tree(retag_tree(vals, axes))
    np.testing.assert_array_equal(np.asarray(v2["wd"]),
                                  np.asarray(vals["wd"]))
    assert a2["wd"].names == ("layer", "mlp", "fsdp")


# --------------------------------------------------------------------------- #
# constrain: no-op outside use_rules, active (and exception-safe) inside.
# --------------------------------------------------------------------------- #
def test_constrain_noop_outside_use_rules():
    x = jnp.ones((4, 8))
    assert current_rules() is None
    assert constrain(x, "batch", None) is x  # identity, not a copy


def test_constrain_noop_under_none_rules():
    x = jnp.ones((4, 8))
    with use_rules(None):
        assert constrain(x, "batch", None) is x


def test_constrain_applies_inside_use_rules():
    mesh = single_device_mesh()
    rules = Rules(mesh, "fsdp")
    x = jnp.ones((4, 8))
    with mesh, use_rules(rules):
        assert current_rules() is rules
        y = constrain(x, "batch", "seq_res")
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    # scope restored on exit, including after nesting
    assert current_rules() is None
    with use_rules(rules):
        with use_rules(None):
            assert current_rules() is None
        assert current_rules() is rules


def test_constrain_skips_shape_only_mesh():
    # FakeMesh has no devices: constrain must degrade to identity instead
    # of building a NamedSharding over a non-mesh.
    x = jnp.ones((32, 16))
    with use_rules(Rules(FakeMesh(POD_MESH), "fsdp")):
        assert constrain(x, "batch", None) is x


# --------------------------------------------------------------------------- #
# Rules on the 3-axis multipod mesh in wus mode (C1 + C2 together).
# --------------------------------------------------------------------------- #
def test_wus_rules_on_3axis_pod_mesh():
    r = Rules(FakeMesh(POD_MESH), "wus")

    # C2: batch spans both data-parallel axes.
    assert r.spec_for(("batch", None), (256, 4096)) == P(("pod", "data"), None)

    # C1: master weights replicated across data, moments sharded.
    axes = Axes(("fsdp", "mlp"))
    shp = jax.ShapeDtypeStruct((4096, 24576), jnp.float32)
    assert param_specs(axes, shp, r) == P(None, "model")
    assert opt_state_specs(axes, shp, r) == P("data", "model")

    # C1 upgrade on unannotated weights, pod mesh included.
    assert opt_state_specs(
        Axes((None, None)), jax.ShapeDtypeStruct((512, 48), jnp.float32), r
    ) == P("data", None)

    # non-divisible fallback still replicates (48 % 16 == 0 but 40 isn't)
    assert opt_state_specs(
        Axes((None,)), jax.ShapeDtypeStruct((40,), jnp.float32), r
    ) == P(None)

    # the structural layer dim is never eligible for the C1 upgrade, even
    # when it is the only divisible dim
    assert opt_state_specs(
        Axes(("layer", None)), jax.ShapeDtypeStruct((32, 40), jnp.float32), r
    ) == P(None, None)
    assert opt_state_specs(
        Axes(("layer", None)), jax.ShapeDtypeStruct((32, 48), jnp.float32), r
    ) == P(None, "data")

    # axis table exposes the mesh-axis sizes for cache-layout decisions
    assert r.axis_size(r.table["kv_heads"]) == 16
    assert r.axis_size(r.table["batch"]) == 32


def test_wus_axes_derived_from_rules():
    from repro.core.weight_update_sharding import wus_axes_from_rules

    assert wus_axes_from_rules(
        Rules(FakeMesh(POD_MESH), "wus")) == ("data", "pod")
    assert wus_axes_from_rules(
        Rules(FakeMesh({"data": 16, "model": 16}), "wus")) == ("data", None)


def test_tp2d_keeps_batch_off_data():
    r = Rules(FakeMesh({"data": 16, "model": 16}), "tp2d")
    assert r.spec_for(("batch", None), (256, 4096)) == P(None, None)
    assert r.param_spec(("fsdp", "mlp"), (4096, 24576)) == P("data", "model")


# --------------------------------------------------------------------------- #
# jax.shard_map with check_vma, called directly and as a partial decorator.
# --------------------------------------------------------------------------- #
def test_compat_shard_map_runs():
    import functools

    from jax import shard_map

    mesh = single_device_mesh()

    out = shard_map(
        lambda a: a * 2, mesh=mesh, in_specs=P(), out_specs=P(),
        check_vma=False,
    )(jnp.arange(4.0))
    np.testing.assert_allclose(np.asarray(out), 2 * np.arange(4.0))

    @functools.partial(shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
                       check_vma=False)
    def f(a):
        return a + 1

    np.testing.assert_allclose(np.asarray(f(jnp.zeros(3))), np.ones(3))
