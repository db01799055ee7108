"""Multi-device equivalence checks, run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (so the main pytest
process keeps its single device; see tests/test_core_distributed.py).

Each check prints 'OK <name>' or raises."""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import warnings

warnings.filterwarnings("ignore")

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import distributed_norm as DN
from repro.core import gradient_summation as GS
from repro.core import spatial_partitioning as SP
from repro.core import weight_update_sharding as WUS
from repro.kernels import ref as kref
from repro.optim import adam, constant, lars, sgd_momentum

from jax import make_mesh
from jax.sharding import AxisType

MESH = make_mesh((4, 2), ("data", "model"),
                 axis_types=(AxisType.Auto,) * 2)
KEY = jax.random.PRNGKey(0)
PARAMS = {"w1": jax.random.normal(KEY, (64, 32)),
          "b": jnp.full((32,), 0.3),
          "w2": jax.random.normal(jax.random.PRNGKey(2), (32, 16))}
LOCAL_G = jax.tree_util.tree_map(
    lambda w: jax.random.normal(jax.random.PRNGKey(1), w.shape), PARAMS)
SUMMED_G = jax.tree_util.tree_map(lambda g: 4.0 * g, LOCAL_G)


def _maxerr(a, b):
    return max(float(jnp.abs(x - y).max())
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def check_gradsum_2d_equals_sum():
    out = GS.gradient_allreduce_2d(LOCAL_G, MESH, scatter_axis="data")
    assert _maxerr(out, SUMMED_G) < 1e-5
    out1 = GS.gradient_allreduce_1d(LOCAL_G, MESH, axes=("data",))
    assert _maxerr(out1, SUMMED_G) < 1e-5
    print("OK gradsum_2d")


def check_flatten_roundtrip():
    flat, meta = GS.flatten_tree(PARAMS, pad_multiple=7)
    back = GS.unflatten_tree(flat, meta)
    assert _maxerr(back, PARAMS) == 0
    print("OK flatten_roundtrip")


def check_wus_adam():
    opt = adam(constant(0.1))
    st = opt.init(PARAMS)
    ref_p, _ = opt.update(SUMMED_G, st, PARAMS, st["step"])
    init, upd = WUS.sharded_update(adam(constant(0.1)), constant(0.1), MESH)
    st2 = init(PARAMS)
    new_p, st3 = jax.jit(upd)(LOCAL_G, st2, PARAMS)
    assert _maxerr(ref_p, new_p) < 1e-5
    # second step exercises the scattered moments
    ref_p2, _ = opt.update(SUMMED_G, opt.update(SUMMED_G, st, PARAMS)[1],
                           ref_p)
    new_p2, _ = jax.jit(upd)(LOCAL_G, st3, new_p)
    assert _maxerr(ref_p2, new_p2) < 1e-5
    print("OK wus_adam")


def check_wus_sgdm():
    opt = sgd_momentum(constant(0.05), weight_decay=1e-4)
    st = opt.init(PARAMS)
    ref_p, _ = opt.update(SUMMED_G, st, PARAMS, st["step"])
    init, upd = WUS.sharded_update(opt, constant(0.05), MESH)
    new_p, _ = jax.jit(upd)(LOCAL_G, init(PARAMS), PARAMS)
    assert _maxerr(ref_p, new_p) < 1e-5
    print("OK wus_sgdm")


def check_wus_lars_both_variants():
    for sm in (True, False):
        opt = lars(constant(0.1), scaled_momentum=sm)
        st = opt.init(PARAMS)
        ref_p, _ = opt.update(SUMMED_G, st, PARAMS, st["step"])
        init, upd = WUS.lars_sharded_update(constant(0.1), MESH,
                                            scaled_momentum=sm)
        new_p, _ = jax.jit(upd)(LOCAL_G, init(PARAMS), PARAMS)
        assert _maxerr(ref_p, new_p) < 1e-5
    print("OK wus_lars")


def check_spatial_conv():
    x = jax.random.normal(KEY, (2, 16, 16, 8))
    for (kh, stride) in [(3, 1), (3, 2), (1, 2), (7, 2), (5, 1)]:
        w = jax.random.normal(KEY, (kh, kh, 8, 4)) * 0.1
        ref = jax.lax.conv_general_dilated(
            x, w, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        got = SP.spatial_conv2d(x, w, stride=stride, mesh=MESH,
                                axis_name="data")
        assert float(jnp.abs(ref - got).max()) < 1e-4, (kh, stride)
    print("OK spatial_conv")


def check_seq_parallel_swa():
    B, S, H, D, W = 2, 32, 4, 16, 8
    q = jax.random.normal(KEY, (B, S, H, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, D))
    want = kref.attention(q, k, v, causal=True, window=W)
    got = SP.seq_parallel_swa(q, k, v, window=W, mesh=MESH,
                              axis_name="data")
    assert float(jnp.abs(want - got).max()) < 1e-4
    print("OK seq_parallel_swa")


def check_distributed_bn():
    x = jax.random.normal(KEY, (8, 4, 4, 8))
    sc, bi = jnp.ones(8), jnp.zeros(8)
    want, _, _ = DN.batch_norm(x, sc, bi)
    got = DN.distributed_batch_norm(x, sc, bi, mesh=MESH, group_size=4)
    assert float(jnp.abs(want - got).max()) < 1e-4
    # group_size=1 == local BN per shard
    got1 = DN.distributed_batch_norm(x, sc, bi, mesh=MESH, group_size=1)
    want1 = jnp.concatenate(
        [DN.batch_norm(x[i * 2:(i + 1) * 2], sc, bi)[0] for i in range(4)])
    assert float(jnp.abs(want1 - got1).max()) < 1e-4
    print("OK distributed_bn")


def check_sharded_trainer_matches_single_device():
    """Same seed/data: 2x2-mesh pjit training == single-device (bf16 tol)."""
    from repro.configs import get_config
    from repro.launch.mesh import make_test_mesh, single_device_mesh
    from repro.train import Trainer, TrainerConfig
    from repro.data.pipeline import synthetic_lm_batches

    cfg = get_config("yi-9b").reduced()
    tcfg = TrainerConfig(total_steps=3, log_every=0)
    losses = []
    for mesh in (single_device_mesh(), make_test_mesh(2, 2)):
        tr = Trainer(cfg, mesh, tcfg)
        batches = list(synthetic_lm_batches(cfg, batch=4, seq=32, steps=3))
        with mesh:
            for b in batches:
                if tr._train_step is None:
                    tr._compile_train(b)
                tr.state, m = tr._train_step(tr.state, b)
        losses.append(float(m["loss"]))
    assert abs(losses[0] - losses[1]) < 0.05, losses
    print("OK sharded_trainer")


def check_graph_partitioning_equivalence():
    """C10: partitioned independent branches == sequential execution."""
    from repro.core.graph_partitioning import run_partitioned

    x = jax.random.normal(KEY, (4, 8))
    w1 = jax.random.normal(jax.random.PRNGKey(5), (8, 6))
    w2 = jax.random.normal(jax.random.PRNGKey(6), (8, 3))
    branches = [lambda: x @ w1, lambda: x @ w2, lambda: jnp.tanh(x),
                lambda: x.sum(axis=1)]
    seq = [b() for b in branches]
    par = run_partitioned(branches, mesh=MESH)
    for a, b in zip(seq, par):
        assert float(jnp.abs(a - b).max()) < 1e-5
    print("OK graph_partitioning")


def check_kernels_per_shard():
    """Pallas kernels (interpret mode) under the 4x2 mesh run per shard
    (``kernels.ops._per_shard``) and equal the unsharded oracles: query
    and KV heads split alike over ``model`` (4 and 2), or stay whole
    when the KV heads cannot split (6 and 3)."""
    from repro.dist import Rules, use_rules
    from repro.kernels import ops

    os.environ["REPRO_USE_PALLAS"] = "interpret"
    try:
        rules = Rules(MESH, "fsdp")
        ks = jax.random.split(KEY, 3)
        B, D, page, npg = 4, 32, 4, 4
        for H, K in ((4, 2), (6, 3)):
            q = jax.random.normal(ks[0], (B, 32, H, D))
            k = jax.random.normal(ks[1], (B, 32, K, D))
            v = jax.random.normal(ks[2], (B, 32, K, D))
            with MESH, use_rules(rules):
                got = jax.jit(ops.attention)(q, k, v)
            want = kref.attention(q, k, v, causal=True)
            assert float(jnp.abs(want - got).max()) < 1e-4, (H, K)

            C = 3
            kp = jax.random.normal(ks[1], (B * npg, K, page, D))
            vp = jax.random.normal(ks[2], (B * npg, K, page, D))
            pt = jnp.arange(B * npg, dtype=jnp.int32).reshape(B, npg)[::-1]
            nv = jnp.asarray([3, 1, 2, 3], jnp.int32)
            pos = jnp.asarray([0, 9, 13, 5], jnp.int32)
            qc = q[:, :C]
            with MESH, use_rules(rules):
                got = jax.jit(lambda q, kp, vp, pt: ops.paged_attention(
                    q, {"kp": kp[None], "vp": vp[None]}, pt, pos=pos,
                    n_valid=nv, layer=0))(qc, kp, vp, pt)
            want = kref.paged_attention(qc, kp, vp, pt, pos=pos, n_valid=nv)
            for b in range(B):
                n = int(nv[b])
                assert float(jnp.abs(want[b, :n] - got[b, :n]).max()) < 1e-4
    finally:
        del os.environ["REPRO_USE_PALLAS"]
    print("OK kernels_per_shard")


def check_serving_copy_keeps_sharding():
    """The engine's compute-dtype copy of its weights keeps each
    master's sharding under ``wus`` and ``tp2d`` on a 2x2 mesh, and
    serves the tokens the same programs give fed the masters."""
    from jax.sharding import NamedSharding
    from repro.configs import get_config
    from repro.dist import Rules, param_specs, split_tree, use_rules
    from repro.launch.mesh import make_test_mesh
    from repro.serve import Engine, ServeConfig, run_offline
    from repro.serve.engine import synthetic_requests
    from repro.train.steps import ModelAPI

    cfg = get_config("yi-9b").reduced()
    mesh = make_test_mesh(2, 2)
    scfg = ServeConfig(max_batch=4, max_len=24, prefill_len=12,
                       page_size=4, prefill_chunk=4)
    for mode in ("wus", "tp2d"):
        rules = Rules(mesh, mode)
        with mesh, use_rules(rules):
            master, axes = split_tree(ModelAPI(cfg).init(cfg, KEY))
            master = jax.tree_util.tree_map(
                lambda w, s: jax.device_put(w, NamedSharding(mesh, s)),
                master, param_specs(axes, master, rules))
            eng = Engine(cfg, master, rules, scfg)
            ref = Engine(cfg, master, rules, scfg)
            ref.params = master
            got = [run_offline(e, synthetic_requests(
                cfg, n=3, tokens=4, prompt_len=10, seed=1)).requests
                for e in (eng, ref)]
        pairs = list(zip(jax.tree_util.tree_leaves(master),
                         jax.tree_util.tree_leaves(eng.params)))
        assert all(c.sharding == m.sharding for m, c in pairs), mode
        assert any(c.dtype == jnp.bfloat16 for _, c in pairs), mode
        assert [r.tokens for r in got[0]] == [r.tokens for r in got[1]]
    print("OK serving_copy_sharding")


if __name__ == "__main__":
    check_gradsum_2d_equals_sum()
    check_flatten_roundtrip()
    check_wus_adam()
    check_wus_sgdm()
    check_wus_lars_both_variants()
    check_spatial_conv()
    check_seq_parallel_swa()
    check_distributed_bn()
    check_sharded_trainer_matches_single_device()
    check_graph_partitioning_equivalence()
    check_kernels_per_shard()
    check_serving_copy_keeps_sharding()
    print("ALL_DIST_CHECKS_PASSED")
