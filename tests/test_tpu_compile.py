"""Compile-only checks of the main path's Pallas kernels for a TPU v5e.

The TPU compiler (Mosaic) refuses layouts that interpret mode accepts:
a block whose last two dims are not (8, 128)-divisible and not the
array's own, VMEM overruns, a kernel XLA would have to partition. These
tests compile, for a described ``v5e:2x2`` topology with no chip
attached, the paged decode kernel at Yi-9B serving shapes and the flash
kernel's forward and gradient at Yi-9B training shapes, on one chip and
under a 2x2 mesh. Nothing runs; they guard the layouts at no chip time.

The topology is described inside a module fixture (only one process at a
time may load the TPU library, so never at import), which skips where it
cannot be described. The persistent compile cache is off around these
compiles: an entry written for a described chip cannot be read back.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

# Yi-9B: 32 query heads, 4 KV heads (GQA group 8), head_dim 128.
H, K, D = 32, 4, 128
PAGE, MAX_PAGES = 16, 32
SEQ = 2048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    old_log = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs go to /tmp
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()
    if old_log is None:
        os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return compiled


@pytest.mark.parametrize("C", [8, 1])
@pytest.mark.parametrize("pool", ["bfloat16", "int8", "int4"])
def test_paged_attention_compiles_for_v5e(one_chip, C, pool):
    """Serving shapes: max_batch 8, C = 8 (chunked prefill) and C = 1
    (decode), head-major pool pages of 16 tokens."""
    from repro.kernels import paged_attention as pa

    B = 8
    n_pool = B * MAX_PAGES + 1
    hd = D // 2 if pool == "int4" else D
    dt = jnp.bfloat16 if pool == "bfloat16" else jnp.int8
    S = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                  sharding=one_chip)
    args = [S((B, C, H, D), jnp.bfloat16), S((n_pool, K, PAGE, hd), dt),
            S((n_pool, K, PAGE, hd), dt), S((B, MAX_PAGES), jnp.int32),
            S((B,), jnp.int32), S((B,), jnp.int32)]
    if pool != "bfloat16":
        args += [S((n_pool, K, PAGE), jnp.float32)] * 2

    def fn(q, kp, vp, pt, pos, nv, *scales):
        ks, vs = scales or (None, None)
        return pa.paged_attention(q, kp, vp, pt, pos=pos, n_valid=nv,
                                  kp_scale=ks, vp_scale=vs)

    _compile(fn, *args)


def _qkv_shapes(sharding_q, sharding_kv, B=1):
    return (jax.ShapeDtypeStruct((B, SEQ, H, D), jnp.bfloat16,
                                 sharding=sharding_q),
            jax.ShapeDtypeStruct((B, SEQ, K, D), jnp.bfloat16,
                                 sharding=sharding_kv),
            jax.ShapeDtypeStruct((B, SEQ, K, D), jnp.bfloat16,
                                 sharding=sharding_kv))


def test_flash_attention_forward_compiles_for_v5e(one_chip):
    from repro.kernels import flash_attention as fa

    _compile(lambda q, k, v: fa.flash_attention(q, k, v),
             *_qkv_shapes(one_chip, one_chip))


def test_flash_attention_grad_compiles_for_v5e(one_chip):
    """Training shapes: S = 2048, GQA group 8; the custom VJP's backward
    compiles alongside the forward kernel."""
    from repro.kernels import flash_attention as fa

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v).astype(jnp.float32))

    _compile(jax.grad(loss, argnums=(0, 1, 2)),
             *_qkv_shapes(one_chip, one_chip))


def test_flash_attention_grad_compiles_sharded_2x2(topo, monkeypatch):
    """Under a 2x2 (data, model) mesh the kernel runs per shard
    (``ops._per_shard``): XLA cannot partition a Mosaic kernel itself."""
    from repro.dist import Rules, use_rules
    from repro.kernels import ops

    monkeypatch.setenv("REPRO_USE_PALLAS", "tpu")  # CPU backend here
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    rules = Rules(mesh, "wus")
    spec = NamedSharding(mesh, P("data", None, "model", None))

    def loss(q, k, v):
        with use_rules(rules):
            return jnp.sum(ops.attention(q, k, v).astype(jnp.float32))

    _compile(jax.grad(loss, argnums=(0, 1, 2)),
             *_qkv_shapes(spec, spec, B=4))
