"""Compile-only checks of the main path's Pallas kernels for a TPU v5e.

The TPU compiler (Mosaic) refuses layouts that interpret mode accepts:
a block whose last two dims are not (8, 128)-divisible and not the
array's own, VMEM overruns, a kernel XLA would have to partition. These
tests compile, for a described ``v5e:2x2`` topology with no chip
attached, the paged decode kernel at Yi-9B serving shapes and the flash
kernel's forward and gradient at Yi-9B training shapes, on one chip and
under a 2x2 mesh, and the serving chunk program, which must update its
donated KV pool in place. Nothing runs; they guard the layouts at no
chip time.

The topology is described inside a module fixture (only one process at a
time may load the TPU library, so never at import), which skips where it
cannot be described. The persistent compile cache is off around these
compiles: an entry written for a described chip cannot be read back.
"""
import dataclasses
import math
import os
import re

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


@pytest.mark.parametrize("B,C,max_pages,pool", [
    pytest.param(8, C, MAX_PAGES, pool, id=f"{pool}-{C}")
    for pool in ("bfloat16", "int8", "int4") for C in (8, 1)] + [
    pytest.param(32, 16, 160, "bfloat16", id="chat-bfloat16-16")])
def test_paged_attention_compiles_for_v5e(one_chip, B, C, max_pages, pool):
    """Serving shapes: max_batch 8, C = 8 (chunked prefill) and C = 1
    (decode), head-major pool pages of 16 tokens; and the chat cell's
    32 rows of C = 16 over 160 pages (max_len 2560)."""
    from repro.kernels import paged_attention as pa

    n_pool = B * max_pages + 1
    hd = D // 2 if pool == "int4" else D
    dt = jnp.bfloat16 if pool == "bfloat16" else jnp.int8
    S = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                  sharding=one_chip)
    args = [S((B, C, H, D), jnp.bfloat16), S((n_pool, K, PAGE, hd), dt),
            S((n_pool, K, PAGE, hd), dt), S((B, max_pages), jnp.int32),
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


@pytest.mark.parametrize("kernel", ["paged_attention", "flash_attention_fwd"])
def test_kernels_carry_their_names(one_chip, kernel):
    """The trace names each kernel's custom call after its Pallas name,
    so the readers need no operand shape to find it."""
    from repro.kernels import flash_attention as fa
    from repro.kernels import paged_attention as pa

    S = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                  sharding=one_chip)
    if kernel == "paged_attention":
        n_pool = 8 * MAX_PAGES + 1
        args = [S((8, 8, H, D), jnp.bfloat16),
                S((n_pool, K, PAGE, D), jnp.bfloat16),
                S((n_pool, K, PAGE, D), jnp.bfloat16),
                S((8, MAX_PAGES), jnp.int32), S((8,), jnp.int32),
                S((8,), jnp.int32)]
        fn = lambda q, kp, vp, pt, pos, nv: pa.paged_attention(
            q, kp, vp, pt, pos=pos, n_valid=nv)
    else:
        args = _qkv_shapes(one_chip, one_chip)
        fn = lambda q, k, v: fa.flash_attention(q, k, v)
    text = _compile(fn, *args).as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls and all(f"%{kernel}" in line.split(" = ")[0]
                         for line in calls), calls[:1]


@pytest.mark.parametrize("k,n", [(2048, 1024), (1024, 2048)],
                         ids=["gate_up", "down"])
def test_moe_gmm_compiles_for_v5e(one_chip, k, n):
    """The dropless MoE layer's grouped matmul at Trinity-Mini's widths
    (hidden 2048, expert 1024, 16 held experts) over the long-chat cell's
    32 x 64 positions x 8 assignments; its custom call is named moe_gmm."""
    from repro.kernels.moe_gmm import moe_gmm

    S = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                  sharding=one_chip)
    args = [S((32 * 64 * 8, k), jnp.bfloat16), S((16, k, n), jnp.bfloat16),
            S((16,), jnp.int32)]
    text = _compile(moe_gmm, *args).as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls and all("%moe_gmm" in line.split(" = ")[0]
                         for line in calls), calls[:1]


# One HLO instruction: its name, result shape (arrays only) and opcode.
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(")


def _chunk_program(device, cfg, B, C, page, n_pages, max_pages):
    """The serving chunk program compiled for ``device``, donated its
    cache as the engine donates it: (HLO text, the cache's leaves, every
    instruction as (name, result dims, opcode), the opcode ``scatter``
    for a fusion whose root is a scatter). The insert is the program's
    only scatter into the pools."""
    from repro.dist import split_tree
    from repro.train import steps as T

    api = T.ModelAPI(cfg)
    S = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=device)
    params = jax.tree.map(S, jax.eval_shape(
        lambda: split_tree(api.init(cfg, jax.random.PRNGKey(0)))[0]))
    cache = jax.tree.map(S, jax.eval_shape(
        lambda: api.init_paged_cache(B, n_pages, page)))
    i32 = lambda *s: S(jax.ShapeDtypeStruct(s, jnp.int32))
    step = jax.jit(T.make_serve_chunk_step(cfg), donate_argnums=(2,))
    text = step.lower(params, i32(B, C), cache, i32(B, max_pages), i32(B),
                      i32(B)).compile().as_text()
    roots = dict(re.findall(
        r"^%?(\S+) .*\{\n(?:.*\n)*?\s*ROOT %\S+ = \S+ (\w+)\(", text, re.M))
    instrs = []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m or not m.group(2):
            continue
        name, op = m.group(1), m.group(3)
        if op == "fusion":
            called = re.search(r"calls=%([\w.-]+)", line).group(1)
            op = "scatter" if roots.get(called) == "scatter" else op
        dims = tuple(int(d) for d in m.group(2).split(","))
        instrs.append((name, dims, op))
    return text, jax.tree.leaves(cache), instrs


def _aliased(text):
    module = text.splitlines()[0]  # HloModule ..., input_output_alias=...
    return len(re.findall(r"\{\d+\}: \(\d+, \{\}, \w+-alias\)", module))


def test_chunk_program_updates_donated_pool_in_place(one_chip, monkeypatch):
    """The serving chunk program at Yi-9B widths (two layers, 32 rows of
    C = 16, a pool of 5120 pages and the trash page), donated its cache
    as the engine donates it: the pools are aliased from input to
    output, and no instruction but the insert's scatters and bitcasts
    has a result the size of a layer's pool or a multiple of it: no
    copy, transpose, dynamic slice or dynamic update of the pool."""
    from repro.configs import get_config

    monkeypatch.setenv("REPRO_USE_PALLAS", "tpu")  # CPU backend here
    cfg = dataclasses.replace(get_config("yi-9b"), n_layers=2)
    n_pages = 5120
    text, leaves, instrs = _chunk_program(one_chip, cfg, 32, 16, 16,
                                          n_pages, 160)
    assert [a.shape for a in leaves] == [(2, n_pages + 1, K, PAGE, D)] * 2
    assert _aliased(text) == len(leaves), text.splitlines()[0][:300]
    pool = math.prod(leaves[0].shape[1:])
    sized = [(name, op) for name, dims, op in instrs
             if math.prod(dims) % pool == 0]
    assert any(op == "scatter" for _, op in sized), (
        "no scatter of the insert in the program")
    pooled = [f"{name} {op}" for name, op in sized if op not in (
        "scatter", "parameter", "get-tuple-element", "bitcast")]
    assert not pooled, pooled


@pytest.mark.parametrize("kv", ["bfloat16", "int4"],
                         ids=["head_dim64", "int4"])
def test_narrow_pool_chunk_program_copies_one_layer_at_most(
        one_chip, monkeypatch, kv):
    """Pools narrower than 128 lanes (head_dim 64, and Yi-9B's 128 dims
    packed to 64 int4 bytes) hold two tokens to a 128-lane row, so the
    stack keeps the standard layout. Eight layers at the chat cell's
    shapes, donated: the pools are aliased, and no instruction but the
    insert's scatters has a result larger than one layer's pool padded
    to the lanes; the kernel takes each layer's pool, sliced out and
    padded, one layer at a time."""
    from repro.configs import get_config

    monkeypatch.setenv("REPRO_USE_PALLAS", "tpu")  # CPU backend here
    cfg = dataclasses.replace(get_config("yi-9b"), n_layers=8,
                              kv_cache_dtype=kv)
    if kv == "bfloat16":
        cfg = dataclasses.replace(cfg, head_dim=64)
    n_pages = 5120
    text, leaves, instrs = _chunk_program(one_chip, cfg, 32, 16, 16,
                                          n_pages, 160)
    pools = [a for a in leaves if a.ndim == 5]
    assert [a.shape for a in pools] == [(8, n_pages + 1, K, PAGE // 2,
                                         128)] * 2
    assert _aliased(text) == len(leaves), text.splitlines()[0][:300]
    layer = math.prod(pools[0].shape[1:])
    sized = [(name, op, dims) for name, dims, op in instrs
             if math.prod(dims) > 2 * layer and (
                 n_pages + 1 in dims or math.prod(dims) % layer == 0)]
    assert any(op == "scatter" for _, op, _ in sized), (
        "no scatter of the insert in the program")
    big = [f"{name} {op} {dims}" for name, op, dims in sized if op not in (
        "scatter", "parameter", "get-tuple-element", "bitcast")]
    assert not big, big
