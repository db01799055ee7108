"""KV-cache unit tests: ring-buffer semantics, int8 quantization accuracy,
prefill->cache construction."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops, paged_pool, ref
from repro.models import layers as L

KEY = jax.random.PRNGKey(0)


def _cfg(kv_dtype="bfloat16"):
    return dataclasses.replace(
        get_config("yi-9b").reduced(), kv_cache_dtype=kv_dtype)


def test_ring_buffer_overwrites_oldest():
    cfg = _cfg()
    B, Lc = 2, 4
    cache = L.init_kv_cache(cfg, B, Lc)
    K, hd = cfg.n_kv_heads, cfg.head_dim
    for pos in range(6):  # wraps twice
        k = jnp.full((B, K, hd), float(pos))
        cache = L.cache_insert(cache, k, k, pos)
    # slots hold positions 4,5,2,3 (pos % 4)
    assert sorted(np.asarray(cache["slot_pos"][0]).tolist()) == [2, 3, 4, 5]
    slot = np.asarray(cache["slot_pos"][0]).tolist().index(5)
    assert float(cache["k"][0, slot, 0, 0]) == 5.0


def test_int8_cache_quantization_accuracy():
    cfg = _cfg("int8")
    B, Lc = 2, 8
    K, hd = cfg.n_kv_heads, cfg.head_dim
    cache = L.init_kv_cache(cfg, B, Lc)
    ks = jax.random.normal(KEY, (Lc, B, K, hd)) * 3.0
    for pos in range(Lc):
        cache = L.cache_insert(cache, ks[pos], ks[pos], pos)
    # dequantized values within int8 step of the original
    deq = cache["k"].astype(jnp.float32) * cache["k_scale"][..., None]
    for pos in range(Lc):
        err = jnp.abs(deq[:, pos] - ks[pos])
        step = cache["k_scale"][:, pos][..., None]
        assert float((err - step).max()) < 1e-5


def test_int8_decode_attention_close_to_fp():
    cfg = _cfg("int8")
    B, Lc = 2, 16
    K, hd, H = cfg.n_kv_heads, cfg.head_dim, cfg.n_heads
    k = jax.random.normal(KEY, (B, Lc, K, hd))
    v = jax.random.normal(jax.random.PRNGKey(1), (B, Lc, K, hd))
    q = jax.random.normal(jax.random.PRNGKey(2), (B, 1, H, hd))
    cache = L.cache_from_prefill(cfg, k, v, Lc)
    got = ops.decode_attention(
        q, cache["k"], cache["v"], cache["slot_pos"], pos=Lc - 1,
        k_scale=cache["k_scale"], v_scale=cache["v_scale"])
    want = ref.attention(q, k, v, causal=True, q_offset=Lc - 1)
    # int8 KV quantization error stays small on the attention output
    assert float(jnp.abs(got - want).max()) < 0.05


def test_windowed_decode_ignores_out_of_window():
    cfg = _cfg()
    B, Lc = 1, 8
    K, hd, H = cfg.n_kv_heads, cfg.head_dim, cfg.n_heads
    k = jax.random.normal(KEY, (B, 12, K, hd))
    v = jax.random.normal(jax.random.PRNGKey(1), (B, 12, K, hd))
    q = jax.random.normal(jax.random.PRNGKey(2), (B, 1, H, hd))
    # fill ring cache of size 8 with positions 0..11 (keeps 4..11)
    cache = L.init_kv_cache(cfg, B, Lc)
    for pos in range(12):
        cache = L.cache_insert(cache, k[:, pos], v[:, pos], pos)
    got = ops.decode_attention(q, cache["k"], cache["v"],
                               cache["slot_pos"], pos=11, window=8)
    want = ref.attention(q, k, v, causal=True, window=8, q_offset=11)
    assert float(jnp.abs(got - want).max()) < 2e-2


def test_cache_from_prefill_matches_inserts():
    cfg = _cfg()
    B, Lc = 2, 6
    K, hd = cfg.n_kv_heads, cfg.head_dim
    k = jax.random.normal(KEY, (B, Lc, K, hd))
    v = jax.random.normal(jax.random.PRNGKey(1), (B, Lc, K, hd))
    bulk = L.cache_from_prefill(cfg, k, v, Lc)
    step = L.init_kv_cache(cfg, B, Lc)
    for pos in range(Lc):
        step = L.cache_insert(step, k[:, pos], v[:, pos], pos)
    for key in bulk:
        np.testing.assert_allclose(
            np.asarray(bulk[key], np.float32),
            np.asarray(step[key], np.float32), rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------- #
# Paged pool insert, copy and permute (serving; see
# repro.serve.cache.PagePool for the host side).
# --------------------------------------------------------------------------- #
def _pool(kv_dtype, n_layers, n_pages, page):
    """A pool as ``paged_pool.init`` lays it out, every row filled with
    non-zero values so that a stray write shows."""
    cfg = _cfg(kv_dtype)
    pool = paged_pool.init(n_layers, n_pages, page, cfg.n_kv_heads,
                           cfg.head_dim, kv_dtype, cfg.dtype)
    keys = iter(jax.random.split(KEY, len(pool)))

    def fill(a):
        if jnp.issubdtype(a.dtype, jnp.integer):
            return jax.random.randint(next(keys), a.shape, 1, 100, a.dtype)
        return jax.random.uniform(next(keys), a.shape, a.dtype, 0.5, 1.5)

    return cfg, {name: fill(a) for name, a in pool.items()}


@pytest.mark.parametrize("n_blocks,layer", [(1, 0), (3, 2)],
                         ids=["one-layer", "stacked"])
@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8", "int4"])
def test_paged_cache_insert_roundtrip(kv_dtype, n_blocks, layer):
    """Two rows' tokens land in their mapped pages of the written layer
    and read back (bf16 to its rounding; int8 and int4, whose pools pack
    two head dims per byte, within one quantization step); masked
    positions go to that layer's trash page; every other row of the
    pool, and every row of the stack's other layers, stays bit-identical.
    ``one-layer`` is a stack of one layer, ``stacked`` layer 2 of a
    three-layer stack as the chunk program carries it. The pools hold
    several tokens to a row here (head_dim 16, int4-packed 8)."""
    from repro.kernels import quant

    page, n_pages = 4, 6
    cfg, cache = _pool(kv_dtype, n_blocks, n_pages, page)
    K, hd = cfg.n_kv_heads, cfg.head_dim
    store_hd = hd // 2 if kv_dtype == "int4" else hd
    t = paged_pool.tokens_per_row(page, store_hd)
    assert t > 1
    assert cache["kp"].shape == (n_blocks, n_pages + 1, K, page // t,
                                 t * store_hd)  # + trash
    assert cache["kp"].dtype == (jnp.int8 if kv_dtype != "bfloat16"
                                 else jnp.bfloat16)
    assert paged_pool.geometry(cache, hd) == (
        page, store_hd, t, "" if kv_dtype == "bfloat16" else kv_dtype)
    pt = jnp.asarray([[2, 5, -1], [4, -1, -1]], jnp.int32)
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 3, K, hd)) * 3.0
    # row 0 writes positions 3..5 (page 0 tail + page 1 head); row 1
    # writes position 1 only (n_valid 1), its masked tokens 1 and 2
    # (positions 2, 3) go to the trash page
    out = paged_pool.insert(cache, k, k, pt, jnp.asarray([3, 1], jnp.int32),
                            jnp.asarray([3, 1], jnp.int32), layer)
    writes = {(2, 3): (0, 0), (5, 0): (0, 1), (5, 1): (0, 2), (4, 1): (1, 0),
              (n_pages, 2): (1, 1), (n_pages, 3): (1, 2)}
    for name in cache:
        before, after = np.asarray(cache[name]), np.asarray(out[name])
        assert after.dtype == before.dtype and after.shape == before.shape
        diff = before != after
        diff = diff.reshape(diff.shape[:3] + (page, -1)).any(axis=(2, 4))
        changed = {(int(b), int(p), int(o)) for b, p, o in zip(*diff.nonzero())}
        assert changed <= {(layer, p, o) for p, o in writes}, name
    kf = np.asarray(k, np.float32)
    for name in ("kp", "vp"):
        vals = np.asarray(out[name])[layer]
        vals = vals.reshape(vals.shape[:2] + (page, store_hd))
        if kv_dtype == "bfloat16":
            deq, step = vals.astype(np.float32), None
        else:
            scale = np.asarray(out[name + "_scale"])[layer]
            deq = np.asarray(quant.dequantize(vals, scale, hd))
        for (phys, off), (b, i) in writes.items():
            err = np.abs(deq[phys, :, off] - kf[b, i])
            if kv_dtype == "bfloat16":
                assert float((err - 1e-2 * np.abs(kf[b, i])).max()) < 1e-2
            else:
                step = scale[phys, :, off][..., None]
                assert float((err - step).max()) < 1e-5


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8", "int4"])
def test_paged_pool_copy_and_permute_move_whole_pages(kv_dtype):
    """On a three-layer stack whose rows hold several tokens (head_dim
    16, int4-packed 8): ``copy_pages`` writes each source page over its
    destination and ``permute`` puts every page where the permutation
    says, in every layer and every leaf, scales included; every other
    page stays bit-identical."""
    n_layers, n_pages, page = 3, 6, 4
    _, pool = _pool(kv_dtype, n_layers, n_pages, page)
    assert paged_pool.geometry(pool, 16)[2] > 1
    assert set(pool) == ({"kp", "vp"} if kv_dtype == "bfloat16" else
                         {"kp", "vp", "kp_scale", "vp_scale"})
    before = {name: np.asarray(a) for name, a in pool.items()}

    copied = paged_pool.copy_pages(pool, [1, 4], [3, 0])
    moves = {3: 1, 0: 4}  # destination page <- source page
    for name, a in copied.items():
        a = np.asarray(a)
        assert a.dtype == before[name].dtype
        for p in range(n_pages + 1):
            np.testing.assert_array_equal(
                a[:, p], before[name][:, moves.get(p, p)], err_msg=name)

    perm = np.asarray([5, 0, 3, 1, 2, 4, n_pages], np.int32)
    moved = paged_pool.permute(pool, perm)
    for name, a in moved.items():
        a = np.asarray(a)
        assert a.shape == before[name].shape
        np.testing.assert_array_equal(a, before[name][:, perm],
                                      err_msg=name)


def test_int4_slab_cache_rejected():
    cfg = _cfg("int4")
    try:
        L.init_kv_cache(cfg, 1, 4)
    except ValueError as e:
        assert "paged" in str(e)
    else:
        raise AssertionError("int4 slab cache should be rejected")


def test_insert_refuses_silent_upcast_into_integer_pool():
    """The old fallback path quietly did astype(int8) on float K/V when
    a quantized pool was missing its scale entries — garbage attention
    with no error. Now it raises at trace time."""
    cfg = _cfg("int8")
    K, hd = cfg.n_kv_heads, cfg.head_dim

    # slab: strip the scale entries to simulate the broken pre-fix cache
    cache = L.init_kv_cache(cfg, 1, 4)
    bare = {k: v for k, v in cache.items()
            if k not in ("k_scale", "v_scale")}
    knew = jnp.ones((1, K, hd))
    with pytest.raises(TypeError, match="quantization scales"):
        L.cache_insert(bare, knew, knew, 0)
    with pytest.raises(TypeError, match="quantization scales"):
        L.cache_insert(bare, knew, knew, jnp.zeros((1,), jnp.int32))
    # the intact quantized cache accepts the same write
    L.cache_insert(cache, knew, knew, 0)

    # paged: same contract, on a stack of one layer
    pcache = paged_pool.init(1, 2, 4, K, hd, cfg.kv_cache_dtype, cfg.dtype)
    pbare = {k: v for k, v in pcache.items()
             if k not in ("kp_scale", "vp_scale")}
    pt = jnp.asarray([[0, 1]], jnp.int32)
    kc = jnp.ones((1, 2, K, hd))
    with pytest.raises(TypeError, match="quantization scales"):
        paged_pool.insert(pbare, kc, kc, pt, jnp.asarray([0], jnp.int32),
                          jnp.asarray([2], jnp.int32), 0)
    paged_pool.insert(pcache, kc, kc, pt, jnp.asarray([0], jnp.int32),
                      jnp.asarray([2], jnp.int32), 0)
