"""KV-cache unit tests: ring-buffer semantics, int8 quantization accuracy,
prefill->cache construction."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.kernels import ops, ref
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
# Paged pool insert (serving; see repro.serve.cache.PagePool).
# --------------------------------------------------------------------------- #
def test_paged_cache_insert_lands_in_mapped_pages():
    cfg = _cfg()
    K, hd = cfg.n_kv_heads, cfg.head_dim
    page, n_pages = 4, 6
    cache = L.init_paged_kv_cache(cfg, n_pages, page)
    assert cache["kp"].shape == (n_pages + 1, K, page, hd)  # + trash page
    pt = jnp.asarray([[2, 5, -1], [4, -1, -1]], jnp.int32)
    B, C = 2, 3
    k = jax.random.normal(KEY, (B, C, K, hd))
    # row 0 writes positions 3..5 (page 0 tail + page 1 head); row 1
    # writes position 1 only (n_valid=1)
    out = L.paged_cache_insert(
        cache, k, k, pt, jnp.asarray([3, 1], jnp.int32),
        jnp.asarray([3, 1], jnp.int32))
    kp = np.asarray(out["kp"], np.float32)
    kf = np.asarray(k, np.float32)
    np.testing.assert_allclose(kp[2, :, 3], kf[0, 0], rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(kp[5, :, 0], kf[0, 1], rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(kp[5, :, 1], kf[0, 2], rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(kp[4, :, 1], kf[1, 0], rtol=1e-2, atol=1e-2)
    # row 1's masked tokens went to the trash page, not a real one
    assert np.abs(kp[:n_pages]).astype(bool).sum() == 4 * K * hd


def test_paged_cache_insert_int8_roundtrip():
    cfg = _cfg("int8")
    K, hd = cfg.n_kv_heads, cfg.head_dim
    page, n_pages = 4, 3
    cache = L.init_paged_kv_cache(cfg, n_pages, page)
    assert cache["kp"].dtype == jnp.int8
    pt = jnp.asarray([[1, 0]], jnp.int32)
    k = jax.random.normal(KEY, (1, 4, K, hd)) * 3.0
    out = L.paged_cache_insert(
        cache, k, k, pt, jnp.asarray([2], jnp.int32),
        jnp.asarray([4], jnp.int32))
    deq = (np.asarray(out["kp"], np.float32)
           * np.asarray(out["kp_scale"])[..., None])
    # positions 2..5 -> page1[2], page1[3], page0[0], page0[1]
    for i, (phys, off) in enumerate(((1, 2), (1, 3), (0, 0), (0, 1))):
        err = np.abs(deq[phys, :, off] - np.asarray(k)[0, i])
        step = np.asarray(out["kp_scale"])[phys, :, off][..., None]
        assert float((err - step).max()) < 1e-5


# --------------------------------------------------------------------------- #
# int4 pools + the silent-upcast bugfix.
# --------------------------------------------------------------------------- #
def test_paged_cache_insert_int4_roundtrip():
    """int4 pools pack two head dims per byte (halves layout) with the
    same per-(token, head) scales; dequantization reconstructs within
    one quantization step."""
    from repro.kernels import quant

    cfg = _cfg("int4")
    K, hd = cfg.n_kv_heads, cfg.head_dim
    page, n_pages = 4, 3
    cache = L.init_paged_kv_cache(cfg, n_pages, page)
    assert cache["kp"].shape == (n_pages + 1, K, page, hd // 2)
    assert cache["kp"].dtype == jnp.int8  # packed nibbles
    pt = jnp.asarray([[1, 0]], jnp.int32)
    k = jax.random.normal(KEY, (1, 4, K, hd)) * 3.0
    out = L.paged_cache_insert(
        cache, k, k, pt, jnp.asarray([2], jnp.int32),
        jnp.asarray([4], jnp.int32))
    deq = np.asarray(quant.dequantize(out["kp"], out["kp_scale"], hd))
    for i, (phys, off) in enumerate(((1, 2), (1, 3), (0, 0), (0, 1))):
        err = np.abs(deq[phys, :, off] - np.asarray(k)[0, i])
        step = np.asarray(out["kp_scale"])[phys, :, off][..., None]
        assert float((err - step).max()) < 1e-5


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
    import pytest

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

    # paged: same contract
    pcache = L.init_paged_kv_cache(cfg, 2, 4)
    pbare = {k: v for k, v in pcache.items()
             if k not in ("kp_scale", "vp_scale")}
    pt = jnp.asarray([[0, 1]], jnp.int32)
    kc = jnp.ones((1, 2, K, hd))
    with pytest.raises(TypeError, match="quantization scales"):
        L.paged_cache_insert(pbare, kc, kc, pt,
                             jnp.asarray([0], jnp.int32),
                             jnp.asarray([2], jnp.int32))
    L.paged_cache_insert(pcache, kc, kc, pt,
                         jnp.asarray([0], jnp.int32),
                         jnp.asarray([2], jnp.int32))
