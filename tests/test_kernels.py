"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels import flash_attention as fa
from repro.kernels import lstm_cell as lk
from repro.kernels import lars as lkr
from repro.kernels import mamba as mk
from repro.kernels import ops

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 else dict(
        rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Sq,Sk,H,K,D,causal,window,q_offset",
    [
        (2, 128, 128, 4, 4, 64, True, None, 0),
        (1, 100, 100, 4, 2, 32, True, None, 0),    # ragged + GQA
        (2, 64, 64, 8, 1, 128, False, None, 0),    # MQA, bidirectional
        (1, 256, 256, 4, 4, 64, True, 64, 0),      # sliding window
        (2, 1, 160, 4, 2, 64, True, None, 159),    # decode-like
        (1, 96, 96, 2, 2, 64, True, 32, 0),
    ],
)
def test_flash_attention_vs_ref(B, Sq, Sk, H, K, D, causal, window,
                                q_offset, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), dtype)
    k = jax.random.normal(ks[1], (B, Sk, K, D), dtype)
    v = jax.random.normal(ks[2], (B, Sk, K, D), dtype)
    want = ref.attention(q, k, v, causal=causal, window=window,
                         q_offset=q_offset)
    got = fa.flash_attention(q, k, v, causal=causal, window=window,
                             q_offset=q_offset, interpret=True,
                             block_q=64, block_k=64)
    np.testing.assert_allclose(
        got.astype(np.float32), want.astype(np.float32), **_tol(dtype))


def test_flash_attention_k_offset_negative_positions_masked():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 32, 2, 32))
    k = jax.random.normal(ks[1], (1, 48, 2, 32))
    v = jax.random.normal(ks[2], (1, 48, 2, 32))
    # halo layout: first 16 keys are at negative positions
    want = ref.attention(q, k, v, causal=True, window=16, q_offset=0,
                         k_offset=-16)
    got = fa.flash_attention(q, k, v, causal=True, window=16, q_offset=0,
                             k_offset=-16, interpret=True, block_q=16,
                             block_k=16)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize(
    "Sq,Sk,H,K,causal,window,q_offset,k_offset",
    [
        (48, 48, 4, 2, True, None, 0, 0),       # causal GQA
        (40, 40, 2, 2, True, 12, 0, 0),         # sliding window
        (24, 24, 4, 1, False, None, 0, 0),      # MQA, bidirectional
        (1, 37, 4, 2, True, None, 36, 0),       # decode-like
        (32, 48, 2, 2, True, 16, 0, -16),       # halo at negative positions
    ],
)
def test_flash_attention_custom_vjp_matches_chunked_grad(
        Sq, Sk, H, K, causal, window, q_offset, k_offset, monkeypatch):
    """The flash kernel's custom VJP (XLA backward from the saved
    log-sum-exp) == jax.grad through the chunked jnp attention, with
    backward tiles smaller than the sequence so the tile loops and the
    causal/window band skipping are exercised."""
    monkeypatch.setattr(fa, "BWD_BLOCK", 16)
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (2, Sq, H, 32))
    k = jax.random.normal(ks[1], (2, Sk, K, 32))
    v = jax.random.normal(ks[2], (2, Sk, K, 32))
    ct = jax.random.normal(ks[3], (2, Sq, H, 32))
    kw = dict(causal=causal, window=window, q_offset=q_offset,
              k_offset=k_offset, scale=None)

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v) * ct)

    got = jax.grad(loss(lambda q, k, v: fa.flash_attention(
        q, k, v, interpret=True, block_q=16, block_k=16, **kw)),
        argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: ops._chunked_attention(
        q, k, v, chunk=16, **kw)), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-5, atol=2e-5)


def test_chunked_jnp_attention_vs_ref():
    ks = jax.random.split(KEY, 3)
    for (Sq, Sk, chunk) in [(128, 128, 32), (100, 100, 48), (1, 77, 16)]:
        q = jax.random.normal(ks[0], (2, Sq, 4, 32))
        k = jax.random.normal(ks[1], (2, Sk, 2, 32))
        v = jax.random.normal(ks[2], (2, Sk, 2, 32))
        qo = Sk - Sq
        want = ref.attention(q, k, v, causal=True, window=24, q_offset=qo)
        got = ops._chunked_attention(q, k, v, causal=True, window=24,
                                     q_offset=qo, k_offset=0, scale=None,
                                     chunk=chunk)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,F,block", [(48, 96, 32), (5, 64, 128), (128, 128, 64)])
def test_lstm_cell_vs_ref(B, F, block, dtype):
    ks = jax.random.split(KEY, 5)
    xp = jax.random.normal(ks[0], (B, 4 * F), dtype)
    h = jax.random.normal(ks[1], (B, F), dtype)
    c = jax.random.normal(ks[2], (B, F), jnp.float32)
    wh = jax.random.normal(ks[3], (F, 4 * F), dtype) * 0.1
    b = jax.random.normal(ks[4], (4 * F,), jnp.float32) * 0.1
    h1, c1 = ref.lstm_cell(xp, h, c, wh, b)
    h2, c2 = lk.lstm_cell(xp, h, c, wh, b, interpret=True, block_b=block)
    np.testing.assert_allclose(h2.astype(np.float32),
                               h1.astype(np.float32), **_tol(dtype))
    np.testing.assert_allclose(c2, c1, **_tol(dtype))


@pytest.mark.parametrize("scaled", [True, False])
@pytest.mark.parametrize("shape", [(300, 170), (64,), (7, 9, 11)])
def test_lars_kernel_vs_ref(scaled, shape):
    ks = jax.random.split(KEY, 2)
    w = jax.random.normal(ks[0], shape)
    g = jax.random.normal(ks[1], shape)
    m = jnp.zeros(shape)
    kw = dict(lr=0.1, weight_decay=1e-4, momentum=0.9, eta=0.001,
              scaled_momentum=scaled)
    w1, m1 = ref.lars_update(w, g, m, **kw)
    w2, m2 = lkr.lars_update(w, g, m, interpret=True, **kw)
    np.testing.assert_allclose(w2, w1, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(m2, m1, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("Bt,S,Di,N,block_d", [(2, 24, 48, 8, 16),
                                               (1, 17, 33, 4, 32)])
def test_mamba_kernel_vs_ref(Bt, S, Di, N, block_d):
    ks = jax.random.split(KEY, 6)
    u = jax.random.normal(ks[0], (Bt, S, Di)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bt, S, Di))) * 0.1
    A = -jnp.abs(jax.random.normal(ks[2], (Di, N)))
    B = jax.random.normal(ks[3], (Bt, S, N)) * 0.3
    C = jax.random.normal(ks[4], (Bt, S, N)) * 0.3
    D = jax.random.normal(ks[5], (Di,)) * 0.1
    y1, h1 = ref.mamba_scan(u, dt, A, B, C, D)
    y2, h2 = mk.mamba_scan(u, dt, A, B, C, D, interpret=True,
                           block_d=block_d)
    np.testing.assert_allclose(y2, y1, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(h2, h1, rtol=1e-4, atol=1e-5)


def test_ops_mamba_scan_matches_ref():
    ks = jax.random.split(KEY, 6)
    Bt, S, Di, N = 2, 40, 16, 4
    u = jax.random.normal(ks[0], (Bt, S, Di)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bt, S, Di))) * 0.1
    A = -jnp.abs(jax.random.normal(ks[2], (Di, N)))
    B = jax.random.normal(ks[3], (Bt, S, N)) * 0.3
    C = jax.random.normal(ks[4], (Bt, S, N)) * 0.3
    D = jax.random.normal(ks[5], (Di,)) * 0.1
    y1, h1 = ref.mamba_scan(u, dt, A, B, C, D)
    y2, h2 = ops.mamba_scan(u, dt, A, B, C, D)
    np.testing.assert_allclose(y2, y1, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(h2, h1, rtol=1e-4, atol=1e-5)


def test_moe_gating_properties():
    G, S, d, E, k, cap = 3, 16, 8, 4, 2, 9
    x = jax.random.normal(KEY, (G, S, d))
    router = jax.random.normal(jax.random.PRNGKey(1), (d, E))
    dispatch, combine, aux = ref.moe_gating(x, router, top_k=k, capacity=cap)
    # each token dispatched to <= k slots, one per chosen expert
    per_token = dispatch.sum(axis=(2, 3))
    assert (per_token <= k + 1e-6).all()
    # capacity respected
    assert (dispatch.sum(axis=1) <= 1 + 1e-6).all()  # one token per (e,c) slot
    # combine weights only where dispatched, bounded by 1
    assert (combine <= dispatch + 1e-6).all()
    assert float(aux) > 0


from hypothesis import given, settings, strategies as st


@given(
    st.integers(1, 8),    # Sq chunks-ish
    st.integers(1, 8),    # extra ragged
    st.sampled_from([None, 16, 48]),
    st.sampled_from([16, 32, 64]),
)
@settings(max_examples=20, deadline=None)
def test_block_skip_attention_property(nq, ragged, window, chunk):
    """Property: block-skipping chunked attention == naive oracle for
    arbitrary ragged lengths / windows / chunk sizes."""
    Sq = nq * 16 + ragged
    q = jax.random.normal(jax.random.PRNGKey(nq), (1, Sq, 2, 16))
    k = jax.random.normal(jax.random.PRNGKey(nq + 1), (1, Sq, 1, 16))
    v = jax.random.normal(jax.random.PRNGKey(nq + 2), (1, Sq, 1, 16))
    want = ref.attention(q, k, v, causal=True, window=window)
    got = ops._chunked_attention(
        q, k, v, causal=True, window=window, q_offset=0, k_offset=0,
        scale=None, chunk=chunk)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)


# --------------------------------------------------------------------------- #
# Paged decode attention (serving hot path).
# --------------------------------------------------------------------------- #
def _paged_case(seed, B, C, H, K, D, page, P, npg, lens, nvs, dtype):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, C, H, D), dtype)
    kp = jax.random.normal(ks[1], (P, K, page, D), dtype)
    vp = jax.random.normal(ks[2], (P, K, page, D), dtype)
    rng = np.random.RandomState(seed)
    pt = np.full((B, npg), -1, np.int32)
    pos = np.zeros((B,), np.int32)
    free = list(rng.permutation(P))
    for b in range(B):
        n_pages = -(-lens[b] // page) if lens[b] else 0
        pt[b, :n_pages] = [free.pop() for _ in range(n_pages)]
        pos[b] = max(0, lens[b] - nvs[b])
    return q, kp, vp, jnp.asarray(pt), jnp.asarray(pos), jnp.asarray(
        np.asarray(nvs, np.int32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [None, 5])
def test_paged_attention_kernel_vs_ref(dtype, window):
    """Pallas kernel (interpret) == oracle on the valid region of a
    ragged mixed batch: a deep decode row, a mid-prefill chunk row and a
    short row; entries past n_valid are garbage by contract."""
    from repro.kernels import paged_attention as pa

    lens, nvs = [13, 6, 2], [1, 4, 2]
    q, kp, vp, pt, pos, nv = _paged_case(
        3, 3, 4, 4, 2, 32, 4, 12, 8, lens, nvs, dtype)
    want = ref.paged_attention(q, kp, vp, pt, pos=pos, n_valid=nv,
                               window=window)
    got = pa.paged_attention(q, kp, vp, pt, pos=pos, n_valid=nv,
                             window=window, interpret=True)
    for b, n in enumerate(nvs):
        np.testing.assert_allclose(
            np.asarray(got[b, :n], np.float32),
            np.asarray(want[b, :n], np.float32), **_tol(dtype))


def test_paged_attention_ops_fallback_vs_ref():
    """The jnp fallback in ops (gather + masked softmax) matches the
    oracle everywhere, including MQA grouping."""
    lens, nvs = [9, 1], [3, 1]
    q, kp, vp, pt, pos, nv = _paged_case(
        5, 2, 3, 4, 1, 16, 2, 10, 6, lens, nvs, jnp.float32)
    want = ref.paged_attention(q, kp, vp, pt, pos=pos, n_valid=nv)
    got = ops.paged_attention(q, kp, vp, pt, pos=pos, n_valid=nv)
    for b, n in enumerate(nvs):
        np.testing.assert_allclose(
            np.asarray(got[b, :n]), np.asarray(want[b, :n]),
            rtol=2e-5, atol=2e-5)


def test_paged_attention_matches_dense_decode():
    """One decode token against a paged pool == decode_attention against
    the equivalent dense ring cache (the slab<->paged bridge the engine
    identity tests rely on)."""
    B, H, K, D, page = 2, 4, 2, 16, 4
    S = 7  # tokens already cached per row
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, 1, H, D))
    k = jax.random.normal(ks[1], (B, S + 1, K, D))
    v = jax.random.normal(ks[2], (B, S + 1, K, D))
    # dense ring cache holding positions 0..S (slot_pos labeled)
    dense = {
        "k": jnp.pad(k, ((0, 0), (0, 3), (0, 0), (0, 0))),
        "v": jnp.pad(v, ((0, 0), (0, 3), (0, 0), (0, 0))),
        "slot_pos": jnp.pad(
            jnp.broadcast_to(jnp.arange(S + 1), (B, S + 1)),
            ((0, 0), (0, 3)), constant_values=-1),
    }
    want = ops.decode_attention(q, dense["k"], dense["v"],
                                dense["slot_pos"], pos=S)
    # paged pool with the same K/V scattered into mapped pages
    pt = jnp.asarray([[3, 0], [1, 2]], jnp.int32)
    kp = jnp.zeros((5, K, page, D))
    vp = jnp.zeros((5, K, page, D))
    for b in range(B):
        for t in range(S + 1):
            phys = int(pt[b, t // page])
            kp = kp.at[phys, :, t % page].set(k[b, t])
            vp = vp.at[phys, :, t % page].set(v[b, t])
    got = ops.paged_attention(
        q, kp, vp, pt, pos=jnp.full((B,), S, jnp.int32),
        n_valid=jnp.ones((B,), jnp.int32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------------- #
# Quantized paged pools: pack/unpack round-trips and kernel parity.
# --------------------------------------------------------------------------- #
from repro.kernels import quant


def test_int4_pack_unpack_roundtrip():
    """Halves-layout nibble packing is lossless over the int4 range."""
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randint(-8, 8, size=(5, 3, 16)), jnp.int8)
    packed = quant.pack_int4(q)
    assert packed.shape == (5, 3, 8) and packed.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(quant.unpack_int4(packed)), q)
    with pytest.raises(ValueError, match="even"):
        quant.pack_int4(q[..., :15])


@pytest.mark.parametrize("qz,lim", [(quant.quantize_int8, 127),
                                    (quant.quantize_int4, 7)])
def test_quantize_bounded_error(qz, lim):
    """Symmetric per-(row, head) quantization: codes live in [-lim, lim]
    and dequantization reconstructs within one scale step."""
    x = jax.random.normal(KEY, (12, 2, 32)) * 3.0
    code, scale = qz(x)
    assert scale.shape == (12, 2) and scale.dtype == jnp.float32
    deq = quant.dequantize(code, scale, 32)
    amax = np.abs(np.asarray(x)).max(axis=-1)
    assert np.all(np.abs(np.asarray(deq)) <= amax[..., None] + 1e-6)
    np.testing.assert_allclose(np.asarray(deq), np.asarray(x),
                               atol=float(scale.max()) * 0.51 + 1e-6)


def _quantize_pool(kp, vp, qz):
    kq, ks = qz(kp)  # per-(page, head, token) scales over head_dim
    vq, vs = qz(vp)
    return kq, vq, ks, vs


@pytest.mark.parametrize("qdtype", ["int8", "int4"])
@pytest.mark.parametrize("window", [None, 5])
def test_paged_attention_quantized_kernel_vs_ref(qdtype, window):
    """Quantized-pool Pallas kernel (in-kernel dequant, fp32 accumulation)
    == the scale-aware oracle on the valid region of a ragged mixed
    batch, for both int8 and packed-int4 pools."""
    from repro.kernels import paged_attention as pa

    qz = quant.quantize_int8 if qdtype == "int8" else quant.quantize_int4
    lens, nvs = [13, 6, 2], [1, 4, 2]
    q, kp, vp, pt, pos, nv = _paged_case(
        3, 3, 4, 4, 2, 32, 4, 12, 8, lens, nvs, jnp.float32)
    kpq, vpq, ks, vs = _quantize_pool(kp, vp, qz)
    want = ref.paged_attention(q, kpq, vpq, pt, pos=pos, n_valid=nv,
                               window=window, kp_scale=ks, vp_scale=vs)
    got = pa.paged_attention(q, kpq, vpq, pt, pos=pos, n_valid=nv,
                             window=window, kp_scale=ks, vp_scale=vs,
                             interpret=True)
    for b, n in enumerate(nvs):
        np.testing.assert_allclose(
            np.asarray(got[b, :n], np.float32),
            np.asarray(want[b, :n], np.float32), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("qdtype", ["int8", "int4"])
def test_paged_attention_quantized_ops_fallback_vs_ref(qdtype):
    """The jnp fallback dequantizes identically (the shim infers int4
    from the packed trailing dim, so legacy call sites need no flag)."""
    qz = quant.quantize_int8 if qdtype == "int8" else quant.quantize_int4
    lens, nvs = [9, 1], [3, 1]
    q, kp, vp, pt, pos, nv = _paged_case(
        5, 2, 3, 4, 1, 16, 2, 10, 6, lens, nvs, jnp.float32)
    kpq, vpq, ks, vs = _quantize_pool(kp, vp, qz)
    want = ref.paged_attention(q, kpq, vpq, pt, pos=pos, n_valid=nv,
                               kp_scale=ks, vp_scale=vs)
    got = ops.paged_attention(q, kpq, vpq, pt, pos=pos, n_valid=nv,
                              kp_scale=ks, vp_scale=vs)
    for b, n in enumerate(nvs):
        np.testing.assert_allclose(
            np.asarray(got[b, :n]), np.asarray(want[b, :n]),
            rtol=2e-5, atol=2e-5)


def test_paged_attention_quantized_close_to_fp32():
    """End-to-end quantization error on the attention output is small:
    int8 pools track the fp32 pool tightly, int4 more loosely."""
    lens, nvs = [13, 6, 2], [1, 4, 2]
    q, kp, vp, pt, pos, nv = _paged_case(
        7, 3, 4, 4, 2, 32, 4, 12, 8, lens, nvs, jnp.float32)
    want = ref.paged_attention(q, kp, vp, pt, pos=pos, n_valid=nv)
    for qz, tol in [(quant.quantize_int8, 0.02), (quant.quantize_int4, 0.25)]:
        kpq, vpq, ks, vs = _quantize_pool(kp, vp, qz)
        got = ref.paged_attention(q, kpq, vpq, pt, pos=pos, n_valid=nv,
                                  kp_scale=ks, vp_scale=vs)
        for b, n in enumerate(nvs):
            np.testing.assert_allclose(
                np.asarray(got[b, :n]), np.asarray(want[b, :n]), atol=tol)
