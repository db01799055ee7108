"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth for the per-kernel allclose sweeps in
``tests/test_kernels.py``. They are deliberately naive (materialize the full
attention matrix, unfused updates) — small-shape correctness references.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def attention(q, k, v, *, causal=True, window=None, q_offset=0, k_offset=0,
              scale=None):
    """Naive multi-head attention oracle.

    q: (B, Sq, H, D); k, v: (B, Sk, K, D) with H % K == 0 (GQA).
    q_offset/k_offset: absolute position of q[0]/k[0] (decode: Sq=1,
    q_offset=pos; sequence-parallel shards pass their global offsets).
    Keys at negative absolute positions are always masked (halo padding).
    window: sliding-window size W — key j visible to query i iff
            i - W < j <= i (causal window).
    Returns (B, Sq, H, D) in q.dtype; softmax in fp32.
    """
    B, Sq, H, D = q.shape
    _, Sk, K, _ = k.shape
    G = H // K
    scale = scale if scale is not None else D ** -0.5
    qf = q.reshape(B, Sq, K, G, D).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qf, kf) * scale
    qpos = q_offset + jnp.arange(Sq)[:, None]
    kpos = k_offset + jnp.arange(Sk)[None, :]
    mask = kpos >= 0
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, D).astype(q.dtype)


def paged_attention(q, kp, vp, page_table, *, pos, n_valid, window=None,
                    scale=None, kp_scale=None, vp_scale=None):
    """Naive paged-decode attention oracle.

    q: (B, C, H, D) — C new tokens per row (decode: C=1 valid; chunked
    prefill: up to C). kp/vp: (P, K, page, hd) physical page pool — the
    NEW tokens' K/V are assumed already written into their pages.
    page_table: (B, max_pages) int32 physical page ids, -1 unmapped.
    pos: (B,) absolute position of each row's first new token.
    n_valid: (B,) how many of the C tokens are real this step.
    kp_scale/vp_scale: (P, K, page) per-row dequant scales for
    quantized pools — int8 (hd == D) or int4-packed (hd == D // 2,
    see ``kernels/quant.py``).

    Key at absolute position j is visible to query i (absolute qpos =
    pos + i) iff its page is mapped, j < pos + n_valid, j <= qpos and
    (window) j > qpos - window. Rows/queries beyond n_valid produce
    garbage the caller must ignore. Softmax in fp32.
    """
    from repro.kernels import quant

    B, C, H, D = q.shape
    P, K, page, hd = kp.shape
    G = H // K
    scale = scale if scale is not None else D ** -0.5
    npg = page_table.shape[1]
    pt = jnp.asarray(page_table, jnp.int32)
    safe = jnp.clip(pt, 0, P - 1)
    if kp_scale is not None:
        kg = quant.dequantize(kp[safe], kp_scale[safe], D)
        vg = quant.dequantize(vp[safe], vp_scale[safe], D)
    else:
        kg = kp[safe].astype(jnp.float32)  # (B,npg,K,page,hd)
        vg = vp[safe].astype(jnp.float32)
    kg = jnp.swapaxes(kg, 2, 3).reshape(B, npg * page, K, D)
    vg = jnp.swapaxes(vg, 2, 3).reshape(B, npg * page, K, D)
    qf = (q.astype(jnp.float32) * scale).reshape(B, C, K, G, D)
    logits = jnp.einsum("bckgd,blkd->bckgl", qf, kg)  # (B,C,K,G,L)
    kpos = jnp.arange(npg * page, dtype=jnp.int32)
    qpos = jnp.asarray(pos, jnp.int32)[:, None] + jnp.arange(C)[None, :]
    mapped = jnp.repeat(pt >= 0, page, axis=1)  # (B, L)
    lim = (jnp.asarray(pos, jnp.int32) + jnp.asarray(n_valid, jnp.int32))
    valid = mapped[:, None, :] & (kpos[None, None, :] < lim[:, None, None])
    valid &= kpos[None, None, :] <= qpos[:, :, None]
    if window is not None:
        valid &= kpos[None, None, :] > qpos[:, :, None] - window
    logits = jnp.where(valid[:, :, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bckgl,blkd->bckgd", probs, vg)
    return out.reshape(B, C, H, D).astype(q.dtype)


def lstm_cell(x_proj, h_prev, c_prev, w_h, b):
    """Fused LSTM cell oracle (GNMT C9: input projection pre-hoisted).

    x_proj: (B, 4F) precomputed input projection for this step.
    h_prev, c_prev: (B, F). w_h: (F, 4F). b: (4F,).
    Gate order: i, f, g, o.
    """
    gates = (
        x_proj.astype(jnp.float32)
        + h_prev.astype(jnp.float32) @ w_h.astype(jnp.float32)
        + b.astype(jnp.float32)
    )
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    c = jax.nn.sigmoid(f) * c_prev.astype(jnp.float32) + jax.nn.sigmoid(
        i
    ) * jnp.tanh(g)
    h = jax.nn.sigmoid(o) * jnp.tanh(c)
    return h.astype(x_proj.dtype), c.astype(jnp.float32)


def lars_update(w, g, m, *, lr, weight_decay, momentum, eta, eps=1e-9,
                scaled_momentum=True):
    """Fused LARS update oracle (paper Fig. 5 scaled / Fig. 6 unscaled).

    Returns (new_w, new_m). All math fp32.
    """
    w32, g32, m32 = (a.astype(jnp.float32) for a in (w, g, m))
    w_norm = jnp.linalg.norm(w32)
    g_norm = jnp.linalg.norm(g32)
    trust = jnp.where(
        (w_norm > 0) & (g_norm > 0),
        eta * w_norm / (g_norm + weight_decay * w_norm + eps),
        1.0,
    )
    update = g32 + weight_decay * w32
    if scaled_momentum:
        # MLPerf reference (Fig. 5): v = m*v + (g + beta*w); w -= lr*trust*v
        new_m = momentum * m32 + update
        new_w = w32 - lr * trust * new_m
    else:
        # You et al. (Fig. 6): v = m*v + lr*trust*(g + beta*w); w -= v
        new_m = momentum * m32 + lr * trust * update
        new_w = w32 - new_m
    return new_w.astype(w.dtype), new_m.astype(m.dtype)


def moe_gating(x, router_w, *, top_k, capacity):
    """Top-k gating + capacity dispatch oracle.

    x: (G, S, d); router_w: (d, E).
    Returns (dispatch (G,S,E,C) f32, combine (G,S,E,C) f32, aux_loss scalar).
    """
    G, S, d = x.shape
    E = router_w.shape[-1]
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)  # (G,S,E)
    dispatch = jnp.zeros((G, S, E, capacity), jnp.float32)
    combine = jnp.zeros((G, S, E, capacity), jnp.float32)
    remaining = gates
    # Track per-expert fill across the k rounds.
    fill = jnp.zeros((G, E), jnp.int32)
    for _ in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)  # (G,S)
        gate = jnp.take_along_axis(remaining, idx[..., None], axis=-1)[..., 0]
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # (G,S,E)
        pos = jnp.cumsum(onehot, axis=1) - onehot + fill[:, None, :]
        pos_tok = jnp.take_along_axis(
            pos, idx[..., None], axis=-1
        )[..., 0].astype(jnp.int32)  # (G,S)
        keep = pos_tok < capacity
        poh = jax.nn.one_hot(pos_tok, capacity, dtype=jnp.float32)
        d_k = onehot[..., None] * poh[:, :, None, :] * keep[..., None, None]
        dispatch = dispatch + d_k
        combine = combine + d_k * gate[..., None, None]
        fill = fill + jnp.sum(
            onehot * keep[..., None], axis=1
        ).astype(jnp.int32)
        remaining = remaining * (1.0 - onehot)
    # Load-balance aux loss (Switch): E * sum_e f_e * p_e.
    top1 = jax.nn.one_hot(jnp.argmax(gates, -1), E, dtype=jnp.float32)
    f_e = top1.mean(axis=(0, 1))
    p_e = gates.mean(axis=(0, 1))
    aux = E * jnp.sum(f_e * p_e)
    return dispatch, combine, aux


def mamba_scan(u, dt, A, B, C, D):
    """Selective-scan oracle: sequential recurrence.

    u, dt: (Bt, S, Di); A: (Di, N); B, C: (Bt, S, N); D: (Di,)
    h_t = exp(dt_t * A) * h_{t-1} + dt_t * B_t * u_t ;  y_t = C_t . h_t + D*u_t
    """
    u32, dt32, B32, C32 = (a.astype(jnp.float32) for a in (u, dt, B, C))
    A32, D32 = A.astype(jnp.float32), D.astype(jnp.float32)
    Bt, S, Di = u32.shape
    N = A32.shape[-1]
    h = jnp.zeros((Bt, Di, N), jnp.float32)
    ys = []
    for t in range(S):
        da = jnp.exp(dt32[:, t, :, None] * A32[None])  # (Bt,Di,N)
        h = da * h + dt32[:, t, :, None] * B32[:, t, None, :] * u32[:, t, :, None]
        ys.append(jnp.einsum("bdn,bn->bd", h, C32[:, t]) + D32 * u32[:, t])
    y = jnp.stack(ys, axis=1)
    return y.astype(u.dtype), h
