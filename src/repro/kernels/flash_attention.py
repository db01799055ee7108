"""Blocked online-softmax attention Pallas kernel (TPU target), with a
custom VJP.

Forward tiling: the kernel runs head-major, q ``(B, H, Sq, D)`` and k/v
``(B, K, Sk, D)``, so the last two dims of every block are
(tokens, head_dim) — the layout Mosaic tiles. Grid (B, H, nQ, nKV); each
step loads a (block_q, D) query tile and a (block_k, D) key/value tile
into VMEM, runs the (block_q x block_k) MXU matmul, and maintains fp32
online-softmax accumulators in VMEM scratch across the sequential minor
grid dimension (TPU grids execute minor-to-major, so the KV axis acts as
the inner loop). Blocks default to 128 — MXU-aligned on both matmul
dims. Besides the output the kernel writes each query's log-sum-exp.

Backward: an XLA computation, not a kernel. It recomputes the softmax
probabilities tile by tile from the saved log-sum-exp (no second online
pass) and forms dq/dk/dv with the standard flash-attention identities
``dS = P * (dP - rowsum(dO * O))``. Query tiles are a Python loop that
visits only the KV tiles its causal/window band intersects (as in
``ops._chunked_attention``); KV tiles are a ``lax.scan``, so memory is
O(tile^2) per head, not O(S^2).

Supports causal + sliding-window masks and GQA (the K/V index map folds
the query head to its KV head; the backward sums a group's dk/dv).
Validated against ``kernels/ref.py`` in interpret mode on CPU
(tests/test_kernels.py); ``tests/test_tpu_compile.py`` compiles forward
and gradient for a v5e.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BWD_BLOCK = 512  # backward tile edge: (B, H, 512, 512) fp32 per temporary


class _Opts(NamedTuple):
    causal: bool
    window: Optional[int]
    q_offset: int
    k_offset: int
    scale: float
    interpret: bool
    block_q: int
    block_k: int


def _kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m, l, *, scale,
            causal, window, q_offset, k_offset, n_kv, block_q, block_k, sq,
            sk):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m[...] = jnp.full_like(m, NEG_INF)
        l[...] = jnp.zeros_like(l)
        acc[...] = jnp.zeros_like(acc)

    qb = q_ref[0, 0].astype(jnp.float32) * scale  # (bq, D)
    kb = k_ref[0, 0].astype(jnp.float32)          # (bk, D)
    vb = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(
        qb, kb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (bq, bk)

    rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    q_idx = qi * block_q + rows
    k_idx = ki * block_k + cols
    qpos = q_offset + q_idx
    kpos = k_offset + k_idx
    mask = (q_idx < sq) & (k_idx < sk) & (kpos >= 0)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m[...]                                # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l[...] = l[...] * corr + p.sum(axis=-1, keepdims=True)
    acc[...] = acc[...] * corr + jax.lax.dot_general(
        p, vb, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finish():
        lsum = jnp.maximum(l[...], 1e-30)
        o_ref[0, 0] = (acc[...] / lsum).astype(o_ref.dtype)
        lse_ref[0, 0] = m[...] + jnp.log(lsum)


def _forward(q, k, v, o: _Opts):
    """Head-major q (B,H,Sq,D), k/v (B,K,Sk,D) -> (out, lse (B,H,Sq))."""
    B, H, Sq, D = q.shape
    _, K, Sk, _ = k.shape
    G = H // K
    block_q = min(o.block_q, Sq)
    block_k = min(o.block_k, Sk)
    n_q = -(-Sq // block_q)
    n_kv = -(-Sk // block_k)
    pad_q = n_q * block_q - Sq
    pad_k = n_kv * block_k - Sk
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0))) if pad_k else v

    kernel = functools.partial(
        _kernel, scale=o.scale, causal=o.causal, window=o.window,
        q_offset=o.q_offset, k_offset=o.k_offset, n_kv=n_kv,
        block_q=block_q, block_k=block_k, sq=Sq, sk=Sk,
    )
    q_map = lambda b, h, qi, ki: (b, h, qi, 0)
    kv_map = lambda b, h, qi, ki: (b, h // G, ki, 0)
    out, lse = pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), q_map),
            pl.BlockSpec((1, 1, block_k, D), kv_map),
            pl.BlockSpec((1, 1, block_k, D), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), q_map),
            pl.BlockSpec((1, 1, block_q, 1), q_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, n_q * block_q, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, n_q * block_q, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=o.interpret,
    )(qp, kp, vp)
    return out[:, :, :Sq], lse[:, :, :Sq, 0]


def _backward(o: _Opts, q, k, v, out, lse, dout):
    """dq/dk/dv recomputed from the saved log-sum-exp, tile by tile."""
    B, H, Sq, D = q.shape
    _, K, Sk, _ = k.shape
    G = H // K
    f32 = jnp.float32
    qg = q.reshape(B, K, G, Sq, D)
    dog = dout.reshape(B, K, G, Sq, D)
    lseg = lse.reshape(B, K, G, Sq)
    delta = jnp.sum(dout.astype(f32) * out.astype(f32), axis=-1).reshape(
        B, K, G, Sq)

    ck = min(BWD_BLOCK, Sk)
    n_kv = -(-Sk // ck)
    pad = n_kv * ck - Sk
    kc = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0))).astype(f32)
    vc = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0))).astype(f32)
    dk = jnp.zeros((B, K, n_kv * ck, D), f32)
    dv = jnp.zeros((B, K, n_kv * ck, D), f32)

    cq = min(BWD_BLOCK, Sq)
    dqs = []
    for q_lo in range(0, Sq, cq):
        q_hi = min(Sq, q_lo + cq)
        qb = qg[:, :, :, q_lo:q_hi].astype(f32)
        dob = dog[:, :, :, q_lo:q_hi].astype(f32)
        lseb = lseg[:, :, :, q_lo:q_hi]
        deltab = delta[:, :, :, q_lo:q_hi]
        qpos = o.q_offset + q_lo + jnp.arange(q_hi - q_lo)
        # band of KV tiles this query tile can see
        lo, hi = 0, n_kv
        if o.causal:
            hi = min(n_kv, max(0, (o.q_offset + q_hi - 1 - o.k_offset)
                                // ck + 1))
        if o.window is not None:
            lo = min(max((o.q_offset + q_lo - o.window + 1 - o.k_offset)
                         // ck, 0), hi)

        def body(carry, j, qb=qb, dob=dob, lseb=lseb, deltab=deltab,
                 qpos=qpos):
            dq, dk, dv = carry
            kj = jax.lax.dynamic_slice_in_dim(kc, j * ck, ck, axis=2)
            vj = jax.lax.dynamic_slice_in_dim(vc, j * ck, ck, axis=2)
            kidx = j * ck + jnp.arange(ck)
            kpos = o.k_offset + kidx
            mask = (kidx[None, :] < Sk) & (kpos[None, :] >= 0)
            if o.causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if o.window is not None:
                mask &= kpos[None, :] > qpos[:, None] - o.window
            s = jnp.einsum("bkgqd,bksd->bkgqs", qb, kj) * o.scale
            p = jnp.where(mask, jnp.exp(s - lseb[..., None]), 0.0)
            dp = jnp.einsum("bkgqd,bksd->bkgqs", dob, vj)
            ds = p * (dp - deltab[..., None]) * o.scale
            dq = dq + jnp.einsum("bkgqs,bksd->bkgqd", ds, kj)
            dk_j = jnp.einsum("bkgqs,bkgqd->bksd", ds, qb)
            dv_j = jnp.einsum("bkgqs,bkgqd->bksd", p, dob)
            at = j * ck
            dk = jax.lax.dynamic_update_slice_in_dim(
                dk, jax.lax.dynamic_slice_in_dim(dk, at, ck, 2) + dk_j,
                at, axis=2)
            dv = jax.lax.dynamic_update_slice_in_dim(
                dv, jax.lax.dynamic_slice_in_dim(dv, at, ck, 2) + dv_j,
                at, axis=2)
            return (dq, dk, dv), None

        dq0 = jnp.zeros(qb.shape, f32)
        (dq, dk, dv), _ = jax.lax.scan(body, (dq0, dk, dv),
                                       jnp.arange(lo, hi))
        dqs.append(dq)
    dq = jnp.concatenate(dqs, axis=3).reshape(B, H, Sq, D)
    return (dq.astype(q.dtype), dk[:, :, :Sk].astype(k.dtype),
            dv[:, :, :Sk].astype(v.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, o: _Opts):
    return _forward(q, k, v, o)[0]


def _flash_fwd(q, k, v, o: _Opts):
    out, lse = _forward(q, k, v, o)
    return out, (q, k, v, out, lse)


def _flash_bwd(o: _Opts, res, dout):
    return _backward(o, *res, dout)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal=True, window=None, q_offset=0,
                    k_offset=0, scale=None, interpret=False,
                    block_q=128, block_k=128):
    """q: (B, Sq, H, D); k, v: (B, Sk, K, D) with H % K == 0.

    Differentiable: ``jax.grad`` runs the XLA backward above.
    """
    if not isinstance(q_offset, int) or not isinstance(k_offset, int):
        raise ValueError("flash kernel needs static offsets; use the jnp "
                         "path for traced offsets")
    D = q.shape[-1]
    opts = _Opts(
        causal=bool(causal), window=window, q_offset=q_offset,
        k_offset=k_offset,
        scale=float(scale) if scale is not None else D ** -0.5,
        interpret=bool(interpret), block_q=block_q, block_k=block_k)
    out = _flash(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                 jnp.swapaxes(v, 1, 2), opts)
    return jnp.swapaxes(out, 1, 2)
