"""Ragged paged-decode attention Pallas kernel (TPU target).

The serving engine stores KV in fixed-size pages of a shared physical
pool (``repro.serve.cache.PagePool``); each batch row owns the pages its
page-table row maps. This kernel runs online-softmax attention for C new
tokens per row against *only the pages that row actually occupies*:

  * the pool is head-major within a page, ``(P, K, page, hd)``, and the
    kernel runs on head-major queries ``(B, H, C, D)``, so the last two
    dims of every block are (tokens, head_dim) — the layout Mosaic tiles
    (a block taking 1 of H or K in the second-minor place is refused);
  * grid ``(B, H, max_pages)`` — the page axis is the sequential minor
    dimension, so fp32 online-softmax accumulators live in VMEM scratch
    across it (same structure as ``kernels/flash_attention.py``);
  * the page table, per-row start positions and per-row valid-token
    counts are **scalar-prefetched** (``pltpu.PrefetchScalarGridSpec``):
    the K/V BlockSpec index maps read the page table to DMA the right
    physical page, the classic paged-attention indirection;
  * pages past a row's occupancy (``p * page >= pos + n_valid``) and
    unmapped pages skip their compute via ``pl.when`` — a ragged batch
    pays for the tokens it holds, not for ``max_len``.

Quantized pools run through the same kernel: pass ``kp_scale`` /
``vp_scale`` of shape ``(P, K, page)``; each grid step DMAs the page's
``(K, page)`` scale block through the same page-table indirection and
picks its KV head's row. A per-key scale is a per-column scale of the
``(C, page)`` score and probability matrices, so it is applied there
and the dequantized page is never built. int8 pools carry
``(P, K, page, hd)`` values; int4 pools pack two dims per byte
(``(P, K, page, hd // 2)``, halves layout — see ``kernels/quant.py``)
and are unpacked in-kernel with pure integer ops. Accumulation stays
fp32 throughout, so quantization only narrows the HBM reads — which is
the point: decode is bandwidth-bound and int8/int4 halves/quarters the
bytes per step.

GQA folds the query head onto its KV head in the index maps. The new
tokens' K/V must already be written into their pages (the model layer
scatters before attending, see ``layers.paged_cache_insert``).
Validated against ``kernels/ref.paged_attention`` in interpret mode on
CPU (tests/test_kernels.py); ``tests/test_tpu_compile.py`` compiles it
for a v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import quant

NEG_INF = -1e30


def _kernel(pt_ref, pos_ref, nv_ref, q_ref, k_ref, v_ref, *rest,
            scale, window, page, n_pages, C, G, int4):
    # Quantized calls carry two extra scale operands between the pool
    # refs and the output ref; scratch always trails.
    if len(rest) == 6:
        ks_ref, vs_ref, o_ref, acc, m, l = rest
    else:
        ks_ref = vs_ref = None
        o_ref, acc, m, l = rest
    b = pl.program_id(0)
    kh = pl.program_id(1) // G  # this query head's KV head
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m[...] = jnp.full_like(m, NEG_INF)
        l[...] = jnp.zeros_like(l)
        acc[...] = jnp.zeros_like(acc)

    pos = pos_ref[b]
    lim = pos + nv_ref[b]  # first absolute position past this row's tokens
    used = jnp.logical_and(pt_ref[b, p] >= 0, p * page < lim)

    @pl.when(used)
    def _update():
        qb = q_ref[0, 0].astype(jnp.float32) * scale       # (C, D)
        kraw = k_ref[0, 0]                                  # (page, D|D//2)
        vraw = v_ref[0, 0]
        if int4:
            kraw = quant.unpack_int4(kraw)                  # (page, D)
            vraw = quant.unpack_int4(vraw)
        kb = kraw.astype(jnp.float32)
        vb = vraw.astype(jnp.float32)
        s = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (C, page)
        if ks_ref is not None:
            s = s * ks_ref[0, pl.ds(kh, 1), :]              # (1, page)
        rows = jax.lax.broadcasted_iota(jnp.int32, (C, page), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (C, page), 1)
        qpos = pos + rows
        kpos = p * page + cols
        mask = (kpos < lim) & (kpos <= qpos)
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m[...]                                     # (C, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        pexp = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l[...] = l[...] * corr + pexp.sum(axis=-1, keepdims=True)
        if vs_ref is not None:
            pexp = pexp * vs_ref[0, pl.ds(kh, 1), :]
        acc[...] = acc[...] * corr + jax.lax.dot_general(
            pexp, vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m[...] = m_new

    @pl.when(p == n_pages - 1)
    def _finish():
        o_ref[0, 0] = (acc[...] / jnp.maximum(l[...], 1e-30)).astype(
            o_ref.dtype)


def paged_attention(q, kp, vp, page_table, *, pos, n_valid, window=None,
                    scale=None, kp_scale=None, vp_scale=None,
                    interpret=False):
    """q: (B, C, H, D); kp/vp: (P, K, page, hd) with H % K == 0.

    page_table: (B, max_pages) int32 physical page ids (-1 unmapped);
    pos/n_valid: (B,) int32. kp_scale/vp_scale: (P, K, page) fp32
    per-row dequant scales for quantized pools — int8 pools have
    hd == D, int4-packed pools hd == D // 2. Returns (B, C, H, D) in
    q.dtype.
    """
    B, C, H, D = q.shape
    P, K, page, hd = kp.shape
    quantized = kp_scale is not None
    int4 = quantized and hd != D
    if int4 and hd != D // 2:
        raise ValueError(
            f"quantized pool trailing dim {hd} matches neither head_dim "
            f"{D} (int8) nor head_dim//2 {D // 2} (int4-packed)")
    if not quantized and hd != D:
        raise ValueError(f"head_dim mismatch: q {D} vs pool {hd}")
    if quantized and (vp_scale is None) != (kp_scale is None):
        raise ValueError("kp_scale and vp_scale must be passed together")
    G = H // K
    n_pages = page_table.shape[1]
    scale = scale if scale is not None else D ** -0.5

    pt = jnp.asarray(page_table, jnp.int32)
    posv = jnp.asarray(pos, jnp.int32).reshape(B)
    nv = jnp.asarray(n_valid, jnp.int32).reshape(B)
    # Unmapped pages DMA page 0 (skipped by pl.when); keep ids in range.
    pt_safe = jnp.clip(pt, -1, P - 1)

    def q_map(b, h, p, *refs):
        return (b, h, 0, 0)

    def kv_map(b, h, p, pt_ref, pos_ref, nv_ref):
        return (jnp.maximum(pt_ref[b, p], 0), h // G, 0, 0)

    def scale_map(b, h, p, pt_ref, pos_ref, nv_ref):
        return (jnp.maximum(pt_ref[b, p], 0), 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, C, D), q_map),
        pl.BlockSpec((1, 1, page, hd), kv_map),
        pl.BlockSpec((1, 1, page, hd), kv_map),
    ]
    # C new tokens per row are few; moving them head-major is cheap,
    # unlike the pool, which is stored head-major.
    operands = [jnp.swapaxes(q, 1, 2), kp, vp]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, K, page), scale_map),
            pl.BlockSpec((1, K, page), scale_map),
        ]
        operands += [kp_scale.astype(jnp.float32),
                     vp_scale.astype(jnp.float32)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, H, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, C, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((C, D), jnp.float32),
            pltpu.VMEM((C, 1), jnp.float32),
            pltpu.VMEM((C, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _kernel, scale=scale, window=window, page=page, n_pages=n_pages,
        C=C, G=G, int4=int4,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, C, D), q.dtype),
        interpret=interpret,
    )(pt_safe, posv, nv, *operands)
    return jnp.swapaxes(out, 1, 2)
