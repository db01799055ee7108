"""Ragged paged-decode attention Pallas kernel (TPU target).

The serving engine stores KV in fixed-size pages of a shared physical
pool (``repro.serve.cache.PagePool``); each batch row owns the pages its
page-table row maps. This kernel runs online-softmax attention for C new
tokens per row against *only the pages that row actually occupies*:

  * grid ``(B, K)``: one step per (row, KV head). The G = H / K query
    heads that share the KV head are folded into one query block: the
    wrapper lays q out as ``(B, K, G * C, D)``, so block row ``r`` is
    query head ``r // C`` of the group at position ``pos + r % C``. The
    last two dims of every block are the array's own, which Mosaic tiles
    for any C (C = 1 included);
  * inside a grid step a ``fori_loop`` walks only the page *blocks* the
    row occupies, ``ceil((pos + n_valid) / tokens_per_block)`` of them
    (from the first block a sliding window reaches). A block is a run of
    ``128 // page`` pages (at most the table width), ≈ 128 tokens, so
    each step's products are ``(G * C, D) x (D, 128)`` MXU work rather
    than one page per query head;
  * the pool stays in HBM (``memory_space=pltpu.HBM``). Each page of a
    block is copied ``pool.at[page_table[b, p], k]`` into VMEM by hand
    with ``pltpu.make_async_copy``, reading the **scalar-prefetched**
    page table (``pltpu.PrefetchScalarGridSpec``, with the per-row start
    positions and valid-token counts). The copies are double-buffered:
    block i + 1's pages are in flight while block i is computed. Mosaic
    slices an HBM array only along lane-dense trailing dims, so a pool
    narrower than 128 lanes (int4-packed, head_dim 64) is padded to the
    lanes before the call;
  * unmapped pages (id -1) inside the occupied range, and the block's
    pages past the row's tokens, are not copied: their buffer pages are
    zeroed and their keys masked. Rows with no tokens do no work and
    write zeros. fp32 online-softmax accumulators live in VMEM scratch.

Quantized pools run through the same kernel: pass ``kp_scale`` /
``vp_scale`` of shape ``(P, K, page)``. A page's scales are too narrow
to copy on their own, so the wrapper gathers each row's scales in key
order, one ``(1, tokens)`` row a block. A per-key scale is a per-column
scale of the ``(G * C, tokens)`` score and probability matrices, so it
is applied there and the dequantized page is never built. int8 pools
carry ``(P, K, page, hd)`` values; int4 pools pack two dims per byte
(``(P, K, page, hd // 2)``, halves layout — see ``kernels/quant.py``)
and are unpacked in-kernel with pure integer ops. Scores take the wider
of q's and the pool's dtype (integer codes are exact in q's) and
accumulate in fp32; P·V is fp32.

The new tokens' K/V must already be written into their pages: the model
layer scatters them in place before attending (``paged_pool.insert``).
The chunk program hands the kernel its layer stack's pools as one pool,
the layer's page ids offset into it, so no layer's pool is copied for the
call; a pool narrower than the lanes, and a call under a mesh, take the
layer's own pool instead (``paged_pool.layer_view``).
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
BLOCK_TOKENS = 128  # MXU edge: a page block holds about this many keys
LANES = 128  # a DMA'd page's trailing dim is padded to a multiple of this


def _kernel(pt_ref, pos_ref, nv_ref, q_ref, kp_ref, vp_ref, *rest,
            scale, window, page, ppb, C, hd, int4):
    # Quantized calls carry two extra scale operands between the pool
    # refs and the output ref; scratch trails.
    if len(rest) == 9:
        ks_ref, vs_ref, o_ref, acc, m, l, kbuf, vbuf, sems = rest
    else:
        ks_ref = vs_ref = None
        o_ref, acc, m, l, kbuf, vbuf, sems = rest
    b = pl.program_id(0)
    kh = pl.program_id(1)
    T = ppb * page
    pos = pos_ref[b]
    nv = nv_ref[b]
    lim = pos + nv  # first absolute position past this row's tokens
    n_blk = jnp.where(nv > 0, pl.cdiv(lim, T), 0)
    first = 0
    if window is not None:  # the first block the window reaches
        first = jnp.minimum(jnp.maximum(pos - window + 1, 0) // T, n_blk)

    m[...] = jnp.full_like(m, NEG_INF)
    l[...] = jnp.zeros_like(l)
    acc[...] = jnp.zeros_like(acc)

    def pages(i, slot):
        """Each page of block i: whether it holds tokens of this row, and
        its K and V copies (source, buffer, semaphore) into ``slot``."""
        for j in range(ppb):
            p = i * ppb + j
            pid = pt_ref[b, p]
            yield ((pid >= 0) & (p * page < lim),
                   [(kp_ref.at[pid, kh], kbuf.at[slot, j], sems.at[0, slot]),
                    (vp_ref.at[pid, kh], vbuf.at[slot, j], sems.at[1, slot])])

    def mapped(i):
        """(1, T): whether each key of block i lies on a mapped page."""
        cols = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
        out = cols >= 0
        for j in range(ppb):
            hole = (pt_ref[b, i * ppb + j] < 0) & (j * page <= cols) & (
                cols < (j + 1) * page)
            out &= jnp.logical_not(hole)
        return out

    def fetch(i, slot):
        for used, cps in pages(i, slot):
            @pl.when(used)
            def _copy():
                for cp in cps:
                    pltpu.make_async_copy(*cp).start()

            # Masked keys still meet the P.V product: keep them finite.
            @pl.when(jnp.logical_not(used))
            def _zero():
                for _, dst, _ in cps:
                    dst[...] = jnp.zeros(dst.shape, dst.dtype)

    def wait(i, slot):
        for used, cps in pages(i, slot):
            @pl.when(used)
            def _wait():
                for cp in cps:
                    pltpu.make_async_copy(*cp).wait()

    @pl.when(first < n_blk)
    def _prime():
        fetch(first, first % 2)

    def body(i, carry):
        slot = i % 2

        @pl.when(i + 1 < n_blk)
        def _next():
            fetch(i + 1, 1 - slot)

        wait(i, slot)
        qb = q_ref[0, 0]                                    # (G*C, D)
        D = qb.shape[-1]
        kraw = kbuf[slot][..., :hd]                         # (ppb, page, hd)
        vraw = vbuf[slot][..., :hd]
        if int4:
            kraw = quant.unpack_int4(kraw)                  # (ppb, page, D)
            vraw = quant.unpack_int4(vraw)
        cdt = jnp.promote_types(qb.dtype, kraw.dtype)
        kb = kraw.astype(cdt).reshape(T, D)
        vb = vraw.astype(jnp.float32).reshape(T, D)
        s = jax.lax.dot_general(
            qb.astype(cdt), kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                           # (G*C, T)
        if ks_ref is not None:
            s = s * ks_ref[0, 0, pl.ds(i, 1), :]            # (1, T)
        GC = s.shape[0]
        rows = jax.lax.broadcasted_iota(jnp.int32, (GC, T), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (GC, T), 1)
        qpos = pos + rows % C
        kpos = i * T + cols
        mask = (kpos < lim) & (kpos <= qpos) & mapped(i)
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m[...]                                     # (G*C, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        pexp = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l[...] = l[...] * corr + pexp.sum(axis=-1, keepdims=True)
        if vs_ref is not None:
            pexp = pexp * vs_ref[0, 0, pl.ds(i, 1), :]
        acc[...] = acc[...] * corr + jax.lax.dot_general(
            pexp, vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m[...] = m_new
        return carry

    jax.lax.fori_loop(first, n_blk, body, 0)
    o_ref[0, 0] = (acc[...] / jnp.maximum(l[...], 1e-30)).astype(o_ref.dtype)


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
    ppb = max(1, min(n_pages, BLOCK_TOKENS // page))  # pages per block
    n_blocks = -(-n_pages // ppb)
    scale = scale if scale is not None else D ** -0.5

    # Pad the table to whole blocks of unmapped pages; keep ids in range.
    pt = jnp.pad(jnp.clip(jnp.asarray(page_table, jnp.int32), -1, P - 1),
                 ((0, 0), (0, n_blocks * ppb - n_pages)), constant_values=-1)
    posv = jnp.asarray(pos, jnp.int32).reshape(B)
    nv = jnp.asarray(n_valid, jnp.int32).reshape(B)

    # (B, C, K, G, D) -> (B, K, G*C, D): a KV head's query group is one
    # block, row g * C + c.
    qg = jnp.transpose(q.reshape(B, C, K, G, D), (0, 2, 3, 1, 4)).reshape(
        B, K, G * C, D)
    q_spec = pl.BlockSpec((1, 1, G * C, D), lambda b, k, *refs: (b, k, 0, 0))
    # Mosaic slices an HBM array only along lane-dense trailing dims, so
    # a narrower page (int4-packed, head_dim 64) is padded to the lanes.
    hd_pad = -(-hd // LANES) * LANES
    if hd_pad != hd:
        pad = ((0, 0),) * 3 + ((0, hd_pad - hd),)
        kp, vp = jnp.pad(kp, pad), jnp.pad(vp, pad)
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    in_specs = [q_spec, hbm, hbm]
    operands = [qg, kp, vp]
    if quantized:
        # Each row's per-key scales in key order, one (1, T) row a block:
        # a page's scales are too narrow to DMA on their own.
        def scale_rows(sc):
            g = sc.astype(jnp.float32)[jnp.maximum(pt, 0)]  # (B, n, K, page)
            return jnp.swapaxes(g, 1, 2).reshape(B, K, n_blocks, ppb * page)
        sc_spec = pl.BlockSpec((1, 1, n_blocks, ppb * page),
                               lambda b, k, *refs: (b, k, 0, 0))
        in_specs += [sc_spec, sc_spec]
        operands += [scale_rows(kp_scale), scale_rows(vp_scale)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, K),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((G * C, D), jnp.float32),
            pltpu.VMEM((G * C, 1), jnp.float32),
            pltpu.VMEM((G * C, 1), jnp.float32),
            pltpu.VMEM((2, ppb, page, hd_pad), kp.dtype),
            pltpu.VMEM((2, ppb, page, hd_pad), vp.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    kernel = functools.partial(
        _kernel, scale=scale, window=window, page=page, ppb=ppb, C=C,
        hd=hd, int4=int4,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G * C, D), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(pt, posv, nv, *operands)
    return jnp.transpose(out.reshape(B, K, G, C, D), (0, 3, 1, 2, 4)).reshape(
        B, C, H, D)
