"""The paged KV pool on the device, and the one home of its format: its
layout, its insert, the layer view the paged kernel reads, and the page
copy and permutation the host allocator (``serve.cache.PagePool``) asks
for. A pool is a dict of leaves stacked over the model's layers, as the
chunk program carries it through its layer loop:

  * ``kp``/``vp``: ``(n_layers, n_pages + 1, K, page // t, t * hd)``.
    Pages are head-major: one KV head's page is ``page`` tokens of
    ``hd`` values, ``t`` consecutive tokens to a row
    (``tokens_per_row``), so the page read as ``(page, hd)`` is the
    block the paged kernel takes. The trailing page (index ``n_pages``)
    is the *trash* page that absorbs masked writes, so the insert's
    scatter needs no conditional. Validity is carried by the page table
    (-1 unmapped) and the rows' lengths, not by the pool.
  * bf16 (the compute dtype) pools store ``hd = head_dim``; int8 pools
    ``hd = head_dim`` int8 codes; int4 pools pack two dims per byte,
    ``hd = head_dim // 2`` (``kernels/quant.py``'s halves layout).
  * ``kp_scale``/``vp_scale`` (int8 and int4 only):
    ``(n_layers, n_pages + 1, K, page)`` float32, one scale per token
    and KV head.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels import quant


def tokens_per_row(page: int, hd: int) -> int:
    """Tokens of a page that share one pool row of ``hd``-wide tokens.

    A TPU lays an array out in rows of 128 lanes. A pool whose rows are
    narrower (head_dim 64, or an int4-packed 128) would be laid out with
    its page axis minor, so every access by page, the insert's and the
    kernel's, would relayout the whole pool. Rows of ``t`` tokens span
    whole lanes where the page size allows (``t * hd`` a multiple of
    128) and keep the standard layout; ``t`` is 1 for a pool 128 lanes
    wide or wider.
    """
    from repro.kernels.paged_attention import LANES

    return math.gcd(page, LANES // math.gcd(hd, LANES))


def init(n_layers: int, n_pages: int, page: int, n_kv_heads: int,
         head_dim: int, kv_dtype: str, dtype) -> dict:
    """A zeroed pool of ``n_pages`` pages of ``page`` tokens (plus the
    trash page) for each of ``n_layers`` layers. ``kv_dtype`` is
    ``"int8"``, ``"int4"`` or anything else for a pool in ``dtype``,
    the compute dtype."""
    quantized = kv_dtype in ("int8", "int4")
    hd = head_dim
    if kv_dtype == "int4":
        if head_dim % 2:
            raise ValueError(
                f"int4 KV packs two dims per byte; head_dim {head_dim} is "
                "odd")
        hd = head_dim // 2
    t = tokens_per_row(page, hd)
    lead = (n_layers, n_pages + 1, n_kv_heads)
    dt = jnp.int8 if quantized else jnp.dtype(dtype)
    pool = {"kp": jnp.zeros(lead + (page // t, t * hd), dt),
            "vp": jnp.zeros(lead + (page // t, t * hd), dt)}
    if quantized:
        pool["kp_scale"] = jnp.zeros(lead + (page,), jnp.float32)
        pool["vp_scale"] = jnp.zeros(lead + (page,), jnp.float32)
    return pool


def geometry(pool, D: int):
    """``(page, hd, t, quantized)`` of a pool read by ``D``-wide queries:
    the page size, the stored width of a token, the tokens to a row and
    ``""``, ``"int8"`` or ``"int4"``. A quantized pool's scales give the
    page; a bf16 pool's tokens are the queries' D wide."""
    kp, ks = pool["kp"], pool.get("kp_scale")
    rows = kp.shape[-2] * kp.shape[-1]
    if ks is None:
        page, hd, kind = rows // D, D, ""
    else:
        page = ks.shape[-1]
        hd = rows // page
        kind = "int4" if hd != D else "int8"
    return page, hd, page // kp.shape[-2], kind


def insert(pool, k_new, v_new, page_table, pos, n_valid, layer):
    """Scatter C new tokens' K/V into their rows' pages of layer ``layer``.

    k_new/v_new: (B, C, K, head_dim). page_table: (B, max_pages) int32
    physical page ids (-1 unmapped). pos: (B,) absolute position of each
    row's first token this step; token i of row b lands at logical
    position ``pos[b] + i``, i.e. page ``(pos+i) // page``, offset
    ``(pos+i) % page`` within the row's mapped physical page. Tokens at i >= n_valid[b] (and any
    position whose page is unmapped) are routed to the trash page. The
    engine guarantees every valid position's page is mapped before the
    step runs. Quantized pools take the tokens through the quantizer.

    One scatter per leaf into the stack at ``(layer, phys, k, ...)``: a
    token's values (or its scale) for one KV head. It updates the stack
    in place, in the layout the stack has: no relayout, and no slice of
    the layer's pool out of the stack. Its operations carry the name
    scope ``kv_insert`` in the compiled program.
    """
    with jax.named_scope("kv_insert"):
        return _insert(pool, k_new, v_new, page_table, pos, n_valid, layer)


def _insert(pool, k_new, v_new, page_table, pos, n_valid, layer):
    B, C, _, hd = k_new.shape
    P1, K = pool["kp"].shape[1:3]
    page, _, _, kind = geometry(pool, hd)
    npg = page_table.shape[1]
    logical = (jnp.asarray(pos, jnp.int32).reshape(B, 1)
               + jnp.arange(C, dtype=jnp.int32)[None, :])      # (B, C)
    pg, off = logical // page, logical % page
    phys = jnp.take_along_axis(
        jnp.asarray(page_table, jnp.int32), jnp.clip(pg, 0, npg - 1), axis=1)
    ok = (jnp.arange(C, dtype=jnp.int32)[None, :]
          < jnp.asarray(n_valid, jnp.int32).reshape(B, 1))
    ok &= (phys >= 0) & (pg < npg)
    phys = jnp.where(ok, phys, P1 - 1).reshape(B * C, 1)        # trash page
    off = off.reshape(B * C, 1)
    kh = jnp.arange(K, dtype=jnp.int32)[None, :]
    lyr = jnp.asarray(layer, jnp.int32)

    def put(leaf, new):  # new (B * C, K, ...)
        new = new.astype(leaf.dtype)
        t = page // leaf.shape[3]  # tokens to a row; 1 for the scales
        if t == 1:
            return leaf.at[lyr, phys, kh, off].set(new)
        # the token's lanes of its row
        w = new.shape[-1]
        lanes = (off % t * w)[..., None] + jnp.arange(w, dtype=jnp.int32)
        return leaf.at[lyr, phys[..., None], kh[..., None],
                       (off // t)[..., None], lanes].set(new)

    out = dict(pool)
    if kind:
        qz = quant.quantize_int4 if kind == "int4" else quant.quantize_int8
        kq, ks = qz(k_new.reshape(B * C, K, hd))
        vq, vs = qz(v_new.reshape(B * C, K, hd))
        out["kp"] = put(pool["kp"], kq)
        out["vp"] = put(pool["vp"], vq)
        out["kp_scale"] = put(pool["kp_scale"], ks)
        out["vp_scale"] = put(pool["vp_scale"], vs)
    else:
        quant.check_insert_dtype(pool["kp"].dtype, k_new.dtype,
                                 "paged_pool.insert")
        out["kp"] = put(pool["kp"], k_new.reshape(B * C, K, hd))
        out["vp"] = put(pool["vp"], v_new.reshape(B * C, K, hd))
    return out


def layer_view(pool, page_table, layer, D: int, *, kernel: bool,
               sharded: bool):
    """Layer ``layer`` of a pool as one ``(pages, K, page, hd)`` pool, its
    scales (None for bf16) and its page table: ``(kp, vp, kp_scale,
    vp_scale, page_table)``, the operands of the paged kernel and of its
    jnp implementation.

    The stack is read as one pool of ``n_layers * (n_pages + 1)`` pages,
    the layer's page ids offset by ``layer * (n_pages + 1)``: merging the
    leading dims is a bitcast, so nothing is copied (a pool the kernel
    reads has one token a row; the jnp path's rows of ``t`` tokens are
    reshaped). Two kernel calls take the layer's own pool instead,
    sliced out (one layer's copy), because the kernel copies what it is
    handed: a pool narrower than its lanes, which it pads, and a call
    under a mesh (``sharded``), where each device takes its heads' share
    of the operand.
    """
    from repro.kernels.paged_attention import LANES

    page, hd, _, _ = geometry(pool, D)
    sliced = kernel and (hd % LANES or sharded)
    lyr = jnp.asarray(layer, jnp.int32)

    def one(a, kv):
        if a is None:
            return None
        a = (jax.lax.dynamic_index_in_dim(a, lyr, keepdims=False) if sliced
             else a.reshape((-1,) + a.shape[2:]))
        # (..., page // t, t * hd) -> (..., page, hd); scales are per token
        return a.reshape(a.shape[:-2] + (page, hd)) if kv else a

    leaves = (pool["kp"], pool["vp"], pool.get("kp_scale"),
              pool.get("vp_scale"))
    pt = jnp.asarray(page_table, jnp.int32)
    if not sliced:
        pt = jnp.where(pt >= 0, pt + lyr * pool["kp"].shape[1], -1)
    return tuple(one(a, i < 2) for i, a in enumerate(leaves)) + (pt,)


def copy_pages(pool, src, dst):
    """Pages ``src[i]`` copied over pages ``dst[i]`` in every layer and
    every leaf (the device half of ``PagePool.cow``)."""
    s = jnp.asarray(src, jnp.int32)
    d = jnp.asarray(dst, jnp.int32)
    return {k: v.at[:, d].set(v[:, s]) for k, v in pool.items()}


def permute(pool, perm):
    """The pool in the page order ``perm`` (``new[:, i] = old[:, perm[i]]``
    in every leaf; ``PagePool.defrag`` gives ``perm``)."""
    p = jnp.asarray(perm, jnp.int32)
    return {k: jnp.take(v, p, axis=1) for k, v in pool.items()}


def _is_pool(node) -> bool:
    return isinstance(node, dict) and "kp" in node


def map_pools(cache, fn):
    """``cache`` with ``fn`` applied to every pool in it; any other entry
    of a model's cache tree (a dense slab, a recurrent state) is kept."""
    return jax.tree_util.tree_map(
        lambda n: fn(n) if _is_pool(n) else n, cache, is_leaf=_is_pool)
