"""Model layer library: norms, RoPE/M-RoPE, GQA attention (full/SWA/decode),
dense & MoE FFN, Mamba, RWKV-6, with logical-axis sharding tags.

All parameters are created in fp32 and tagged via ``repro.dist.p`` with
logical axis names; compute casts to the config dtype (bf16) while norms,
softmax and the SSM recurrences run in fp32 (paper C7 mixed precision).
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import MambaConfig, ModelConfig, RWKV6Config
from repro.dist import constrain, p
from repro.kernels import ops, quant


def _cdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


# --------------------------------------------------------------------------- #
# Norms (fp32 math).
# --------------------------------------------------------------------------- #
def init_norm(cfg: ModelConfig, d: int):
    if cfg.norm == "layernorm":
        return {"scale": p(jnp.ones((d,), jnp.float32), None),
                "bias": p(jnp.zeros((d,), jnp.float32), None)}
    return {"scale": p(jnp.ones((d,), jnp.float32), None)}


def apply_norm(params, x, cfg: ModelConfig, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    if "bias" in params:
        mu = x32.mean(-1, keepdims=True)
        var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
        y = (x32 - mu) * jax.lax.rsqrt(var + eps)
        y = y * params["scale"] + params["bias"]
    else:
        var = (x32 ** 2).mean(-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + eps) * params["scale"]
    return y.astype(x.dtype)


# --------------------------------------------------------------------------- #
# Rotary embeddings (standard + multimodal M-RoPE).
# --------------------------------------------------------------------------- #
def _rope_angles(positions, half: int, theta: float, mrope: bool):
    """positions: (B,S) or (B,S,3) -> angles (B,S,half) fp32."""
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if not mrope:
        return positions.astype(jnp.float32)[..., None] * freqs
    # M-RoPE: split the rotary half-dims into (temporal, height, width)
    # sections of proportion 1/4, 3/8, 3/8 (qwen2-vl style).
    s1 = half // 4
    s2 = (half - s1) // 2
    sec = jnp.concatenate([
        jnp.zeros((s1,), jnp.int32),
        jnp.ones((s2,), jnp.int32),
        jnp.full((half - s1 - s2,), 2, jnp.int32),
    ])
    pos = jnp.take_along_axis(
        positions.astype(jnp.float32),
        jnp.broadcast_to(sec, positions.shape[:-1] + (half,)).astype(jnp.int32),
        axis=-1,
    )  # (B,S,half) picking the right position stream per frequency
    return pos * freqs


def apply_rope(x, positions, *, theta: float, mrope: bool = False):
    """x: (B,S,H,D) -> rotated. positions: (B,S) or (B,S,3)."""
    B, S, H, D = x.shape
    half = D // 2
    ang = _rope_angles(positions, half, theta, mrope)  # (B,S,half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def default_positions(cfg: ModelConfig, B: int, S: int, offset=0):
    pos = offset + jnp.arange(S, dtype=jnp.int32)[None, :]
    pos = jnp.broadcast_to(pos, (B, S))
    if cfg.rope == "mrope":
        return jnp.broadcast_to(pos[..., None], (B, S, 3))
    return pos


# --------------------------------------------------------------------------- #
# Attention (GQA; full / sliding-window / decode-with-cache).
# --------------------------------------------------------------------------- #
def init_attention(cfg: ModelConfig, key, cross: bool = False):
    d, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    sc = d ** -0.5
    prm = {
        "wq": p(_normal(ks[0], (d, H, hd), sc), "fsdp", "heads", None),
        "wk": p(_normal(ks[1], (d, K, hd), sc), "fsdp", "kv_heads", None),
        "wv": p(_normal(ks[2], (d, K, hd), sc), "fsdp", "kv_heads", None),
        "wo": p(_normal(ks[3], (H, hd, d), (H * hd) ** -0.5),
                "heads", None, "fsdp"),
    }
    if cfg.qkv_bias:
        prm["bq"] = p(jnp.zeros((H, hd), jnp.float32), "heads", None)
        prm["bk"] = p(jnp.zeros((K, hd), jnp.float32), "kv_heads", None)
        prm["bv"] = p(jnp.zeros((K, hd), jnp.float32), "kv_heads", None)
    return prm


def _qkv(params, x, cfg: ModelConfig, which: str):
    dt = _cdtype(cfg)
    w = params["w" + which][0] if isinstance(params["w" + which], tuple) else params["w" + which]
    y = jnp.einsum("bsd,dhk->bshk", x, w.astype(dt))
    bkey = "b" + which
    if bkey in params:
        b = params[bkey][0] if isinstance(params[bkey], tuple) else params[bkey]
        y = y + b.astype(dt)
    return y


def attention_full(params, x, cfg: ModelConfig, *, positions, window=None,
                   causal=True, kv_x=None, kv_positions=None):
    """Full-sequence attention (train / prefill / encoder / cross).

    kv_x: source sequence for cross-attention (defaults to x).
    Returns (out, (k, v)) — k/v in compute dtype for cache construction.
    """
    src = x if kv_x is None else kv_x
    q = _qkv(params, x, cfg, "q")
    k = _qkv(params, src, cfg, "k")
    v = _qkv(params, src, cfg, "v")
    if cfg.rope != "none" and kv_x is None:
        mr = cfg.rope == "mrope"
        q = apply_rope(q, positions, theta=cfg.rope_theta, mrope=mr)
        k = apply_rope(k, positions, theta=cfg.rope_theta, mrope=mr)
    q = constrain(q, "batch", None, "act_heads", None)
    k = constrain(k, "batch", None, "act_heads", None)
    v = constrain(v, "batch", None, "act_heads", None)
    out = ops.attention(q, k, v, causal=causal, window=window)
    out = constrain(out, "batch", None, "act_heads", None)
    wo = params["wo"][0] if isinstance(params["wo"], tuple) else params["wo"]
    y = jnp.einsum("bshk,hkd->bsd", out, wo.astype(_cdtype(cfg)))
    return y, (k, v)


def _decode_positions(pos, B: int) -> jnp.ndarray:
    """(B,1) int32 rope positions from a scalar or per-row (B,) ``pos``."""
    return jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1, 1), (B, 1))


def gather_last(x, last_pos):
    """Per-example final-position slice of x (B,S,d) -> (B,1,d).

    last_pos None -> position S-1 for every row (ordinary prefill);
    scalar or (B,) -> that absolute position per row (serving pads
    prompts to one compile shape and reads each prompt's true end).
    """
    if last_pos is None:
        return x[:, -1:, :]
    lp = jnp.broadcast_to(
        jnp.asarray(last_pos, jnp.int32).reshape(-1), (x.shape[0],)
    )
    return jnp.take_along_axis(x, lp[:, None, None], axis=1)


def attention_decode(params, x, cfg: ModelConfig, cache: Dict[str, Any], *,
                     pos, window=None, cross=False):
    """One-token attention against the layer cache; returns (out, new_cache).

    cache keys: k, v, slot_pos (+ k_scale/v_scale when int8). For
    cross-attention the cache is static (precomputed encoder K/V).
    ``pos`` is a scalar, or a (B,) vector when each row decodes at its own
    offset (continuous batching).
    """
    B = x.shape[0]
    q = _qkv(params, x, cfg, "q")
    if cfg.rope != "none" and not cross:
        posv = _decode_positions(pos, B)
        if cfg.rope == "mrope":
            posv = jnp.broadcast_to(posv[..., None], (B, 1, 3))
        q = apply_rope(q, posv, theta=cfg.rope_theta, mrope=cfg.rope == "mrope")
    if cross:
        new_cache = cache
    else:
        k_new = _qkv(params, x, cfg, "k")
        v_new = _qkv(params, x, cfg, "v")
        if cfg.rope != "none":
            posv = _decode_positions(pos, B)
            if cfg.rope == "mrope":
                posv = jnp.broadcast_to(posv[..., None], (B, 1, 3))
            k_new = apply_rope(
                k_new, posv, theta=cfg.rope_theta, mrope=cfg.rope == "mrope"
            )
        new_cache = cache_insert(cache, k_new[:, 0], v_new[:, 0], pos)
    out = ops.decode_attention(
        q,
        new_cache["k"],
        new_cache["v"],
        new_cache["slot_pos"],
        pos=pos,
        window=window,
        k_scale=new_cache.get("k_scale"),
        v_scale=new_cache.get("v_scale"),
    )
    wo = params["wo"][0] if isinstance(params["wo"], tuple) else params["wo"]
    y = jnp.einsum("bshk,hkd->bsd", out, wo.astype(_cdtype(cfg)))
    return y, new_cache


# ---- KV cache ------------------------------------------------------------- #
def init_kv_cache(cfg: ModelConfig, B: int, length: int) -> Dict[str, Any]:
    K, hd = cfg.n_kv_heads, cfg.head_dim
    if cfg.kv_cache_dtype == "int4":
        raise ValueError(
            "int4 KV is only supported by the paged layout "
            "(kv_cache_dtype='int4' with a slab cache)")
    int8 = cfg.kv_cache_dtype == "int8"
    dt = jnp.int8 if int8 else _cdtype(cfg)
    cache = {
        "k": jnp.zeros((B, length, K, hd), dt),
        "v": jnp.zeros((B, length, K, hd), dt),
        "slot_pos": jnp.full((B, length), -1, jnp.int32),
    }
    if int8:
        cache["k_scale"] = jnp.zeros((B, length, K), jnp.float32)
        cache["v_scale"] = jnp.zeros((B, length, K), jnp.float32)
    return cache


# Per-(row, K-head) symmetric int8 quantization over the head dim;
# shared with the kernels/tests via kernels.quant.
_quantize_kv = quant.quantize_int8


def _check_insert_dtype(pool_dtype, new_dtype, where: str) -> None:
    """Writes into an integer pool must come through the quantizer.

    Without this, the fallback ``astype(pool.dtype)`` would silently
    truncate float K/V into an int8/int4 pool whose scale entries are
    missing — garbage attention, no error. Dtypes are static, so this
    raises at trace time, not mid-step.
    """
    if (jnp.issubdtype(pool_dtype, jnp.integer)
            and not jnp.issubdtype(new_dtype, jnp.integer)):
        raise TypeError(
            f"{where}: writing {new_dtype} values into a {pool_dtype} pool "
            "without quantization scales — quantized caches must carry "
            "k_scale/v_scale (slab) or kp_scale/vp_scale (paged) entries")


def cache_insert(cache, k_new, v_new, pos):
    """Insert one token's K/V at ring slot pos % L. k_new/v_new: (B,K,hd).

    ``pos`` may be a (B,) vector (per-row positions, continuous batching):
    each row then writes its own ring slot via a one-hot select instead of
    a single dynamic_update_slice.
    """
    L = cache["k"].shape[1]
    posv = jnp.asarray(pos, jnp.int32)
    if posv.ndim:
        return _cache_insert_per_row(cache, k_new, v_new, posv)
    slot = posv % L
    out = dict(cache)
    if "k_scale" in cache:
        kq, ks = _quantize_kv(k_new)
        vq, vs = _quantize_kv(v_new)
        out["k"] = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], kq[:, None], slot, axis=1)
        out["v"] = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], vq[:, None], slot, axis=1)
        out["k_scale"] = jax.lax.dynamic_update_slice_in_dim(
            cache["k_scale"], ks[:, None], slot, axis=1)
        out["v_scale"] = jax.lax.dynamic_update_slice_in_dim(
            cache["v_scale"], vs[:, None], slot, axis=1)
    else:
        _check_insert_dtype(cache["k"].dtype, k_new.dtype, "cache_insert")
        out["k"] = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k_new[:, None].astype(cache["k"].dtype), slot, axis=1)
        out["v"] = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v_new[:, None].astype(cache["v"].dtype), slot, axis=1)
    out["slot_pos"] = jax.lax.dynamic_update_slice_in_dim(
        cache["slot_pos"],
        jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (cache["k"].shape[0], 1)),
        slot, axis=1)
    return out


def _cache_insert_per_row(cache, k_new, v_new, posv):
    """cache_insert with per-row positions posv: (B,) int32."""
    L = cache["k"].shape[1]
    hit = jnp.arange(L, dtype=jnp.int32)[None, :] == (posv % L)[:, None]  # B,L

    def put(arr, new):  # arr (B,L,...), new (B,...)
        m = hit.reshape(hit.shape + (1,) * (arr.ndim - 2))
        return jnp.where(m, new[:, None].astype(arr.dtype), arr)

    out = dict(cache)
    if "k_scale" in cache:
        kq, ks = _quantize_kv(k_new)
        vq, vs = _quantize_kv(v_new)
        out["k"], out["v"] = put(cache["k"], kq), put(cache["v"], vq)
        out["k_scale"] = put(cache["k_scale"], ks)
        out["v_scale"] = put(cache["v_scale"], vs)
    else:
        _check_insert_dtype(cache["k"].dtype, k_new.dtype, "cache_insert")
        out["k"], out["v"] = put(cache["k"], k_new), put(cache["v"], v_new)
    out["slot_pos"] = jnp.where(hit, posv[:, None], cache["slot_pos"])
    return out


# ---- paged KV cache (serving; see repro.serve.cache.PagePool) ------------- #
def init_paged_kv_cache(cfg: ModelConfig, n_pages: int, page: int):
    """Physical page pool for one attention layer.

    ``n_pages`` real pages plus one trailing *trash* page (index
    ``n_pages``) that absorbs masked writes — ``paged_cache_insert``
    routes invalid token positions there so the scatter needs no
    conditional. Validity is carried by the page table (-1 = unmapped)
    plus per-row lengths, not by a per-slot ``slot_pos`` map.

    Pages are head-major, ``(n_pages + 1, K, page, hd)`` (scales
    ``(n_pages + 1, K, page)``), so one KV head's page is a
    ``(page, hd)`` tile — the block the paged kernel DMAs.
    """
    K, hd = cfg.n_kv_heads, cfg.head_dim
    quantized = cfg.kv_cache_dtype in ("int8", "int4")
    store_hd = hd
    if cfg.kv_cache_dtype == "int4":
        if hd % 2:
            raise ValueError(
                f"int4 KV packs two dims per byte; head_dim {hd} is odd")
        store_hd = hd // 2  # two nibbles per byte (kernels.quant layout)
    dt = jnp.int8 if quantized else _cdtype(cfg)
    cache = {
        "kp": jnp.zeros((n_pages + 1, K, page, store_hd), dt),
        "vp": jnp.zeros((n_pages + 1, K, page, store_hd), dt),
    }
    if quantized:
        cache["kp_scale"] = jnp.zeros((n_pages + 1, K, page), jnp.float32)
        cache["vp_scale"] = jnp.zeros((n_pages + 1, K, page), jnp.float32)
    return cache


def paged_cache_insert(cache, k_new, v_new, page_table, pos, n_valid):
    """Scatter C new tokens' K/V into their rows' pages.

    k_new/v_new: (B, C, K, hd). page_table: (B, max_pages) int32 physical
    page ids (-1 unmapped). pos: (B,) absolute position of each row's
    first token this step; token i of row b lands at logical position
    ``pos[b] + i``, i.e. page ``(pos+i) // page``, offset ``(pos+i) %
    page`` within the row's mapped physical page. Tokens at i >=
    n_valid[b] (and any position whose page is unmapped) are routed to
    the trash page. The engine guarantees every valid position's page is
    mapped before the step runs.
    """
    P1, _, page = cache["kp"].shape[:3]
    B, C, K, hd = k_new.shape
    npg = page_table.shape[1]
    logical = (jnp.asarray(pos, jnp.int32).reshape(B, 1)
               + jnp.arange(C, dtype=jnp.int32)[None, :])      # (B, C)
    pg, off = logical // page, logical % page
    phys = jnp.take_along_axis(
        jnp.asarray(page_table, jnp.int32), jnp.clip(pg, 0, npg - 1), axis=1)
    ok = (jnp.arange(C, dtype=jnp.int32)[None, :]
          < jnp.asarray(n_valid, jnp.int32).reshape(B, 1))
    ok &= (phys >= 0) & (pg < npg)
    row = jnp.where(ok, phys, P1 - 1).reshape(B * C)          # trash page
    off = off.reshape(B * C)

    def put(pool, new):  # pool (P1, K, page, ...), new (B, C, K, ...)
        return pool.at[row, :, off].set(
            new.reshape((B * C,) + new.shape[2:]).astype(pool.dtype))

    out = dict(cache)
    if "kp_scale" in cache:
        store_hd = cache["kp"].shape[-1]
        qz = quant.quantize_int4 if store_hd != hd else quant.quantize_int8
        kq, ks = qz(k_new.reshape(B * C, K, hd))
        vq, vs = qz(v_new.reshape(B * C, K, hd))
        out["kp"] = put(cache["kp"], kq.reshape(B, C, K, store_hd))
        out["vp"] = put(cache["vp"], vq.reshape(B, C, K, store_hd))
        out["kp_scale"] = put(cache["kp_scale"], ks.reshape(B, C, K))
        out["vp_scale"] = put(cache["vp_scale"], vs.reshape(B, C, K))
    else:
        _check_insert_dtype(cache["kp"].dtype, k_new.dtype,
                            "paged_cache_insert")
        out["kp"] = put(cache["kp"], k_new)
        out["vp"] = put(cache["vp"], v_new)
    return out


def paged_copy_pages(cache, src, dst):
    """Copy-on-write content copy: pool pages ``src[i] -> dst[i]``.

    ``cache`` is one paged-attention pool dict (``kp``/``vp`` + optional
    int8 scales), either per-layer ``(n_pages+1, K, page, ...)`` or
    stacked ``(n_blocks, n_pages+1, K, page, ...)``. The copy runs
    before the owning slot's next ``paged_cache_insert`` writes into
    ``dst``, so a shared source page is never mutated.
    """
    s = jnp.asarray(src, jnp.int32)
    d = jnp.asarray(dst, jnp.int32)
    if cache["kp"].ndim == 5:  # n_blocks-stacked: page axis 1
        return {k: v.at[:, d].set(v[:, s]) for k, v in cache.items()}
    return {k: v.at[d].set(v[s]) for k, v in cache.items()}


def attention_decode_paged(params, x, cfg: ModelConfig, cache, page_table,
                           pos, n_valid, *, window=None):
    """C-token attention against the paged pool; returns (out, new_cache).

    x: (B, C, d) — the chunk program's mixed batch: decode rows feed one
    real token, chunked-prefill rows up to C (``n_valid`` masks the
    rest). The new K/V are scattered into the rows' pages first, then
    every query attends causally over exactly its row's occupied pages
    (``ops.paged_attention``). Positions beyond ``n_valid`` produce
    garbage the caller masks at the logit gather.
    """
    B, C, _ = x.shape
    q = _qkv(params, x, cfg, "q")
    k_new = _qkv(params, x, cfg, "k")
    v_new = _qkv(params, x, cfg, "v")
    if cfg.rope != "none":
        posm = (jnp.asarray(pos, jnp.int32).reshape(B, 1)
                + jnp.arange(C, dtype=jnp.int32)[None, :])
        mr = cfg.rope == "mrope"
        if mr:
            posm = jnp.broadcast_to(posm[..., None], (B, C, 3))
        q = apply_rope(q, posm, theta=cfg.rope_theta, mrope=mr)
        k_new = apply_rope(k_new, posm, theta=cfg.rope_theta, mrope=mr)
    new_cache = paged_cache_insert(
        cache, k_new, v_new, page_table, pos, n_valid)
    out = ops.paged_attention(
        q, new_cache["kp"], new_cache["vp"], page_table,
        pos=pos, n_valid=n_valid, window=window,
        kp_scale=new_cache.get("kp_scale"),
        vp_scale=new_cache.get("vp_scale"),
    )
    wo = params["wo"][0] if isinstance(params["wo"], tuple) else params["wo"]
    y = jnp.einsum("bshk,hkd->bsd", out, wo.astype(_cdtype(cfg)))
    return y, new_cache


def attention_cross_chunk(params, x, cfg: ModelConfig, cache):
    """C-query cross-attention against a static (encoder) KV cache.

    x: (B, C, d); cache: dense {"k","v","slot_pos"} of the encoder K/V
    (non-causal; slots with ``slot_pos`` -1 masked). The chunk-program
    counterpart of ``attention_decode`` with ``cross=True``.
    """
    B, C, _ = x.shape
    q = _qkv(params, x, cfg, "q")  # (B, C, H, hd)
    H, D = q.shape[2], q.shape[3]
    K = cache["k"].shape[2]
    G = H // K
    kf = cache["k"].astype(jnp.float32)
    vf = cache["v"].astype(jnp.float32)
    if "k_scale" in cache:
        kf = kf * cache["k_scale"][..., None].astype(jnp.float32)
        vf = vf * cache["v_scale"][..., None].astype(jnp.float32)
    qf = (q.astype(jnp.float32) * D ** -0.5).reshape(B, C, K, G, D)
    logits = jnp.einsum("bckgd,bskd->bckgs", qf, kf)
    valid = cache["slot_pos"] >= 0  # (B, S)
    logits = jnp.where(valid[:, None, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bckgs,bskd->bckgd", probs, vf).reshape(B, C, H, D)
    wo = params["wo"][0] if isinstance(params["wo"], tuple) else params["wo"]
    return jnp.einsum("bshk,hkd->bsd", out.astype(_cdtype(cfg)),
                      wo.astype(_cdtype(cfg)))


def cache_from_prefill(cfg: ModelConfig, k, v, length: int):
    """Build a decode cache from prefill K/V (B,S,K,hd); S <= length."""
    B, S = k.shape[0], k.shape[1]
    cache = init_kv_cache(cfg, B, length)
    if "k_scale" in cache:
        kq, ks = jax.vmap(_quantize_kv, in_axes=1, out_axes=1)(k)
        vq, vs = jax.vmap(_quantize_kv, in_axes=1, out_axes=1)(v)
        cache["k"] = jax.lax.dynamic_update_slice_in_dim(cache["k"], kq, 0, 1)
        cache["v"] = jax.lax.dynamic_update_slice_in_dim(cache["v"], vq, 0, 1)
        cache["k_scale"] = jax.lax.dynamic_update_slice_in_dim(
            cache["k_scale"], ks, 0, 1)
        cache["v_scale"] = jax.lax.dynamic_update_slice_in_dim(
            cache["v_scale"], vs, 0, 1)
    else:
        cache["k"] = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), 0, 1)
        cache["v"] = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), 0, 1)
    cache["slot_pos"] = jax.lax.dynamic_update_slice_in_dim(
        cache["slot_pos"],
        jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S)),
        0, 1)
    return cache


# --------------------------------------------------------------------------- #
# Dense FFN.
# --------------------------------------------------------------------------- #
def _act(name: str):
    return {
        "silu": jax.nn.silu,
        "gelu": jax.nn.gelu,
        "relu": jax.nn.relu,
        "relu2": lambda x: jnp.square(jax.nn.relu(x)),
    }[name]


def init_ffn(cfg: ModelConfig, key):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    prm = {
        "wu": p(_normal(ks[0], (d, f), d ** -0.5), "fsdp", "mlp"),
        "wd": p(_normal(ks[1], (f, d), f ** -0.5), "mlp", "fsdp"),
    }
    if cfg.glu:
        prm["wg"] = p(_normal(ks[2], (d, f), d ** -0.5), "fsdp", "mlp")
    return prm


def apply_ffn(params, x, cfg: ModelConfig):
    dt = _cdtype(cfg)
    get = lambda n: (params[n][0] if isinstance(params[n], tuple) else params[n]).astype(dt)
    h = jnp.einsum("bsd,df->bsf", x, get("wu"))
    if cfg.glu:
        g = jnp.einsum("bsd,df->bsf", x, get("wg"))
        h = _act(cfg.activation)(g) * h
    else:
        h = _act(cfg.activation)(h)
    h = constrain(h, "batch", None, "act_mlp")
    return jnp.einsum("bsf,fd->bsd", h, get("wd"))


# --------------------------------------------------------------------------- #
# Mixture-of-Experts FFN (GShard-style capacity dispatch, expert-parallel).
# --------------------------------------------------------------------------- #
MOE_GROUP = 256  # tokens per dispatch group


def init_moe(cfg: ModelConfig, key):
    d, f, E = cfg.d_model, cfg.d_ff, cfg.moe.n_experts
    ks = jax.random.split(key, 4)
    # §Perf hillclimb B2: when the expert dim is model-axis-sharded (E
    # divides the production model axis of 16), wd's data shard goes on the
    # CONTRACTION dim f — the expert einsum partial-sums + psums instead of
    # all-gathering the whole expert matrix. For E < 16 (mixtral/grok 8e)
    # the "expert" tag drops, f takes the model axis to match the wu output
    # sharding, and d takes data (measured regression otherwise; see
    # EXPERIMENTS.md §Perf B2-regress).
    wd_axes = (
        ("expert", "fsdp", "mlp") if E % 16 == 0
        else ("expert", "mlp", "fsdp")
    )
    prm = {
        "router": p(_normal(ks[0], (d, E), d ** -0.5), None, None),
        "wu": p(_normal(ks[1], (E, d, f), d ** -0.5), "expert", "fsdp", "mlp"),
        "wd": p(_normal(ks[2], (E, f, d), f ** -0.5), *wd_axes),
    }
    if cfg.glu:
        prm["wg"] = p(_normal(ks[3], (E, d, f), d ** -0.5),
                      "expert", "fsdp", "mlp")
    return prm


def apply_moe(params, x, cfg: ModelConfig):
    """x: (B,S,d) -> (y, aux_loss). Tokens grouped; experts sharded ('expert'
    -> model axis) so the dispatch einsums lower to all-to-all style
    collectives under GSPMD."""
    dt = _cdtype(cfg)
    B, S, d = x.shape
    E, k = cfg.moe.n_experts, cfg.moe.top_k
    get = lambda n: params[n][0] if isinstance(params[n], tuple) else params[n]

    Sg = min(MOE_GROUP, S)
    n_groups = (B * S) // Sg
    xg = x.reshape(n_groups, Sg, d)
    cap = max(1, int(math.ceil(Sg * k * cfg.moe.capacity_factor / E)))
    dispatch, combine, aux = ops.moe_gating(
        xg, get("router"), top_k=k, capacity=cap
    )
    dispatch = constrain(dispatch.astype(dt), "batch", None, "act_expert", None)
    combine = constrain(combine.astype(jnp.float32), "batch", None,
                        "act_expert", None)
    xin = jnp.einsum("gsec,gsd->egcd", dispatch, xg)
    xin = constrain(xin, "act_expert", "batch", None, None)
    h = jnp.einsum("egcd,edf->egcf", xin, get("wu").astype(dt))
    if cfg.glu:
        g = jnp.einsum("egcd,edf->egcf", xin, get("wg").astype(dt))
        h = _act(cfg.activation)(g) * h
    else:
        h = _act(cfg.activation)(h)
    out = jnp.einsum("egcf,efd->egcd", h, get("wd").astype(dt))
    out = constrain(out, "act_expert", "batch", None, None)
    y = jnp.einsum("gsec,egcd->gsd", combine.astype(jnp.float32),
                   out.astype(jnp.float32))
    return y.reshape(B, S, d).astype(x.dtype), aux


# --------------------------------------------------------------------------- #
# Mamba (S6 selective scan) mixer.
# --------------------------------------------------------------------------- #
def _mamba_dims(cfg: ModelConfig):
    m = cfg.mamba or MambaConfig()
    di = m.expand * cfg.d_model
    dt_rank = m.dt_rank or -(-cfg.d_model // 16)
    return m, di, dt_rank


def init_mamba(cfg: ModelConfig, key):
    m, di, R = _mamba_dims(cfg)
    d, N = cfg.d_model, m.d_state
    ks = jax.random.split(key, 7)
    A = jnp.tile(jnp.arange(1, N + 1, dtype=jnp.float32)[None], (di, 1))
    return {
        "wx": p(_normal(ks[0], (d, di), d ** -0.5), "fsdp", "mlp"),
        "wz": p(_normal(ks[1], (d, di), d ** -0.5), "fsdp", "mlp"),
        "conv_w": p(_normal(ks[2], (m.d_conv, di), m.d_conv ** -0.5),
                    None, "mlp"),
        "conv_b": p(jnp.zeros((di,), jnp.float32), "mlp"),
        "x_proj": p(_normal(ks[3], (di, R + 2 * N), di ** -0.5), "mlp", None),
        "dt_w": p(_normal(ks[4], (R, di), R ** -0.5), None, "mlp"),
        "dt_bias": p(jnp.full((di,), -4.6, jnp.float32), "mlp"),  # softplus≈0.01
        "A_log": p(jnp.log(A), "mlp", None),
        "D": p(jnp.ones((di,), jnp.float32), "mlp"),
        "out_proj": p(_normal(ks[5], (di, d), di ** -0.5), "mlp", "fsdp"),
    }


def _mamba_conv(u, conv_w, conv_b, state=None):
    """Causal depthwise conv over time. u: (B,S,Di), conv_w: (Kc,Di).

    state: (B,Kc-1,Di) previous inputs for decode; returns (out, new_state).
    """
    Kc = conv_w.shape[0]
    if state is None:
        up = jnp.pad(u, ((0, 0), (Kc - 1, 0), (0, 0)))
    else:
        up = jnp.concatenate([state.astype(u.dtype), u], axis=1)
    out = sum(
        up[:, i : i + u.shape[1], :] * conv_w[i][None, None] for i in range(Kc)
    ) + conv_b[None, None]
    new_state = up[:, -(Kc - 1):, :] if Kc > 1 else None
    return out, new_state


def _mamba_ssm_inputs(params, u, cfg):
    m, di, R = _mamba_dims(cfg)
    get = lambda n: params[n][0] if isinstance(params[n], tuple) else params[n]
    x_dbl = jnp.einsum("bsd,dr->bsr", u.astype(jnp.float32),
                       get("x_proj").astype(jnp.float32))
    dt_in, Bc, Cc = jnp.split(x_dbl, [R, R + m.d_state], axis=-1)
    dt = jax.nn.softplus(
        jnp.einsum("bsr,rd->bsd", dt_in, get("dt_w").astype(jnp.float32))
        + get("dt_bias")
    )
    A = -jnp.exp(get("A_log"))
    return dt, A, Bc, Cc, get("D")


def apply_mamba(params, x, cfg: ModelConfig, *, cache=None):
    """Full-sequence mamba mixer; returns (y, new_cache or None)."""
    dt_c = _cdtype(cfg)
    get = lambda n: params[n][0] if isinstance(params[n], tuple) else params[n]
    u = jnp.einsum("bsd,de->bse", x, get("wx").astype(dt_c))
    z = jnp.einsum("bsd,de->bse", x, get("wz").astype(dt_c))
    u = constrain(u, "batch", None, "act_mlp")
    conv_state = None if cache is None else cache["conv"]
    u, new_conv = _mamba_conv(u, get("conv_w").astype(dt_c),
                              get("conv_b").astype(dt_c), conv_state)
    u = jax.nn.silu(u)
    dt, A, Bc, Cc, D = _mamba_ssm_inputs(params, u, cfg)
    y, h = ops.mamba_scan(u, dt, A, Bc, Cc, D)
    y = y * jax.nn.silu(z)
    out = jnp.einsum("bse,ed->bsd", y.astype(dt_c), get("out_proj").astype(dt_c))
    new_cache = {"conv": new_conv.astype(dt_c), "ssm": h}
    return out, new_cache


def apply_mamba_step(params, x, cfg: ModelConfig, cache):
    """Single-token mamba decode. x: (B,1,d); cache: {conv, ssm}."""
    dt_c = _cdtype(cfg)
    get = lambda n: params[n][0] if isinstance(params[n], tuple) else params[n]
    u = jnp.einsum("bsd,de->bse", x, get("wx").astype(dt_c))
    z = jnp.einsum("bsd,de->bse", x, get("wz").astype(dt_c))
    u, new_conv = _mamba_conv(u, get("conv_w").astype(dt_c),
                              get("conv_b").astype(dt_c), cache["conv"])
    u = jax.nn.silu(u)
    dt, A, Bc, Cc, D = _mamba_ssm_inputs(params, u, cfg)
    h, y = ops.mamba_step(
        cache["ssm"], u[:, 0], dt[:, 0], A, Bc[:, 0], Cc[:, 0], D
    )
    y = y[:, None] * jax.nn.silu(z)
    out = jnp.einsum("bse,ed->bsd", y.astype(dt_c), get("out_proj").astype(dt_c))
    return out, {"conv": new_conv.astype(cache["conv"].dtype), "ssm": h}


def init_mamba_cache(cfg: ModelConfig, B: int):
    m, di, _ = _mamba_dims(cfg)
    return {
        "conv": jnp.zeros((B, m.d_conv - 1, di), _cdtype(cfg)),
        "ssm": jnp.zeros((B, di, m.d_state), jnp.float32),
    }


# --------------------------------------------------------------------------- #
# RWKV-6 ("Finch") mixer: data-dependent decay time-mix.
# --------------------------------------------------------------------------- #
def init_rwkv6(cfg: ModelConfig, key):
    r = cfg.rwkv6 or RWKV6Config()
    d, Dw = cfg.d_model, r.decay_lora_dim
    ks = jax.random.split(key, 8)
    return {
        "wr": p(_normal(ks[0], (d, d), d ** -0.5), "fsdp", "mlp"),
        "wk": p(_normal(ks[1], (d, d), d ** -0.5), "fsdp", "mlp"),
        "wv": p(_normal(ks[2], (d, d), d ** -0.5), "fsdp", "mlp"),
        "wg": p(_normal(ks[3], (d, d), d ** -0.5), "fsdp", "mlp"),
        "wo": p(_normal(ks[4], (d, d), d ** -0.5), "mlp", "fsdp"),
        # data-dependent decay low-rank path (the Finch contribution)
        "w0": p(jnp.full((d,), -5.0, jnp.float32), None),
        "w1": p(_normal(ks[5], (d, Dw), d ** -0.5), "fsdp", None),
        "w2": p(_normal(ks[6], (Dw, d), Dw ** -0.5), None, "mlp"),
        "u": p(_normal(ks[7], (d,), 0.5), None),  # per-channel bonus
        # token-shift mixing coefficients for r,k,v,w,g streams
        "mu": p(jnp.full((5, d), 0.5, jnp.float32), None, None),
        "ln_scale": p(jnp.ones((d,), jnp.float32), None),
    }


def _rwkv_wkv_scan(r, k, v, w, u, H, dh):
    """WKV recurrence. r,k,v,w: (B,S,d) fp32; returns (y (B,S,d), state)."""
    B, S, d = r.shape
    rh = r.reshape(B, S, H, dh)
    kh = k.reshape(B, S, H, dh)
    vh = v.reshape(B, S, H, dh)
    wh = w.reshape(B, S, H, dh)
    uh = u.reshape(H, dh)

    def step(Sst, inp):
        r_t, k_t, v_t, w_t = inp  # (B,H,dh) each
        kv = k_t[..., :, None] * v_t[..., None, :]  # (B,H,dh,dh)
        y = jnp.einsum("bhij,bhi->bhj", Sst + uh[None, :, :, None] * kv, r_t)
        Sst = w_t[..., :, None] * Sst + kv
        return Sst, y

    from repro.models.scan_utils import chunked_scan

    S0 = jnp.zeros((B, H, dh, dh), jnp.float32)
    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (rh, kh, vh, wh))
    # chunked+checkpointed: the (B,H,dh,dh) carry is ~10MB/step — a plain
    # scan would stash S of them for backward (tens of GB at 4k tokens).
    Sf, ys = chunked_scan(step, S0, xs, chunk=64)
    return jnp.moveaxis(ys, 0, 1).reshape(B, S, d), Sf


def apply_rwkv6(params, x, cfg: ModelConfig, *, cache=None):
    """Full-sequence RWKV-6 time mix; returns (y, new_cache or None)."""
    r_cfg = cfg.rwkv6 or RWKV6Config()
    d = cfg.d_model
    dh = r_cfg.head_dim
    H = d // dh
    get = lambda n: params[n][0] if isinstance(params[n], tuple) else params[n]
    x32 = x.astype(jnp.float32)
    prev = (
        jnp.pad(x32[:, :-1], ((0, 0), (1, 0), (0, 0)))
        if cache is None
        else jnp.concatenate(
            [cache["shift"].astype(jnp.float32)[:, None], x32[:, :-1]], axis=1
        )
    )
    xx = prev - x32
    mu = get("mu")
    xr, xk, xv, xw, xg = (x32 + xx * mu[i][None, None] for i in range(5))
    r = xr @ get("wr").astype(jnp.float32)
    k = xk @ get("wk").astype(jnp.float32)
    v = xv @ get("wv").astype(jnp.float32)
    g = jax.nn.silu(xg @ get("wg").astype(jnp.float32))
    # data-dependent decay in (0,1): w = exp(-exp(w0 + tanh(x w1) w2))
    wlog = get("w0") + jnp.tanh(xw @ get("w1").astype(jnp.float32)) @ get(
        "w2"
    ).astype(jnp.float32)
    w = jnp.exp(-jnp.exp(wlog))
    y, Sf = _rwkv_wkv_scan(r, k, v, w, get("u"), H, dh)
    # per-head group norm (simplified to rmsnorm over head dim)
    yh = y.reshape(*y.shape[:-1], H, dh)
    yh = yh * jax.lax.rsqrt(jnp.mean(yh ** 2, -1, keepdims=True) + 1e-6)
    y = yh.reshape(y.shape) * get("ln_scale")
    out = (y * g) @ get("wo").astype(jnp.float32)
    new_cache = {"shift": x[:, -1], "wkv": Sf}
    return out.astype(x.dtype), new_cache


def apply_rwkv6_step(params, x, cfg: ModelConfig, cache):
    """Single-token RWKV-6 decode. x: (B,1,d); cache: {shift, wkv}."""
    r_cfg = cfg.rwkv6 or RWKV6Config()
    d = cfg.d_model
    dh = r_cfg.head_dim
    H = d // dh
    get = lambda n: params[n][0] if isinstance(params[n], tuple) else params[n]
    x32 = x[:, 0].astype(jnp.float32)  # (B,d)
    xx = cache["shift"].astype(jnp.float32) - x32
    mu = get("mu")
    xr, xk, xv, xw, xg = (x32 + xx * mu[i][None] for i in range(5))
    r = (xr @ get("wr").astype(jnp.float32)).reshape(-1, H, dh)
    k = (xk @ get("wk").astype(jnp.float32)).reshape(-1, H, dh)
    v = (xv @ get("wv").astype(jnp.float32)).reshape(-1, H, dh)
    g = jax.nn.silu(xg @ get("wg").astype(jnp.float32))
    wlog = get("w0") + jnp.tanh(xw @ get("w1").astype(jnp.float32)) @ get(
        "w2"
    ).astype(jnp.float32)
    w = jnp.exp(-jnp.exp(wlog)).reshape(-1, H, dh)
    uh = get("u").reshape(H, dh)
    kv = k[..., :, None] * v[..., None, :]
    Sst = cache["wkv"]
    y = jnp.einsum("bhij,bhi->bhj", Sst + uh[None, :, :, None] * kv, r)
    Snew = w[..., :, None] * Sst + kv
    y = y * jax.lax.rsqrt(jnp.mean(y ** 2, -1, keepdims=True) + 1e-6)
    y = y.reshape(-1, d) * get("ln_scale")
    out = (y * g) @ get("wo").astype(jnp.float32)
    return out[:, None].astype(x.dtype), {"shift": x[:, 0], "wkv": Snew}


def init_rwkv6_cache(cfg: ModelConfig, B: int):
    r = cfg.rwkv6 or RWKV6Config()
    H = cfg.d_model // r.head_dim
    return {
        "shift": jnp.zeros((B, cfg.d_model), _cdtype(cfg)),
        "wkv": jnp.zeros((B, H, r.head_dim, r.head_dim), jnp.float32),
    }
