"""KV-cache quantization helpers shared by the model layers, the paged
pool, the kernel fallbacks and the test oracles.

Symmetric per-row scales over the trailing (head) dimension:

  * **int8**: ``scale = amax / 127``, values in [-127, 127];
  * **int4**: ``scale = amax / 7``, values in [-7, 7], packed two per
    byte along the head dimension — byte ``j`` holds dim ``j`` in the
    low nibble and dim ``j + head_dim // 2`` in the high nibble (a
    halves layout: the unpack is one lane-dim concatenate, which Pallas
    handles where an interleave would need a relayout), so an int4
    pool's trailing axis is ``head_dim // 2`` (head_dim must be even).

Dequantization is ``values * scale`` in fp32; the nibble unpack uses
pure integer ops (``(x & 0xF ^ 8) - 8`` sign extension) so the same
code runs inside Pallas kernels on TPU and in the jnp fallbacks.
"""
from __future__ import annotations

import jax.numpy as jnp


def quantize_int8(x):
    """x: (..., hd) -> (int8 values (..., hd), fp32 scale (...,))."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.round(x.astype(jnp.float32) / scale[..., None])
    return jnp.clip(q, -127, 127).astype(jnp.int8), scale


def pack_int4(q):
    """q: integer values in [-8, 7], (..., hd) with hd even -> int8
    (..., hd // 2) packed nibbles."""
    if q.shape[-1] % 2:
        raise ValueError(f"int4 packing needs an even trailing dim, "
                         f"got {q.shape[-1]}")
    h = q.shape[-1] // 2
    lo = q[..., :h].astype(jnp.int32)
    hi = q[..., h:].astype(jnp.int32)
    return ((hi << 4) | (lo & 0xF)).astype(jnp.int8)


def unpack_int4(packed):
    """int8 (..., hd // 2) packed nibbles -> int8 (..., hd)."""
    p = packed.astype(jnp.int32)
    lo = ((p & 0xF) ^ 8) - 8
    hi = (((p >> 4) & 0xF) ^ 8) - 8
    return jnp.concatenate([lo, hi], axis=-1).astype(jnp.int8)


def quantize_int4(x):
    """x: (..., hd), hd even -> (packed int8 (..., hd // 2), fp32 scale)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 7.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]), -7, 7)
    return pack_int4(q.astype(jnp.int32)), scale


def dequantize(pool, scale, head_dim: int):
    """Quantized pool (..., hd) int8 or (..., hd // 2) int4-packed, plus
    per-row scale (...,) -> fp32 (..., hd). The int4 case is inferred
    from the trailing-axis size."""
    vals = pool if pool.shape[-1] == head_dim else unpack_int4(pool)
    return vals.astype(jnp.float32) * scale[..., None].astype(jnp.float32)


def check_insert_dtype(pool_dtype, new_dtype, where: str) -> None:
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
