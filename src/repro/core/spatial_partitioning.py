"""Spatial partitioning with halo exchange (paper §2 "Model parallelism",
Fig. 3; C3) — and its transformer analogue, sequence partitioning.

The paper shards conv layers along spatial dims across 2-4 cores; each core
exchanges a halo of ``kernel//2`` rows with its neighbours before the conv.
On TPU-v3 this gave SSD a 1.6x speedup on 4 cores (Fig. 10), enabling
scaling past the global-batch limit.

JAX mapping: ``shard_map`` over the 'model' axis + ``lax.ppermute`` for the
neighbour exchange. The same halo pattern implements *sequence-parallel
sliding-window attention*: a sequence shard needs exactly the previous
shard's last ``window`` keys/values — Fig. 3 with rows -> tokens.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels import ops as kops


# --------------------------------------------------------------------------- #
# Halo exchange primitive (inside shard_map).
# --------------------------------------------------------------------------- #
def halo_exchange(x, axis_name: str, *, lo: int, hi: int, axis: int):
    """Fetch ``lo`` trailing rows from the left neighbour and ``hi`` leading
    rows from the right neighbour along ``axis``; boundary shards get zeros.

    Returns x extended to size + lo + hi along ``axis``.
    """
    n = axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    parts = []
    if lo:
        tail = jax.lax.slice_in_dim(x, x.shape[axis] - lo, x.shape[axis], axis=axis)
        from_left = jax.lax.ppermute(
            tail, axis_name, [(i, (i + 1) % n) for i in range(n)]
        )
        from_left = jnp.where(idx == 0, jnp.zeros_like(from_left), from_left)
        parts.append(from_left)
    parts.append(x)
    if hi:
        head = jax.lax.slice_in_dim(x, 0, hi, axis=axis)
        from_right = jax.lax.ppermute(
            head, axis_name, [(i, (i - 1) % n) for i in range(n)]
        )
        from_right = jnp.where(
            idx == n - 1, jnp.zeros_like(from_right), from_right
        )
        parts.append(from_right)
    return jnp.concatenate(parts, axis=axis)


# --------------------------------------------------------------------------- #
# Spatially partitioned 2-D convolution (NHWC, shard H across cores).
# --------------------------------------------------------------------------- #
def spatial_conv2d(x, w, *, stride: int = 1, mesh: Mesh,
                   axis_name: str = "model"):
    """Conv2d with the H dim sharded over ``axis_name`` (paper Fig. 3).

    x: (B, H, W, C) — H divisible by (axis size * stride).
    w: (kh, kw, C, O), SAME padding. Equivalent to unsharded conv (tested).
    """
    kh = w.shape[0]
    H = x.shape[1]
    n = mesh.shape[axis_name]
    h_loc = H // n
    # XLA SAME padding (extra row goes at the end for even overhang):
    total = max((-(-H // stride) - 1) * stride + kh - H, 0)
    pad_lo = total // 2
    # Per-shard halos so each shard computes exactly its h_loc//stride rows.
    lo = pad_lo
    hi = (h_loc // stride - 1) * stride + kh - pad_lo - h_loc

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(None, axis_name, None, None), P()),
        out_specs=P(None, axis_name, None, None),
        check_vma=False,
    )
    def run(x_sh, w_):
        xh = halo_exchange(x_sh, axis_name, lo=lo, hi=max(hi, 0), axis=1)
        if hi < 0:
            xh = jax.lax.slice_in_dim(xh, 0, xh.shape[1] + hi, axis=1)
        kw = w_.shape[1]
        totw = max((-(-x_sh.shape[2] // stride) - 1) * stride + kw
                   - x_sh.shape[2], 0)
        return jax.lax.conv_general_dilated(
            xh, w_, window_strides=(stride, stride),
            padding=((0, 0), (totw // 2, totw - totw // 2)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )

    return run(x, w)


# --------------------------------------------------------------------------- #
# Sequence-parallel sliding-window attention (the transformer analogue).
# --------------------------------------------------------------------------- #
def seq_parallel_swa(q, k, v, *, window: int, mesh: Mesh,
                     axis_name: str = "model"):
    """Causal sliding-window attention with the sequence sharded over
    ``axis_name``; each shard halo-exchanges the previous shard's last
    ``window`` K/V (C3 transplanted to sequence dim).

    q,k,v: (B, S, H, D) with S divisible by the axis size; window <= S/n.
    """

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(None, axis_name, None, None),) * 3,
        out_specs=P(None, axis_name, None, None),
        check_vma=False,
    )
    def run(q_sh, k_sh, v_sh):
        idx = jax.lax.axis_index(axis_name)
        s_loc = q_sh.shape[1]
        kx = halo_exchange(k_sh, axis_name, lo=window, hi=0, axis=1)
        vx = halo_exchange(v_sh, axis_name, lo=window, hi=0, axis=1)
        # Global offsets: q[0] sits at idx*s_loc; the halo'd K/V starts at
        # idx*s_loc - window. Keys at negative global positions (shard 0's
        # zero halo) are masked inside ops.attention.
        q_off = idx * s_loc
        return kops.attention(
            q_sh, kx, vx, causal=True, window=window,
            q_offset=q_off, k_offset=q_off - window,
        )

    return run(q, k, v)
