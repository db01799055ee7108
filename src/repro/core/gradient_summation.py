"""2-D gradient summation (paper §2 "Optimize gradient summation", C2).

The paper aggregates gradients over the TPU-v3 2-D torus with a
two-phase algorithm: reduce-scatter along one torus dimension, all-reduce
along the orthogonal dimension, then all-gather the result back — and
pipelines the gathers of non-contiguous gradient tensors from HBM with the
network transfer (>1.5x gradient-summation speedup on ResNet-50).

JAX mapping (DESIGN.md §2.2):
  * the data-parallel mesh axes are already 2-D on the multi-pod mesh
    ("data" within a pod, "pod" across pods);
  * ``psum_scatter``("data") -> ``psum``("pod") -> ``all_gather``("data")
    inside ``shard_map`` reproduces the schedule — the slow cross-pod
    links carry only 1/|data| of the bytes;
  * the paper's HBM-gather pipelining of non-contiguous tensors maps to
    flattening the gradient pytree into ONE contiguous buffer before the
    collectives (``flatten_tree``/``unflatten_tree``), letting XLA overlap
    the copy-in/copy-out with network transfer.

``gradient_allreduce_2d`` is the explicit shard_map implementation used by
the paper-faithful path and the equivalence tests; inside pjit'd train
steps GSPMD emits the same schedule from the sharding annotations.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


# --------------------------------------------------------------------------- #
# Contiguous-buffer (un)flattening — the non-contiguous-tensor pipelining.
# --------------------------------------------------------------------------- #
def flatten_tree(tree, pad_multiple: int = 1, dtype=jnp.float32):
    """Concatenate every leaf into one contiguous 1-D buffer (padded)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    flat = jnp.concatenate([l.astype(dtype).reshape(-1) for l in leaves])
    pad = (-flat.size) % pad_multiple
    if pad:
        flat = jnp.pad(flat, (0, pad))
    meta = (treedef, [(l.shape, l.dtype) for l in leaves], pad)
    return flat, meta


def unflatten_tree(flat, meta):
    treedef, shapes, pad = meta
    if pad:
        flat = flat[: flat.size - pad]
    out, off = [], 0
    for shape, dtype in shapes:
        n = int(np.prod(shape)) if shape else 1
        out.append(flat[off : off + n].reshape(shape).astype(dtype))
        off += n
    return jax.tree_util.tree_unflatten(treedef, out)


# --------------------------------------------------------------------------- #
# 2-D all-reduce schedules (explicit collectives; run inside shard_map).
# --------------------------------------------------------------------------- #
def allreduce_1d(x, axis: str):
    """Baseline: single-phase psum over one (possibly large) axis."""
    return jax.lax.psum(x, axis)


def allreduce_2d(x, scatter_axis: str, reduce_axis: Optional[str]):
    """reduce-scatter(scatter_axis) -> psum(reduce_axis) -> all-gather.

    x must be a 1-D buffer divisible by the scatter axis size.
    """
    shard = jax.lax.psum_scatter(x, scatter_axis, tiled=True)
    if reduce_axis is not None:
        shard = jax.lax.psum(shard, reduce_axis)
    return jax.lax.all_gather(shard, scatter_axis, tiled=True)


def reduce_scatter_2d(x, scatter_axis: str, reduce_axis: Optional[str]):
    """Like allreduce_2d but leaves the result scattered (WUS consumes the
    shard directly — the all-gather happens after the weight update)."""
    shard = jax.lax.psum_scatter(x, scatter_axis, tiled=True)
    if reduce_axis is not None:
        shard = jax.lax.psum(shard, reduce_axis)
    return shard


# --------------------------------------------------------------------------- #
# Public API: whole-pytree 2-D gradient summation.
# --------------------------------------------------------------------------- #
def gradient_allreduce_2d(grads, mesh: Mesh, *, scatter_axis: str = "data",
                          reduce_axis: Optional[str] = None,
                          dtype=jnp.float32):
    """Sum a replicated-layout gradient pytree across the data axes.

    Gradients enter replicated over (scatter_axis, reduce_axis) with each
    device holding its local contribution; the summed result is returned in
    the same layout. Paper-faithful fp32 summation by default (C7).
    """
    if reduce_axis is not None and reduce_axis not in mesh.axis_names:
        reduce_axis = None
    n_scatter = mesh.shape[scatter_axis]
    flat, meta = flatten_tree(grads, pad_multiple=n_scatter, dtype=dtype)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=P(),  # every device holds its full local gradient buffer
        out_specs=P(),
        check_vma=False,
    )
    def summed(buf):
        return allreduce_2d(buf, scatter_axis, reduce_axis)

    return unflatten_tree(summed(flat), meta)


def gradient_allreduce_1d(grads, mesh: Mesh, *, axes: Sequence[str] = ("data",),
                          dtype=jnp.float32):
    """Single-phase baseline for the benchmarks (no scatter phase)."""
    axes = tuple(a for a in axes if a in mesh.axis_names)
    flat, meta = flatten_tree(grads, dtype=dtype)

    @functools.partial(shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
                       check_vma=False)
    def summed(buf):
        out = buf
        for a in axes:
            out = jax.lax.psum(out, a)
        return out

    return unflatten_tree(summed(flat), meta)
