"""Distributed (cross-replica) batch normalization (paper §2, from Ying et
al. [19]; C5).

When examples-per-core drops below a threshold, per-core batch-norm
statistics become too noisy; the fix is to compute mean/variance over a
*subgroup* of replicas (not the whole pod — that would serialize on the
interconnect and change the regularization).

``distributed_batch_norm`` runs inside shard_map with
``axis_index_groups`` controlling the subgroup size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P


def batch_norm(x, scale, bias, *, eps: float = 1e-5):
    """Plain batch norm over (batch, spatial) dims. x: (B,H,W,C) or (B,C)."""
    red = tuple(range(x.ndim - 1))
    x32 = x.astype(jnp.float32)
    mu = x32.mean(red)
    var = x32.var(red)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps) * scale + bias
    return y.astype(x.dtype), mu, var


def _group_psum(x, axis_name: str, group_size: int):
    n = axis_size(axis_name)
    if group_size >= n:
        return jax.lax.psum(x, axis_name), n
    groups = [
        list(range(g * group_size, (g + 1) * group_size))
        for g in range(n // group_size)
    ]
    return jax.lax.psum(x, axis_name, axis_index_groups=groups), group_size


def distributed_batch_norm(x, scale, bias, *, mesh: Mesh,
                           axis_name: str = "data", group_size: int = 2,
                           eps: float = 1e-5):
    """Batch norm with statistics shared across a replica subgroup.

    x: (B, ..., C) with B sharded over ``axis_name``.
    group_size: replicas per statistics group (the [19] threshold knob).
    """

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(axis_name), P(), P()),
        out_specs=P(axis_name),
        check_vma=False,
    )
    def run(x_sh, scale_, bias_):
        red = tuple(range(x_sh.ndim - 1))
        x32 = x_sh.astype(jnp.float32)
        cnt = np.prod([x_sh.shape[i] for i in red])
        s1, g = _group_psum(x32.sum(red), axis_name, group_size)
        s2, _ = _group_psum((x32 ** 2).sum(red), axis_name, group_size)
        mu = s1 / (cnt * g)
        var = s2 / (cnt * g) - mu ** 2
        y = (x32 - mu) * jax.lax.rsqrt(var + eps) * scale_ + bias_
        return y.astype(x_sh.dtype)

    return run(x, scale, bias)
