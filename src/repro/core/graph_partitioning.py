"""Graph partitioning (paper §3 Mask-RCNN stage 2, C10): "we apply graph
partitioning by placing independent ops on up to four different cores."

JAX mapping: independent branches whose inputs are replicated run inside a
``shard_map`` over the 'model' axis, each branch gated to its shard group
with ``lax.cond`` (so a device only executes the branch it owns) and the
results rebuilt with a sum over disjoint supports — the same
tensor-granular pattern as ``weight_update_sharding.lars_sharded_update``.

Equivalence with sequential execution is tested (tests/dist_checks.py);
the speedup claim at pod scale is Fig. 10's.
"""
from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def run_partitioned(branches: Sequence[Callable], *, mesh: Mesh,
                    axis_name: str = "model"):
    """Execute independent thunks, branch i owned by shard group i%n.

    Each thunk must close over replicated inputs and return one array.
    Returns the list of branch outputs (replicated).
    """
    n = mesh.shape[axis_name]
    shapes = [jax.eval_shape(b) for b in branches]

    @functools.partial(shard_map, mesh=mesh, in_specs=(), out_specs=P(),
                       check_vma=False)
    def run():
        idx = jax.lax.axis_index(axis_name)
        outs = []
        for i, b in enumerate(branches):
            owner = i % n

            def do(b=b):
                return b().astype(jnp.float32)

            def skip(i=i):
                return jnp.zeros(shapes[i].shape, jnp.float32)

            val = jax.lax.cond(idx == owner, do, skip)
            # exactly one shard computed this branch -> psum rebuilds it
            outs.append(jax.lax.psum(val, axis_name))
        return tuple(outs)

    outs = run()
    return [o.astype(s.dtype) for o, s in zip(outs, shapes)]
