"""bfloat16 mixed-precision policy (paper §2, C7).

The policy, applied across every model definition:
  * matmuls / convolutions / attention contractions: bf16 operands
    (each apply-fn casts weights at use);
  * normalization statistics, softmax, losses, SSM recurrent state and
    gradient summation: fp32 (layers cast up internally);
  * master weights fp32; 1-D parameters (norm scales, biases) stay fp32.

Where the cast happens depends on whether the weights change:
  * training casts inside every step (``compute_cast``, pinned to the
    parameter sharding so FSDP all-gathers move bf16), since its master
    weights change every step;
  * serving holds a compute-dtype copy made once (``serving_copy``):
    the weights never change, so the per-use casts of every step would
    recompute the same values. Which leaves it casts is read from the
    serving programs themselves, so the copy gives bit-identical output.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.extend import core as jcore

COMPUTE_DTYPE = jnp.bfloat16
MASTER_DTYPE = jnp.float32
NORM_DTYPE = jnp.float32      # batch/rms/layer-norm statistics
LOSS_DTYPE = jnp.float32
GRADSUM_DTYPE = jnp.float32   # paper default; 300B+ configs opt into bf16


def compute_cast(params, axes, rules, dtype="bfloat16"):
    """bf16 compute copy of the params, sharding-pinned BEFORE use so the
    FSDP all-gather moves bf16, not fp32 (half the bytes & HBM).

    1-D params (norm scales, biases) stay fp32 (C7 mixed precision).
    """
    dt = jnp.dtype(dtype)

    def one(w, a):
        if w.dtype != jnp.float32 or w.ndim <= 1:
            return w
        c = w.astype(dt)
        if rules is not None:
            from jax.sharding import NamedSharding

            # param_spec, not spec_for: the compute copy mirrors the
            # master-weight layout (in wus mode params stay replicated
            # across data; only the moments take the data axis).
            c = jax.lax.with_sharding_constraint(
                c, NamedSharding(rules.mesh, rules.param_spec(a.names, w.shape))
            )
        return c

    return jax.tree_util.tree_map(one, params, axes)


# Higher-order primitives whose one body takes the eqn's operands
# positionally, and the param that holds it.
_BODIES = {"jit": "jaxpr", "remat2": "jaxpr",
           "custom_jvp_call": "call_jaxpr", "custom_vjp_call": "call_jaxpr",
           "scan": "jaxpr", "shard_map": "jaxpr"}


def _sort_uses(jaxpr, env, dt, cast, other):
    """Sort each use of a tracked var (``env``: var -> leaf index) into
    ``cast`` (a ``convert_element_type`` to ``dt``) or ``other`` (any
    other use), following it into the bodies of higher-order eqns."""
    other.update(env[v] for v in jaxpr.outvars
                 if isinstance(v, jcore.Var) and v in env)
    for eqn in jaxpr.eqns:
        hits = [(i, env[v]) for i, v in enumerate(eqn.invars)
                if isinstance(v, jcore.Var) and v in env]
        if not hits:
            continue
        name, params = eqn.primitive.name, eqn.params
        if name == "convert_element_type":
            (cast if params["new_dtype"] == dt else other).update(
                leaf for _, leaf in hits)
        elif name == "cond":  # operand 0 picks the branch
            other.update(leaf for i, leaf in hits if i == 0)
            for br in params["branches"]:
                _sort_uses(br.jaxpr, {br.jaxpr.invars[i - 1]: leaf
                                      for i, leaf in hits if i > 0},
                           dt, cast, other)
        elif name == "while":  # cond consts, body consts, then the carry
            nc, nb = params["cond_nconsts"], params["body_nconsts"]
            cj, bj = params["cond_jaxpr"].jaxpr, params["body_jaxpr"].jaxpr
            other.update(leaf for i, leaf in hits if i >= nc + nb)
            _sort_uses(cj, {cj.invars[i]: leaf for i, leaf in hits if i < nc},
                       dt, cast, other)
            _sort_uses(bj, {bj.invars[i - nc]: leaf for i, leaf in hits
                            if nc <= i < nc + nb}, dt, cast, other)
        elif name in _BODIES:
            body = params[_BODIES[name]]
            body = getattr(body, "jaxpr", body)  # ClosedJaxpr -> Jaxpr
            _sort_uses(body, {body.invars[i]: leaf for i, leaf in hits},
                       dt, cast, other)
        else:
            other.update(leaf for _, leaf in hits)


def serving_copy(params, programs, dtype="bfloat16"):
    """The tree a server computes from: each float32 leaf of ndim >= 2
    that every program only ever casts to ``dtype`` is cast once, here,
    on its master's sharding; every other leaf is passed through as is.

    ``programs``: ``(fn, args)`` pairs, each run as ``fn(params, *args)``
    (``args`` may be abstract, e.g. ``jax.ShapeDtypeStruct``). Each is
    traced to a jaxpr and every use of every leaf is followed, into
    ``scan``, ``jit``, ``cond``, ``while``, ``shard_map``, remat and
    custom-derivative bodies. A leaf is cast only if it is used, and each of its uses is a
    ``convert_element_type`` to ``dtype``; a read in float32, a Pallas
    call or any primitive not known here keeps it float32. The programs
    then compute with the very values they computed before, so their
    outputs are bit-identical; the casts at use become no-ops. A leaf
    already in ``dtype`` is returned unchanged, so a second server handed
    this copy shares its arrays.
    """
    dt = jnp.dtype(dtype)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    cast, other = set(), set()
    for fn, args in programs:
        # traced through a fresh wrapper, so no trace of ``fn`` at the
        # master dtypes lands in the trace cache a ``jax.jit(fn)`` shares
        closed = jax.make_jaxpr(lambda *a, _fn=fn: _fn(*a))(params, *args)
        invars = closed.jaxpr.invars[:len(leaves)]
        _sort_uses(closed.jaxpr, dict(zip(invars, range(len(leaves)))),
                   dt, cast, other)
    for i in cast - other:
        if leaves[i].dtype == jnp.float32 and leaves[i].ndim >= 2:
            # an eager cast keeps the master's sharding and whether it is
            # committed to it, so the programs see the same placements
            leaves[i] = leaves[i].astype(dt)
    return jax.tree_util.tree_unflatten(treedef, leaves)
