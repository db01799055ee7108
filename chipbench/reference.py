"""Plain reference for a decoder-only (Llama-style) configuration.

Straight ``jax.numpy`` with no kernels, cache or batching, and nothing
from the program under test: token embedding, then per layer RMSNorm ->
grouped-query attention with rotary positions (half-split rotation,
``rope_theta``) -> residual -> RMSNorm -> SwiGLU MLP
(``down(silu(gate(x)) * up(x))``) -> residual, a final RMSNorm and the
output head. Weights come from ``chipbench.weights``.

``precision="f32"`` computes in float32 with every matmul at
``Precision.HIGHEST`` (a TPU would otherwise round float32 operands to
bfloat16). ``precision="int8"`` or ``"fp8"`` is the control, one step
below the configuration's bfloat16 compute: both operands of every
matmul (weights per output channel, activations per row, attention
scores and values too) on the int8 or float8-e4m3 grid, accumulated in
float32; norms, softmax and the residual stream stay float32.

Attention runs in blocks of ``QBLOCK`` queries, layers under one scan,
each layer and block rematerialised in the backward pass, so a
reference over thousands of positions at published widths fits beside
nothing else on one chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench import weights as W

QBLOCK = 512
HIGHEST = jax.lax.Precision.HIGHEST


# --------------------------------------------------------------------------- #
# Precision policies.
# --------------------------------------------------------------------------- #
def _fake(x, axes, precision):
    """``x`` on the low-precision grid, one scale per slice along the
    contracted ``axes`` (a weight's output channel, an activation's
    row): symmetric int8, or float8 e4m3 scaled to its range. Rounding
    passes gradients straight through, as fake-quantized training does."""
    top = 127.0 if precision == "int8" else 448.0
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jax.lax.stop_gradient(jnp.where(amax > 0, amax / top, 1.0))
    v = x / scale
    if precision == "int8":
        q = jnp.round(v)
    else:
        q = v.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return (v + jax.lax.stop_gradient(q - v)) * scale


def _mm(eq, x, w, x_axes, w_axes, precision):
    """The einsum ``eq`` of x and w, contracting ``x_axes`` of x and
    ``w_axes`` of w; below float32 both operands are put on the
    precision's grid first (accumulation stays float32)."""
    if precision != "f32":
        x, w = _fake(x, x_axes, precision), _fake(w, w_axes, precision)
    return jnp.einsum(eq, x, w, precision=HIGHEST)


# --------------------------------------------------------------------------- #
# Forward.
# --------------------------------------------------------------------------- #
def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _rope(x, positions, theta):
    """x: (S, heads, hd); rotate the two halves of each head."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs       # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, precision):
    """Causal GQA over one sequence. q: (S, H, hd); k, v: (S, K, hd)."""
    S, H, hd = q.shape
    K = k.shape[1]
    G = H // K
    n_blocks = S // QBLOCK
    qb = q.reshape(n_blocks, QBLOCK, K, G, hd)
    cols = jnp.arange(S)

    def block(args):
        i, qi = args
        s = _mm("qkgd,skd->kgqs", qi, k, (3,), (2,), precision) * hd ** -0.5
        rows = i * QBLOCK + jnp.arange(QBLOCK)
        s = jnp.where(cols[None, :] <= rows[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("kgqs,skd->qkgd", p, v, (3,), (0,), precision)

    out = jax.lax.map(jax.checkpoint(block), (jnp.arange(n_blocks), qb))
    return out.reshape(S, H, hd)


def hidden(w: dict, cfg: dict, tokens, precision: str = "f32"):
    """Final-norm hidden states (S, d) of one sequence; S % QBLOCK == 0."""
    eps = cfg["rms_norm_eps"]
    theta = cfg["rope_theta"]
    S = tokens.shape[0]
    pos = jnp.arange(S)
    table = w["embed"]
    x = (table if precision == "f32" else _fake(table, (1,), precision))[
        tokens]
    layers = {k: w[k] for k in ("attn_norm", "wq", "wk", "wv", "wo",
                                "mlp_norm", "w_gate", "w_up", "w_down")}

    def layer(x, lw):
        h = _rms(x, lw["attn_norm"], eps)
        q = _rope(_mm("sd,dhk->shk", h, lw["wq"], (1,), (0,), precision), pos, theta)
        k = _rope(_mm("sd,dhk->shk", h, lw["wk"], (1,), (0,), precision), pos, theta)
        v = _mm("sd,dhk->shk", h, lw["wv"], (1,), (0,), precision)
        a = _attention(q, k, v, precision)
        x = x + _mm("shk,hkd->sd", a, lw["wo"], (1, 2), (0, 1), precision)
        h = _rms(x, lw["mlp_norm"], eps)
        g = _mm("sd,df->sf", h, lw["w_gate"], (1,), (0,), precision)
        u = _mm("sd,df->sf", h, lw["w_up"], (1,), (0,), precision)
        x = x + _mm("sf,fd->sd", jax.nn.silu(g) * u, lw["w_down"], (1,),
                    (0,), precision)
        return x, None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, layers)
    return _rms(x, w["final_norm"], eps)


def logits_at(w, cfg, tokens, idx, precision="f32"):
    """Logits (len(idx), V) at positions ``idx`` of one sequence."""
    x = hidden(w, cfg, tokens, precision)[idx]
    return _mm("sd,dv->sv", x, w["head"], (1,), (0,), precision)


def padded_len(n: int) -> int:
    return -(-n // QBLOCK) * QBLOCK


# --------------------------------------------------------------------------- #
# Serving check: gap of each served token below the reference's best.
# --------------------------------------------------------------------------- #
@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _gaps(w, tokens, served, cfg_items, precision):
    """At every position: the float32 reference's best logit minus its
    logit for ``served`` (f32), or for the token that ``precision``
    puts first (the control)."""
    cfg = dict(cfg_items)
    x = hidden(w, cfg, tokens, "f32")
    ref = _mm("sd,dv->sv", x, w["head"], (1,), (0,), "f32")
    if precision != "f32":
        low = _mm("sd,dv->sv", hidden(w, cfg, tokens, precision), w["head"],
                  (1,), (0,), precision)
        served = jnp.argmax(low, -1)
    return jnp.max(ref, -1) - jnp.take_along_axis(ref, served[:, None],
                                                  -1)[:, 0]


def served_gaps(w, cfg, prompt, served, precision="f32"):
    """Gap of each served token: the reference's best logit minus the
    logit of the token served, at the position that produced it.

    With ``precision`` other than f32 the served tokens are replaced by
    the tokens the lower precision puts first at the same positions
    (the control); the gaps are still read from the float32 logits.
    Sequences are padded to a multiple of ``QBLOCK``, so few shapes
    compile.
    """
    import numpy as np

    seq = list(prompt) + list(served[:-1])
    S = padded_len(len(seq))
    tokens = np.zeros((S,), np.int32)
    tokens[:len(seq)] = seq
    at = np.zeros((S,), np.int32)        # token served after position i
    first = len(prompt) - 1
    at[first:first + len(served)] = served
    out = _gaps(w, jnp.asarray(tokens), jnp.asarray(at),
                tuple(sorted(_scalars(cfg).items())), precision)
    return np.asarray(out)[first:first + len(served)]


def _scalars(cfg: dict) -> dict:
    return {k: v for k, v in cfg.items()
            if isinstance(v, (int, float, str)) and not isinstance(v, bool)}


# --------------------------------------------------------------------------- #
# Training: loss, gradients in blocks of rows, Adam.
# --------------------------------------------------------------------------- #
def row_loss(w, cfg, tokens, precision="f32"):
    """Mean next-token cross entropy of one row (S,), S % QBLOCK == 0."""
    x = hidden(w, cfg, tokens, precision)[:-1]
    lg = _mm("sd,dv->sv", x, w["head"], (1,), (0,), precision)
    logz = jax.scipy.special.logsumexp(lg, -1)
    gold = jnp.take_along_axis(lg, tokens[1:, None], -1)[:, 0]
    return jnp.mean(logz - gold)


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"),
                   donate_argnums=(0,))
def _add_row(acc, w, tokens, scale, cfg_items, precision):
    """acc + scale * (one row's gradient), and the row's loss."""
    loss, g = jax.value_and_grad(row_loss)(w, dict(cfg_items), tokens,
                                           precision)
    return jax.tree_util.tree_map(lambda a, b: a + b * scale, acc, g), loss


def loss_and_grad(w, cfg, batch, precision="f32"):
    """Batch mean loss and its gradient, one row at a time."""
    items = tuple(sorted(_scalars(cfg).items()))
    n = batch.shape[0]
    total = 0.0
    grad = jax.tree_util.tree_map(jnp.zeros_like, w)
    for r in range(n):
        grad, loss = _add_row(grad, w, jnp.asarray(batch[r]),
                              jnp.float32(1.0 / n), items, precision)
        total += float(loss) / n
    return total, grad


def lr_at(opt: dict, step: int) -> float:
    """Linear warm-up to ``lr`` over ``warmup_steps``, then cosine to 0
    at ``total_steps``; ``step`` counts from 0."""
    import math

    base, warm, total = opt["lr"], opt["warmup_steps"], opt["total_steps"]
    if step < warm:
        return base * (step + 1) / max(1, warm)
    frac = min(max((step - warm) / max(1, total - warm), 0.0), 1.0)
    return 0.5 * base * (1 + math.cos(math.pi * frac))


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps"),
                   donate_argnums=(0, 2, 3))
def _adam(w, g, m, v, lr, t, b1, b2, eps):
    """One leaf's Adam update."""
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mh = m / (1 - b1 ** t)
    vh = v / (1 - b2 ** t)
    return w - lr * mh / (jnp.sqrt(vh) + eps), m, v


@jax.jit
def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def change_norms(cfg: dict, seed: int, w: dict) -> dict:
    """Per leaf, the norm of ``w`` minus the seeded starting weights
    (each starting leaf made again from the seed, one at a time)."""
    key = W.seed_key(seed)
    return {k: float(_change_norm(w[k], key, tuple(sorted(
        _scalars(cfg).items())), k)) for k in W.shapes(cfg)}


@functools.partial(jax.jit, static_argnames=("cfg_items", "name"))
def _change_norm(x, key, cfg_items, name):
    start = W.leaf(dict(cfg_items), key, name)
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32) - start)))


def train_steps(cfg: dict, seed: int, batches, opt: dict, precision="f32",
                against=None, keep_grad=False) -> dict:
    """Follow the program's first ``len(batches)`` Adam steps from the
    seeded weights. Returns the losses, the per-leaf norms of the first step's
    gradient (``grad``) and of the weights' change over all the steps
    (``change``); with ``against`` (leaf -> host array, another first
    gradient) the per-leaf norms of the difference (``diff``); with
    ``keep_grad`` the first gradient itself, on the host (``g1``)."""
    import numpy as np

    w = W.make(cfg, seed)
    m = v = None  # Adam's moments, on the host between steps
    out = {"losses": []}
    for step, batch in enumerate(batches):
        loss, g = loss_and_grad(w, cfg, batch, precision)
        out["losses"].append(loss)
        if step == 0:
            out["grad"] = {k: float(x) for k, x in leaf_norms(g).items()}
            if against is not None:
                out["diff"] = {k: float(jnp.sqrt(jnp.sum(jnp.square(
                    g[k] - jax.device_put(against[k], g[k].sharding)))))
                    for k in g}
            if keep_grad:
                out["g1"] = {k: np.asarray(x) for k, x in g.items()}
        lr, t = jnp.float32(lr_at(opt, step)), jnp.float32(step + 1)
        last = step == len(batches) - 1
        new_m, new_v = {}, {}
        for k in list(w):
            mk = jnp.zeros_like(w[k]) if m is None else jnp.asarray(m.pop(k))
            vk = jnp.zeros_like(w[k]) if v is None else jnp.asarray(v.pop(k))
            w[k], mk, vk = _adam(w[k], g.pop(k), mk, vk, lr, t,
                                 opt["b1"], opt["b2"], opt["eps"])
            if not last:
                new_m[k], new_v[k] = np.asarray(mk), np.asarray(vk)
            del mk, vk
        m, v = new_m, new_v
    del m, v
    out["change"] = change_norms(cfg, seed, w)
    return out
