"""Seeded weights for a decoder-only configuration, made by the benchmark.

``make(cfg, seed)`` returns a flat dict of float32 arrays, named as the
reference reads them; each leaf draws from its own key (``fold_in`` of
the seed's key with the leaf's index), so one leaf can be made again
alone (``leaf``). Both the system under test and the plain reference get
their weights from here, never from each other.

Scales keep the residual stream and the logits at unit size: fan-in
scaled projections, embeddings of unit variance, RMSNorm gains near 1.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"d": d, "f": cfg["intermediate_size"], "H": h,
            "K": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or d // h,
            "L": cfg["num_hidden_layers"], "V": cfg["vocab_size"]}


def shapes(cfg: dict) -> dict:
    """Leaf name -> (shape, init std), in a fixed order."""
    n = dims(cfg)
    d, f, H, K, hd, L, V = (n[k] for k in ("d", "f", "H", "K", "hd", "L", "V"))
    return {
        "embed": ((V, d), 1.0),
        "attn_norm": ((L, d), None),
        "wq": ((L, d, H, hd), d ** -0.5),
        "wk": ((L, d, K, hd), d ** -0.5),
        "wv": ((L, d, K, hd), d ** -0.5),
        "wo": ((L, H, hd, d), (H * hd) ** -0.5),
        "mlp_norm": ((L, d), None),
        "w_gate": ((L, d, f), d ** -0.5),
        "w_up": ((L, d, f), d ** -0.5),
        "w_down": ((L, f, d), f ** -0.5),
        "final_norm": ((d,), None),
        "head": ((d, V), d ** -0.5),
    }


def seed_key(seed: int):
    """A key from a seed of any size (the driver's exceed 32 bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def leaf(cfg: dict, seed_or_key, name: str):
    table = shapes(cfg)
    key = seed_or_key if not isinstance(seed_or_key, int) else seed_key(
        seed_or_key)
    k = jax.random.fold_in(key, list(table).index(name))
    shape, std = table[name]
    if std is None:  # norm gain: 1 + N(0, 0.1^2)
        return 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
    return std * jax.random.normal(k, shape, jnp.float32)


def build(cfg: dict):
    """key -> every leaf, float32: the body of one jitted call."""
    return lambda key: {name: leaf(cfg, key, name) for name in shapes(cfg)}


def make(cfg: dict, seed: int) -> dict:
    """Every leaf in one jitted call on the default device."""
    return jax.jit(build(cfg))(seed_key(seed))
