"""The system under test, seen from the benchmark: its configuration
object and its parameter tree, built from a configuration file and the
benchmark's own weights (``chipbench.weights``)."""
from __future__ import annotations

import os
import sys

from chipbench.harness import ROOT

sys.path.insert(0, os.path.join(ROOT, "src"))

# flat leaf name -> path in the program's parameter tree
LAYOUT = {
    "embed": ("embed",),
    "attn_norm": ("blocks", 0, "norm1", "scale"),
    "wq": ("blocks", 0, "mixer", "wq"),
    "wk": ("blocks", 0, "mixer", "wk"),
    "wv": ("blocks", 0, "mixer", "wv"),
    "wo": ("blocks", 0, "mixer", "wo"),
    "mlp_norm": ("blocks", 0, "norm2", "scale"),
    "w_gate": ("blocks", 0, "ffn", "wg"),
    "w_up": ("blocks", 0, "ffn", "wu"),
    "w_down": ("blocks", 0, "ffn", "wd"),
    "final_norm": ("final_norm", "scale"),
    "head": ("head",),
}


def model_config(cfg: dict, overrides: dict | None = None):
    """The program's ``ModelConfig`` for a configuration file, checked
    against the file's published numbers."""
    from repro.configs import base, get_config

    mc = base.apply_overrides(get_config(cfg["arch"]),
                              {**cfg.get("overrides", {}),
                               **(overrides or {})})
    want = {"d_model": cfg["hidden_size"], "d_ff": cfg["intermediate_size"],
            "n_heads": cfg["num_attention_heads"],
            "n_kv_heads": cfg["num_key_value_heads"],
            "n_layers": cfg["num_hidden_layers"], "vocab": cfg["vocab_size"],
            "rope_theta": cfg["rope_theta"], "tie_embeddings": False,
            "norm": "rmsnorm", "activation": "silu", "glu": True,
            "qkv_bias": False, "rope": "rope"}
    got = {k: getattr(mc, k) for k in want}
    if got != want:
        raise ValueError(f"program config {got} differs from {want}")
    return mc


def to_tree(flat: dict) -> dict:
    """The program's parameter tree holding the benchmark's arrays."""
    tree = {"blocks": ({},)}
    for name, path in LAYOUT.items():
        node = tree
        for key in path[:-1]:
            if isinstance(key, int):
                node = node[key]
            else:
                node = node.setdefault(key, {})
        node[path[-1]] = flat[name]
    return tree


def from_tree(tree) -> dict:
    out = {}
    for name, path in LAYOUT.items():
        node = tree
        for key in path:
            node = node[key]
        out[name] = node
    return out


def check_layout(mc, flat_shapes: dict) -> None:
    """The program's own (abstract) parameters have exactly the tree and
    shapes the benchmark fills."""
    import jax

    from repro.train.steps import init_params_and_axes

    want, _ = init_params_and_axes(mc, jax.random.PRNGKey(0))
    got = to_tree({k: jax.ShapeDtypeStruct(s, "float32")
                   for k, s in flat_shapes.items()})
    ws = jax.tree_util.tree_structure(want)
    gs = jax.tree_util.tree_structure(got)
    if ws != gs:
        raise ValueError(f"parameter tree differs: program {ws}, "
                         f"benchmark {gs}")
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        if tuple(a.shape) != tuple(b.shape) or a.dtype != b.dtype:
            raise ValueError(f"leaf {a} differs from {b}")
