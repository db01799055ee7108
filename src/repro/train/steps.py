"""Train / prefill / decode step builders + sharding-spec derivation.

The GSPMD path of the paper's techniques lives here:
  * C1 weight-update sharding: optimizer-state specs from
    ``opt_state_specs`` put the data axis on the moments, so XLA emits
    reduce-scatter(grads) -> sharded update -> all-gather(weights);
  * C2 2-D gradient summation: batch is sharded over ("pod","data"), so
    gradient reduction factorizes over the two axes (reduce-scatter within
    a pod, all-reduce across pods);
  * C7 mixed precision: bf16 compute, fp32 master weights & loss.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig
from repro.dist import Axes, Rules, param_specs, opt_state_specs, split_tree, use_rules
from repro.optim import Optimizer, adam, cosine_warmup


# --------------------------------------------------------------------------- #
# Family dispatch.
# --------------------------------------------------------------------------- #
class ModelAPI:
    """Uniform facade over the decoder-only and enc-dec model modules."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        if cfg.is_encdec:
            from repro.models import encdec as M
            self._m = M
            self.init = M.init_encdec
        else:
            from repro.models import lm as M
            self._m = M
            self.init = M.init_lm

    def loss(self, params, batch):
        return self._m.loss_fn(params, self.cfg, batch)

    def prefill(self, params, batch, *, cache_len=None, window=None,
                last_pos=None):
        if self.cfg.is_encdec:
            return self._m.prefill(
                params, self.cfg, batch["media"], batch["tokens"],
                cache_len=cache_len, window=window, last_pos=last_pos,
            )
        return self._m.prefill(
            params, self.cfg, batch["tokens"], media=batch.get("media"),
            cache_len=cache_len, window=window, last_pos=last_pos,
        )

    def decode(self, params, token, cache, pos, *, window=None):
        return self._m.decode_step(
            params, self.cfg, token, cache, pos, window=window
        )

    def init_cache(self, B, seq_len, window=None):
        return self._m.init_cache(self.cfg, B, seq_len, window)

    # ---- paged serving (attention-only stacks; repro.serve) ----------- #
    def init_paged_cache(self, B, n_pages, page):
        if self.cfg.is_encdec:
            return self._m.init_paged_cache(self.cfg, B, n_pages, page)
        return self._m.init_paged_cache(self.cfg, n_pages, page)

    def decode_chunk(self, params, tokens, cache, page_table, pos, n_valid,
                     *, window=None, full_logits=False):
        return self._m.decode_chunk(
            params, self.cfg, tokens, cache, page_table, pos, n_valid,
            window=window, full_logits=full_logits,
        )

    def encode_cross(self, params, frames):
        """Enc-dec only: encoder + per-layer cross K/V for one request."""
        return self._m.encode_cross(params, self.cfg, frames)


def make_optimizer(cfg: ModelConfig, total_steps: int = 10_000) -> Optimizer:
    """Default per-arch optimizer: Adam w/ cosine schedule (the paper's
    Transformer choice, with tuned betas for large batch)."""
    return adam(
        cosine_warmup(3e-4, min(1000, total_steps // 10), total_steps),
        b1=0.9, b2=0.95, eps=1e-8,
        moment_dtype=cfg.moment_dtype,
    )


# --------------------------------------------------------------------------- #
# State init + shapes + specs.
# --------------------------------------------------------------------------- #
def init_params_and_axes(cfg: ModelConfig, key, concrete: bool = False):
    """Returns (param values or shapes, axes tree) — axes captured during
    (abstract) tracing so no memory is allocated unless concrete=True."""
    api = ModelAPI(cfg)
    captured = {}

    def f(k):
        vals, axes = split_tree(api.init(cfg, k))
        captured["axes"] = axes
        return vals

    vals = f(key) if concrete else jax.eval_shape(f, key)
    return vals, captured["axes"]


def init_train_state(cfg: ModelConfig, optimizer: Optimizer, key,
                     concrete: bool = False):
    if concrete:
        params, axes = init_params_and_axes(cfg, key, concrete=True)
        return {"params": params, "opt": optimizer.init(params)}, axes
    params, axes = init_params_and_axes(cfg, key)
    opt = jax.eval_shape(optimizer.init, params)
    return {"params": params, "opt": opt}, axes


def train_state_specs(cfg: ModelConfig, state_shapes, axes, rules: Rules):
    """PartitionSpec tree matching {"params", "opt"}."""
    pspecs = param_specs(axes, state_shapes["params"], rules)
    ospecs = {}
    for k, v in state_shapes["opt"].items():
        if k == "step":
            ospecs[k] = P()
        else:  # moments mirror params with the WUS 'opt_fsdp' upgrade (C1)
            ospecs[k] = opt_state_specs(axes, v, rules)
    return {"params": pspecs, "opt": ospecs}


def param_specs_serving(cfg: ModelConfig, params_shapes, axes, rules: Rules):
    """Serving param specs (same logical rules; fsdp dim per config mode)."""
    return param_specs(axes, params_shapes, rules)


def batch_pspecs(batch_shapes, rules: Rules):
    def one(s):
        logical = ("batch",) + (None,) * (len(s.shape) - 1)
        return rules.spec_for(logical, s.shape)

    return jax.tree_util.tree_map(one, batch_shapes)


# ---- decode-cache specs ---------------------------------------------------- #
def _kv_cache_axes(cfg: ModelConfig, rules: Rules) -> Dict[str, Axes]:
    model_size = rules.axis_size(rules.table.get("kv_heads", ()))
    head_sharded = model_size > 1 and cfg.n_kv_heads % model_size == 0
    seq_tag = None if head_sharded else "kv_seq"
    kv_tag = "kv_heads" if head_sharded else None
    ax = {
        "k": Axes(("layer", "batch", seq_tag, kv_tag, None)),
        "v": Axes(("layer", "batch", seq_tag, kv_tag, None)),
        "slot_pos": Axes(("layer", "batch", seq_tag)),
    }
    if cfg.kv_cache_dtype == "int8":
        ax["k_scale"] = Axes(("layer", "batch", seq_tag, kv_tag))
        ax["v_scale"] = Axes(("layer", "batch", seq_tag, kv_tag))
    return ax


def cache_axes(cfg: ModelConfig, rules: Rules):
    """Axes tree matching init_cache structure."""
    if cfg.is_encdec:
        return {
            "self": _kv_cache_axes(cfg, rules),
            "cross": _kv_cache_axes(cfg, rules),
        }
    entries = []
    for spec in cfg.block_pattern:
        if spec.mixer == "attn":
            entries.append(_kv_cache_axes(cfg, rules))
        elif spec.mixer == "mamba":
            entries.append({
                "conv": Axes(("layer", "batch", None, "act_mlp")),
                "ssm": Axes(("layer", "batch", "act_mlp", None)),
            })
        else:  # rwkv6
            entries.append({
                "shift": Axes(("layer", "batch", None)),
                "wkv": Axes(("layer", "batch", None, None, None)),
            })
    return tuple(entries)


def cache_pspecs(cfg: ModelConfig, cache_shapes, rules: Rules):
    axes = cache_axes(cfg, rules)
    return jax.tree_util.tree_map(
        lambda a, s: rules.spec_for(a.names, s.shape), axes, cache_shapes
    )


# --------------------------------------------------------------------------- #
# Steps.
# --------------------------------------------------------------------------- #
from repro.optim.precision import compute_cast  # C7 policy (noqa: E402)


def _global_norm(tree):
    """L2 norm over every leaf (computed in fp32)."""
    sq = sum(
        jnp.sum(jnp.square(g.astype(jnp.float32)))
        for g in jax.tree_util.tree_leaves(tree)
    )
    return jnp.sqrt(sq)


# Metric names make_train_step knows how to plumb into its metrics dict
# (requested per run via TrainerConfig.metrics / --set trainer.metrics=...).
EXTRA_METRICS = ("grad_norm", "param_norm")


def make_train_step(cfg: ModelConfig, optimizer: Optimizer,
                    rules: Optional[Rules] = None,
                    axes=None, extra_metrics=()) -> Callable:
    api = ModelAPI(cfg)
    M = cfg.microbatches
    unknown = [m for m in extra_metrics if m not in EXTRA_METRICS]
    if unknown:
        raise ValueError(
            f"unknown extra metric(s) {unknown}; supported: {EXTRA_METRICS}"
        )

    def train_step(state, batch):
        with use_rules(rules):
            params, opt_state = state["params"], state["opt"]

            def loss_of(p, mb):
                if axes is not None:
                    p = compute_cast(p, axes, rules, cfg.dtype)
                return api.loss(p, mb)

            if M > 1:
                mb_batch = jax.tree_util.tree_map(
                    lambda a: a.reshape((M, a.shape[0] // M) + a.shape[1:]),
                    batch,
                )

                def mb_step(acc, mb):
                    g_acc, l_acc = acc
                    (loss, metrics), grads = jax.value_and_grad(
                        loss_of, has_aux=True
                    )(params, mb)
                    grads = jax.tree_util.tree_map(
                        lambda a: a.astype(jnp.dtype(cfg.grad_dtype)), grads
                    )
                    g_acc = jax.tree_util.tree_map(
                        lambda x, y: x + y, g_acc, grads
                    )
                    return (g_acc, l_acc + loss), metrics["nll"]

                g0 = jax.tree_util.tree_map(
                    lambda s: jnp.zeros(s.shape, jnp.dtype(cfg.grad_dtype)),
                    jax.eval_shape(lambda p: p, params),
                )
                (grads, loss_sum), nlls = jax.lax.scan(
                    mb_step, (g0, jnp.zeros((), jnp.float32)), mb_batch
                )
                grads = jax.tree_util.tree_map(lambda g: g / M, grads)
                loss = loss_sum / M
                nll = jnp.mean(nlls)
            else:
                (loss, metrics), grads = jax.value_and_grad(
                    loss_of, has_aux=True
                )(params, batch)
                grads = jax.tree_util.tree_map(
                    lambda a: a.astype(jnp.dtype(cfg.grad_dtype)), grads
                )
                nll = metrics["nll"]

            metrics_out = {"loss": loss, "nll": nll}
            if "grad_norm" in extra_metrics:
                metrics_out["grad_norm"] = _global_norm(grads)
            with jax.named_scope("optimizer"):
                new_params, new_opt = optimizer.update(
                    grads, opt_state, params)
            if "param_norm" in extra_metrics:
                metrics_out["param_norm"] = _global_norm(new_params)
            return ({"params": new_params, "opt": new_opt}, metrics_out)

    return train_step


def make_eval_step(cfg: ModelConfig, rules: Optional[Rules] = None):
    """Distributed eval (C4): per-example NLL, padded examples masked out."""
    api = ModelAPI(cfg)
    per_example = api._m.per_example_nll

    def eval_step(params, batch, mask):
        with use_rules(rules):
            nll_ex, _ = per_example(params, cfg, batch)
            return jnp.sum(nll_ex * mask), jnp.sum(mask)

    return eval_step


def make_prefill_step(cfg: ModelConfig, shape: InputShape,
                      rules: Optional[Rules] = None):
    api = ModelAPI(cfg)
    window = cfg.effective_window(shape)

    def prefill_step(params, batch):
        with use_rules(rules):
            return api.prefill(
                params, batch, cache_len=shape.seq_len, window=window
            )

    return prefill_step


def make_decode_step(cfg: ModelConfig, shape: InputShape,
                     rules: Optional[Rules] = None):
    api = ModelAPI(cfg)
    window = cfg.effective_window(shape)

    def decode_step(params, token, cache, pos):
        with use_rules(rules):
            return api.decode(params, token, cache, pos, window=window)

    return decode_step


# ---- serving steps (continuous batching; repro.serve) ---------------------- #
def make_serve_prefill_step(cfg: ModelConfig, rules: Optional[Rules] = None,
                            *, cache_len: int, window=None):
    """Prefill step for the serving path.

    ``prefill_step(params, batch, last_pos)`` returns (logits of each
    example's true final prompt position, decode cache sized
    ``cache_len``). Prompts may be right-padded to one compile shape;
    ``last_pos`` (B,) selects the real last position per example, and the
    returned cache still contains the padded positions' K/V — the caller
    (repro.serve.Engine) masks them via ``serve.cache.invalidate_beyond``
    so padded prefill is exactly equivalent to unpadded prefill.
    """
    api = ModelAPI(cfg)

    def prefill_step(params, batch, last_pos):
        with use_rules(rules):
            return api.prefill(params, batch, cache_len=cache_len,
                               window=window, last_pos=last_pos)

    return prefill_step


def make_serve_chunk_step(cfg: ModelConfig, rules: Optional[Rules] = None,
                          *, window=None, full_logits=False):
    """The paged engine's single compiled program: C tokens per row
    against the paged KV pool — decode rows feed one real token,
    chunked-prefill rows up to C, in the same dispatch. Every prompt
    length maps onto the one (B, C) compile shape, so there are no
    per-length prefill specializations to compile.

    ``full_logits`` returns the head over every fed position ((B, C,
    vocab)) — the speculative engine's verify variant; it is still one
    compiled program, the engine just always asks for the full head."""
    api = ModelAPI(cfg)

    def chunk_step(params, tokens, cache, page_table, pos, n_valid):
        with use_rules(rules):
            return api.decode_chunk(
                params, tokens, cache, page_table, pos, n_valid,
                window=window, full_logits=full_logits)

    return chunk_step


def make_serve_decode_step(cfg: ModelConfig, rules: Optional[Rules] = None,
                           *, window=None):
    """Decode step for the serving path: ``pos`` is a (B,) vector, one
    absolute offset per KV-cache slot, so a single compiled program
    advances every in-flight sequence (continuous batching)."""
    api = ModelAPI(cfg)

    def decode_step(params, token, cache, pos):
        with use_rules(rules):
            return api.decode(params, token, cache, pos, window=window)

    return decode_step
