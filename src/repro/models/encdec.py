"""Encoder-decoder transformer (Whisper-style backbone; also used by the
MLPerf Transformer reproduction with token inputs on the encoder side).

The audio frontend is a STUB per the brief: the encoder consumes
precomputed frame embeddings (B, T, d_model). Positions are sinusoidal
(parameter-free) so one param tree serves every input shape.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist import constrain, p, retag_tree, split_tree, stack_axes
from repro.kernels import paged_pool
from repro.models import layers as L
from repro.models.lm import _is_tagged_tree


def sinusoid(S: int, d: int, dtype) -> jnp.ndarray:
    pos = jnp.arange(S, dtype=jnp.float32)[:, None]
    i = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, 2 * i / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1).astype(dtype)[None]


# --------------------------------------------------------------------------- #
# Init.
# --------------------------------------------------------------------------- #
def _init_enc_layer(cfg, key):
    ks = jax.random.split(key, 2)
    return {
        "norm1": L.init_norm(cfg, cfg.d_model),
        "attn": L.init_attention(cfg, ks[0]),
        "norm2": L.init_norm(cfg, cfg.d_model),
        "ffn": L.init_ffn(cfg, ks[1]),
    }


def _init_dec_layer(cfg, key):
    ks = jax.random.split(key, 3)
    return {
        "norm1": L.init_norm(cfg, cfg.d_model),
        "self_attn": L.init_attention(cfg, ks[0]),
        "norm_x": L.init_norm(cfg, cfg.d_model),
        "cross_attn": L.init_attention(cfg, ks[1], cross=True),
        "norm2": L.init_norm(cfg, cfg.d_model),
        "ffn": L.init_ffn(cfg, ks[2]),
    }


def _stack(init_fn, cfg, key, n):
    proto_vals, proto_axes = split_tree(init_fn(cfg, key))

    def one(k):
        return split_tree(init_fn(cfg, k))[0]

    stacked = jax.vmap(one)(jax.random.split(key, n))
    return retag_tree(stacked, stack_axes(proto_axes))


def init_encdec(cfg: ModelConfig, key):
    ks = jax.random.split(key, 5)
    params = {
        "embed": p(
            jax.random.normal(ks[0], (cfg.vocab, cfg.d_model), jnp.float32)
            * cfg.d_model ** -0.5,
            "vocab", "fsdp",
        ),
        "enc_blocks": _stack(_init_enc_layer, cfg, ks[1], cfg.n_enc_layers),
        "enc_norm": L.init_norm(cfg, cfg.d_model),
        "dec_blocks": _stack(_init_dec_layer, cfg, ks[2], cfg.n_layers),
        "dec_norm": L.init_norm(cfg, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["head"] = p(
            jax.random.normal(ks[3], (cfg.d_model, cfg.vocab), jnp.float32)
            * cfg.d_model ** -0.5,
            "fsdp", "vocab",
        )
    return params


# --------------------------------------------------------------------------- #
# Encoder.
# --------------------------------------------------------------------------- #
def encode(params, cfg: ModelConfig, frames):
    """frames: (B, T, d_model) precomputed embeddings -> (B, T, d)."""
    vals = split_tree(params)[0] if _is_tagged_tree(params) else params
    dt = jnp.dtype(cfg.dtype)
    B, T, _ = frames.shape
    x = frames.astype(dt) + sinusoid(T, cfg.d_model, dt)
    x = constrain(x, "batch", "seq_res", None)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))

    def block_fn(x, bp):
        h = L.apply_norm(bp["norm1"], x, cfg)
        y, _ = L.attention_full(bp["attn"], h, cfg, positions=positions,
                                causal=False)
        x = constrain(x + y, "batch", "seq_res", None)
        h = L.apply_norm(bp["norm2"], x, cfg)
        x = constrain(x + L.apply_ffn(bp["ffn"], h, cfg),
                      "batch", "seq_res", None)
        return x, None

    fn = jax.checkpoint(block_fn) if cfg.remat else block_fn
    x, _ = jax.lax.scan(fn, x, vals["enc_blocks"])
    return L.apply_norm(vals["enc_norm"], x, cfg)


# --------------------------------------------------------------------------- #
# Decoder (teacher forcing / prefill / decode).
# --------------------------------------------------------------------------- #
def _dec_block_full(cfg, bp, x, enc_out, positions, collect_kv=False):
    h = L.apply_norm(bp["norm1"], x, cfg)
    y, kv_self = L.attention_full(bp["self_attn"], h, cfg,
                                  positions=positions, causal=True)
    x = constrain(x + y, "batch", "seq_res", None)
    h = L.apply_norm(bp["norm_x"], x, cfg)
    y, kv_cross = L.attention_full(bp["cross_attn"], h, cfg,
                                   positions=positions, causal=False,
                                   kv_x=enc_out)
    x = constrain(x + y, "batch", "seq_res", None)
    h = L.apply_norm(bp["norm2"], x, cfg)
    x = constrain(x + L.apply_ffn(bp["ffn"], h, cfg), "batch", "seq_res", None)
    if collect_kv:
        return x, (kv_self, kv_cross)
    return x, None


def _head(vals, cfg, x):
    if cfg.tie_embeddings:
        w = vals["embed"].T
    else:
        w = vals["head"]
    return jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype))


def forward(params, cfg: ModelConfig, frames, tokens):
    """Teacher-forced decode over full target. Returns (logits, aux=0)."""
    vals = split_tree(params)[0] if _is_tagged_tree(params) else params
    enc_out = encode(vals, cfg, frames)
    dt = jnp.dtype(cfg.dtype)
    B, S = tokens.shape
    x = jnp.take(vals["embed"], tokens, axis=0).astype(dt)
    x = x + sinusoid(S, cfg.d_model, dt)
    x = constrain(x, "batch", "seq_res", None)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    def block_fn(x, bp):
        x, _ = _dec_block_full(cfg, bp, x, enc_out, positions)
        return x, None

    fn = jax.checkpoint(block_fn) if cfg.remat else block_fn
    x, _ = jax.lax.scan(fn, x, vals["dec_blocks"])
    x = L.apply_norm(vals["dec_norm"], x, cfg)
    return _head(vals, cfg, x), jnp.zeros((), jnp.float32)


def per_example_nll(params, cfg: ModelConfig, batch):
    logits, _ = forward(params, cfg, batch["media"], batch["tokens"])
    tgt = batch["tokens"][:, 1:]
    lg = logits[:, :-1, :].astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, tgt[..., None], axis=-1)[..., 0]
    return (logz - gold).mean(axis=-1), jnp.zeros((), jnp.float32)


def loss_fn(params, cfg: ModelConfig, batch) -> Tuple[jnp.ndarray, Dict]:
    """batch: {"media": (B,T,d) frames, "tokens": (B,S) targets}."""
    nll_ex, _ = per_example_nll(params, cfg, batch)
    nll = nll_ex.mean()
    return nll, {"nll": nll, "aux": jnp.zeros(())}


def init_cache(cfg: ModelConfig, B: int, seq_len: int, window=None):
    """Self-attn ring caches + cross-attn caches for all decoder layers."""
    Ls = min(seq_len, window) if window else seq_len
    self_c = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape),
        L.init_kv_cache(cfg, B, Ls),
    )
    cross_c = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape),
        L.init_kv_cache(cfg, B, cfg.enc_source_len),
    )
    return {"self": self_c, "cross": cross_c}


def init_paged_cache(cfg: ModelConfig, B: int, n_pages: int, page: int):
    """Paged decoder self-attn pools + dense per-slot cross caches.

    Self-attention KV pages like ``lm.init_paged_cache``; cross-attention
    K/V is computed once per request from the encoder output
    (``encode_cross``) and written into its slot of a dense
    ``(n_layers, B, enc_source_len, ...)`` slab — it never grows, so
    paging buys nothing there.
    """
    self_c = paged_pool.init(cfg.n_layers, n_pages, page, cfg.n_kv_heads,
                             cfg.head_dim, cfg.kv_cache_dtype, cfg.dtype)
    cross_c = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape),
        L.init_kv_cache(cfg, B, cfg.enc_source_len),
    )
    return {"self": self_c, "cross": cross_c}


def encode_cross(params, cfg: ModelConfig, frames):
    """Run the encoder and project per-layer cross K/V for one request.

    frames: (B, T, d_model). Returns the cross-cache tree
    (n_layers, B, enc_source_len, ...) the chunk program reads — the
    only encoder work a request ever needs, done once at admission.
    """
    vals = split_tree(params)[0] if _is_tagged_tree(params) else params
    enc_out = encode(vals, cfg, frames)

    def block_fn(carry, bp):
        k = L._qkv(bp["cross_attn"], enc_out, cfg, "k")
        v = L._qkv(bp["cross_attn"], enc_out, cfg, "v")
        return carry, L.cache_from_prefill(cfg, k, v, cfg.enc_source_len)

    _, cross = jax.lax.scan(
        block_fn, jnp.zeros((), jnp.float32), vals["dec_blocks"])
    return cross


def decode_chunk(params, cfg: ModelConfig, tokens, cache, page_table, pos,
                 n_valid, *, window=None, full_logits=False):
    """C decoder tokens per row against paged self-attn KV + static cross
    caches (see ``lm.decode_chunk`` for the batch contract and the
    ``full_logits`` speculative-verify variant)."""
    vals = split_tree(params)[0] if _is_tagged_tree(params) else params
    dt = jnp.dtype(cfg.dtype)
    B, C = tokens.shape
    positions = (jnp.asarray(pos, jnp.int32).reshape(B, 1)
                 + jnp.arange(C, dtype=jnp.int32)[None, :])
    x = jnp.take(vals["embed"], tokens, axis=0).astype(dt)
    x = x + jnp.take(sinusoid_table(cfg, dt), positions, axis=0)

    def block_fn(carry, binp):
        x, cs = carry
        bp, cc, layer = binp
        h = L.apply_norm(bp["norm1"], x, cfg)
        y, cs = L.attention_decode_paged(
            bp["self_attn"], h, cfg, cs, page_table, pos, n_valid,
            layer=layer, window=window)
        x = x + y
        h = L.apply_norm(bp["norm_x"], x, cfg)
        x = x + L.attention_cross_chunk(bp["cross_attn"], h, cfg, cc)
        h = L.apply_norm(bp["norm2"], x, cfg)
        x = x + L.apply_ffn(bp["ffn"], h, cfg)
        return (x, cs), None

    # the self-attention pools ride the carry, as in lm.decode_chunk
    (x, new_self), _ = jax.lax.scan(
        block_fn, (x, cache["self"]),
        (vals["dec_blocks"], cache["cross"],
         jnp.arange(cfg.n_layers, dtype=jnp.int32)))
    x = L.apply_norm(vals["dec_norm"], x, cfg)
    if full_logits:
        return _head(vals, cfg, x), {"self": new_self,
                                     "cross": cache["cross"]}
    logits = _head(vals, cfg, L.gather_last(
        x, jnp.asarray(n_valid, jnp.int32) - 1))
    return logits[:, 0], {"self": new_self, "cross": cache["cross"]}


def prefill(params, cfg: ModelConfig, frames, tokens, *, cache_len=None,
            window=None, last_pos=None):
    """Encode + teacher-force the prompt, building decode caches.

    ``last_pos`` (scalar or (B,) int32): per-example position whose logits
    to return (serving pads prompts to one compile shape; see lm.prefill).
    """
    vals = split_tree(params)[0] if _is_tagged_tree(params) else params
    enc_out = encode(vals, cfg, frames)
    dt = jnp.dtype(cfg.dtype)
    B, S = tokens.shape
    cache_len = cache_len or S
    Ls = min(cache_len, window) if window else cache_len
    x = jnp.take(vals["embed"], tokens, axis=0).astype(dt)
    x = x + sinusoid(S, cfg.d_model, dt)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    def block_fn(x, bp):
        x, (kv_self, kv_cross) = _dec_block_full(
            cfg, bp, x, enc_out, positions, collect_kv=True
        )
        cs = L.cache_from_prefill(cfg, kv_self[0][:, -Ls:], kv_self[1][:, -Ls:], Ls)
        cc = L.cache_from_prefill(cfg, kv_cross[0], kv_cross[1],
                                  cfg.enc_source_len)
        return x, (cs, cc)

    x, (self_c, cross_c) = jax.lax.scan(block_fn, x, vals["dec_blocks"])
    x = L.apply_norm(vals["dec_norm"], x, cfg)
    logits = _head(vals, cfg, L.gather_last(x, last_pos))
    return logits[:, 0], {"self": self_c, "cross": cross_c}


def decode_step(params, cfg: ModelConfig, token, cache, pos, *, window=None):
    vals = split_tree(params)[0] if _is_tagged_tree(params) else params
    dt = jnp.dtype(cfg.dtype)
    x = jnp.take(vals["embed"], token, axis=0).astype(dt)
    # position embedding for the current step (dynamic index); pos may be a
    # (B,) vector (continuous batching: one offset per row)
    posv = jnp.asarray(pos, jnp.int32)
    if posv.ndim:
        x = x + jnp.take(sinusoid_table(cfg, dt), posv, axis=0)[:, None]
    else:
        x = x + jax.lax.dynamic_slice_in_dim(
            sinusoid_table(cfg, dt), posv, 1, axis=0
        )[None]

    def block_fn(x, binp):
        bp, cs, cc = binp
        h = L.apply_norm(bp["norm1"], x, cfg)
        y, ncs = L.attention_decode(bp["self_attn"], h, cfg, cs, pos=pos,
                                    window=window)
        x = x + y
        h = L.apply_norm(bp["norm_x"], x, cfg)
        y, _ = L.attention_decode(bp["cross_attn"], h, cfg, cc,
                                  pos=10**9, cross=True)
        x = x + y
        h = L.apply_norm(bp["norm2"], x, cfg)
        x = x + L.apply_ffn(bp["ffn"], h, cfg)
        return x, ncs

    x, new_self = jax.lax.scan(
        block_fn, x, (vals["dec_blocks"], cache["self"], cache["cross"])
    )
    x = L.apply_norm(vals["dec_norm"], x, cfg)
    logits = _head(vals, cfg, x)
    return logits[:, 0], {"self": new_self, "cross": cache["cross"]}


_SIN_CACHE = {}


def sinusoid_table(cfg: ModelConfig, dtype, max_len: int = 65536):
    return sinusoid(max_len, cfg.d_model, dtype)[0]
