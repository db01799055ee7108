"""Decoder-only language model covering dense / MoE / SSM / hybrid / VLM
families via ``ModelConfig.block_pattern``.

Layer weights are stacked over repeat-blocks on a leading 'layer' axis and
the forward pass scans over them (``jax.lax.scan`` + optional per-block
remat), so even the 72-layer 398B config lowers to a compact HLO.

Modality frontends are stubs per the brief: ``media`` embeddings of shape
(B, n_media, d_model) are consumed directly (prepended to token embeds).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist import constrain, p, retag_tree, split_tree, stack_axes
from repro.kernels import paged_pool
from repro.models import layers as L


# --------------------------------------------------------------------------- #
# Init.
# --------------------------------------------------------------------------- #
def _init_block_pos(cfg: ModelConfig, spec, key):
    ks = jax.random.split(key, 4)
    prm = {"norm1": L.init_norm(cfg, cfg.d_model)}
    if cfg.post_norms:
        prm["post_norm1"] = L.init_norm(cfg, cfg.d_model)
        if spec.ffn != "none":
            prm["post_norm2"] = L.init_norm(cfg, cfg.d_model)
    if spec.mixer == "attn":
        prm["mixer"] = L.init_attention(cfg, ks[0])
    elif spec.mixer == "mamba":
        prm["mixer"] = L.init_mamba(cfg, ks[0])
    elif spec.mixer == "rwkv6":
        prm["mixer"] = L.init_rwkv6(cfg, ks[0])
    else:
        raise ValueError(spec.mixer)
    if spec.ffn != "none":
        prm["norm2"] = L.init_norm(cfg, cfg.d_model)
        prm["ffn"] = (
            L.init_moe(cfg, ks[1]) if spec.ffn == "moe" else L.init_ffn(cfg, ks[1])
        )
    return prm


def _init_stacked_blocks(cfg: ModelConfig, key):
    """Per pattern position: params stacked over n_blocks (leading axis)."""
    out = []
    for j, spec in enumerate(cfg.block_pattern):
        kj = jax.random.fold_in(key, j)
        proto_vals, proto_axes = split_tree(_init_block_pos(cfg, spec, kj))

        def one(k, _spec=spec):
            vals, _ = split_tree(_init_block_pos(cfg, _spec, k))
            return vals

        keys = jax.random.split(kj, cfg.n_blocks)
        stacked = jax.vmap(one)(keys)
        out.append(retag_tree(stacked, stack_axes(proto_axes)))
    return tuple(out)


def init_lm(cfg: ModelConfig, key):
    """Returns tagged params pytree (leaves = (array, Axes))."""
    ks = jax.random.split(key, 4)
    params = {
        "embed": p(
            jax.random.normal(ks[0], (cfg.vocab, cfg.d_model), jnp.float32)
            * cfg.d_model ** -0.5,
            "vocab",
            "fsdp",
        ),
        "blocks": _init_stacked_blocks(cfg, ks[1]),
        "final_norm": L.init_norm(cfg, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["head"] = p(
            jax.random.normal(ks[2], (cfg.d_model, cfg.vocab), jnp.float32)
            * cfg.d_model ** -0.5,
            "fsdp",
            "vocab",
        )
    return params


# --------------------------------------------------------------------------- #
# Embedding / head / positions.
# --------------------------------------------------------------------------- #
def _embed(params, cfg: ModelConfig, tokens):
    table = params["embed"]
    table = table[0] if isinstance(table, tuple) else table
    # Cast + keep the table vocab-sharded (replicating the fsdp dim) so the
    # gather partitions as local-gather+mask+psum instead of an fp32
    # all-gather of the whole table.
    table = constrain(table.astype(jnp.dtype(cfg.dtype)), "vocab", None)
    x = jnp.take(table, tokens, axis=0)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


def _head_weight(params, cfg: ModelConfig, dtype):
    if cfg.tie_embeddings:
        w = params["embed"]
        w = w[0] if isinstance(w, tuple) else w
        return constrain(w.astype(dtype), "vocab", None).T
    w = params["head"]
    w = w[0] if isinstance(w, tuple) else w
    return constrain(w.astype(dtype), None, "vocab")


def _head(params, cfg: ModelConfig, x):
    logits = jnp.einsum("bsd,dv->bsv", x, _head_weight(params, cfg, x.dtype))
    return constrain(logits, "batch", None, "act_mlp")


def _positions(cfg: ModelConfig, B: int, S: int, n_media: int = 0):
    """Token positions; M-RoPE gives media tokens (t,h,w) grid coords."""
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    if cfg.rope != "mrope":
        return pos
    if n_media == 0:
        return jnp.broadcast_to(pos[..., None], (B, S, 3))
    # Media tokens get (t=0, h, w) grid coords; text tokens use their
    # absolute index on all three streams (keeps decode_step — which only
    # knows the absolute position — consistent with the full forward).
    side = max(1, int(n_media ** 0.5))
    idx = jnp.arange(S, dtype=jnp.int32)
    is_media = idx < n_media
    t = jnp.where(is_media, 0, idx)
    h = jnp.where(is_media, idx // side, idx)
    w = jnp.where(is_media, idx % side, idx)
    p3 = jnp.stack([t, h, w], axis=-1)
    return jnp.broadcast_to(p3[None], (B, S, 3))


# --------------------------------------------------------------------------- #
# Block application (shared by train/prefill and decode).
# --------------------------------------------------------------------------- #
import os as _os

# §Perf hillclimb C: nested remat — checkpoint each SUBLAYER inside the
# (already-rematted) block so one sublayer's backward working set is live
# at a time instead of the whole 8-layer block's.
_NESTED_REMAT = _os.environ.get("REPRO_NESTED_REMAT", "0") == "1"  # refuted: see EXPERIMENTS.md §Perf C1


def _maybe_ckpt(fn):
    return jax.checkpoint(fn) if _NESTED_REMAT else fn


def _window(spec, window):
    """A layer's attention window: its own, else the model-wide one."""
    return spec.window if spec.window is not None else window


def _post(lp, name, y, cfg: ModelConfig):
    """A sublayer's output, RMSNormed before the residual add where the
    config sandwiches its sublayers (``post_norms``)."""
    return L.apply_norm(lp[name], y, cfg) if cfg.post_norms else y


def _dropless(cfg: ModelConfig, serving: bool) -> bool:
    """The serving path always runs the dropless MoE layer; training and
    the full forward keep the capacity dispatch where it computes the
    config's routing (``MoEConfig.capacity_dispatch_ok``)."""
    return serving or not cfg.moe.capacity_dispatch_ok


def _ffn(lp, h, cfg: ModelConfig, spec, *, dropless, n_valid=None):
    """(y, aux, routed): the layer's FFN; routed is the held experts'
    assignment counts of a dropless MoE layer, else None."""
    if spec.ffn != "moe":
        return L.apply_ffn(lp["ffn"], h, cfg), jnp.zeros((), jnp.float32), None
    if dropless:
        y, routed = L.apply_moe_dropless(lp["ffn"], h, cfg, n_valid)
        return y, jnp.zeros((), jnp.float32), routed
    y, aux = L.apply_moe(lp["ffn"], h, cfg)
    return y, aux, None


def _apply_block_full(cfg: ModelConfig, bparams, x, *, positions, window,
                      collect_kv: bool):
    """One repeat-block, full-sequence. Returns (x, aux_loss, kv_list).
    ``collect_kv`` (prefill, a serving path) also runs MoE dropless."""
    aux = jnp.zeros((), jnp.float32)
    kvs = []
    dropless = cfg.uses_moe and _dropless(cfg, collect_kv)
    for j, spec in enumerate(cfg.block_pattern):
        lp = bparams[j]

        def mixer_fn(lp, x, _spec=spec):
            h = L.apply_norm(lp["norm1"], x, cfg)
            if _spec.mixer == "attn":
                return L.attention_full(
                    lp["mixer"], h, cfg, positions=positions,
                    window=_window(_spec, window), rope=_spec.rope
                )
            if _spec.mixer == "mamba":
                return L.apply_mamba(lp["mixer"], h, cfg)
            return L.apply_rwkv6(lp["mixer"], h, cfg)

        y, kv = (mixer_fn if collect_kv else _maybe_ckpt(mixer_fn))(lp, x)
        if collect_kv:
            kvs.append(kv)
        x = constrain(x + _post(lp, "post_norm1", y, cfg),
                      "batch", "seq_res", None)
        if spec.ffn != "none":

            def ffn_fn(lp, x, _spec=spec):
                h = L.apply_norm(lp["norm2"], x, cfg)
                y, a, _ = _ffn(lp, h, cfg, _spec, dropless=dropless)
                return _post(lp, "post_norm2", y, cfg), a

            y, a = _maybe_ckpt(ffn_fn)(lp, x)
            aux = aux + a
            x = constrain(x + y, "batch", "seq_res", None)
    return x, aux, kvs


def forward_hidden(params, cfg: ModelConfig, tokens, *, media=None,
                   window=None):
    """Full-sequence forward up to the final norm (no output projection).

    Returns (hidden (B,S,d), aux_loss).
    """
    vals = split_tree(params)[0] if _is_tagged_tree(params) else params
    x = _embed(vals, cfg, tokens)
    n_media = 0
    if media is not None:
        media = media.astype(x.dtype)
        x = jnp.concatenate([media, x], axis=1)
        n_media = media.shape[1]
    B, S, _ = x.shape
    x = constrain(x, "batch", "seq_res", None)
    positions = _positions(cfg, B, S, n_media)

    def block_fn(x, bparams):
        x, aux, _ = _apply_block_full(
            cfg, bparams, x, positions=positions, window=window,
            collect_kv=False,
        )
        return x, aux

    fn = jax.checkpoint(block_fn) if cfg.remat else block_fn
    x, auxs = jax.lax.scan(fn, x, vals["blocks"])
    x = L.apply_norm(vals["final_norm"], x, cfg)
    return x, jnp.sum(auxs)


def forward(params, cfg: ModelConfig, tokens, *, media=None, window=None):
    """Full-sequence forward. Returns (logits (B,S,vocab), aux_loss)."""
    vals = split_tree(params)[0] if _is_tagged_tree(params) else params
    x, aux = forward_hidden(vals, cfg, tokens, media=media, window=window)
    return _head(vals, cfg, x), aux


def _is_tagged_tree(params) -> bool:
    from repro.dist.sharding import _is_tagged

    leaves = jax.tree_util.tree_leaves(params, is_leaf=_is_tagged)
    return bool(leaves) and _is_tagged(leaves[0])


def _chunked_ce(vals, cfg: ModelConfig, hidden, targets):
    """Per-example summed CE, computed in sequence chunks so the full fp32
    logits tensor (B,S,vocab) is never materialized.

    hidden: (B, S, d) positions aligned with ``targets`` (B, S).
    Returns (B,) summed nll.
    """
    B, S, d = hidden.shape
    c = min(cfg.loss_chunk, S)
    n_chunks = -(-S // c)
    pad = n_chunks * c - S
    h = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
    t = jnp.pad(targets, ((0, 0), (0, pad)))
    valid = jnp.pad(jnp.ones((B, S), jnp.float32), ((0, 0), (0, pad)))
    h = h.reshape(B, n_chunks, c, d)
    t = t.reshape(B, n_chunks, c)
    valid = valid.reshape(B, n_chunks, c)

    @jax.checkpoint  # recompute chunk logits in backward: never store B,S,V
    def body(acc, inp):
        h_i, t_i, v_i = inp  # (B,c,d), (B,c), (B,c)
        lg = _head_chunk(vals, cfg, h_i).astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, t_i[..., None], axis=-1)[..., 0]
        return acc + jnp.sum((logz - gold) * v_i, axis=-1), None

    acc0 = jnp.zeros((B,), jnp.float32)
    xs = (jnp.moveaxis(h, 1, 0), jnp.moveaxis(t, 1, 0),
          jnp.moveaxis(valid, 1, 0))
    acc, _ = jax.lax.scan(body, acc0, xs)
    return acc


def _head_chunk(vals, cfg: ModelConfig, x):
    logits = jnp.einsum("bsd,dv->bsv", x, _head_weight(vals, cfg, x.dtype))
    return constrain(logits, "batch", None, "act_mlp")


def per_example_nll(params, cfg: ModelConfig, batch) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(nll (B,), aux scalar) — per-example for masked distributed eval (C4)."""
    vals = split_tree(params)[0] if _is_tagged_tree(params) else params
    tokens = batch["tokens"]
    media = batch.get("media")
    hidden, aux = forward_hidden(vals, cfg, tokens, media=media)
    n_media = 0 if media is None else media.shape[1]
    # predict token t+1 from hidden at text position t
    h = hidden[:, n_media:-1, :]
    tgt = tokens[:, 1:]
    nll_sum = _chunked_ce(vals, cfg, h, tgt)
    return nll_sum / tgt.shape[1], aux


def loss_fn(params, cfg: ModelConfig, batch) -> Tuple[jnp.ndarray, Dict]:
    """Next-token cross entropy (fp32) + MoE aux loss.

    batch: {"tokens": (B,S) int32, optional "media": (B,n,d)}. Media tokens
    are prepended; loss only counts text positions.
    """
    nll_ex, aux = per_example_nll(params, cfg, batch)
    nll = nll_ex.mean()
    total = nll + (cfg.moe.aux_loss_weight * aux if cfg.uses_moe else 0.0)
    return total, {"nll": nll, "aux": aux}


# --------------------------------------------------------------------------- #
# Serving: cache init, prefill, decode.
# --------------------------------------------------------------------------- #
def _attn_cache_len(cfg: ModelConfig, seq_len: int, window) -> int:
    return min(seq_len, window) if window else seq_len


def init_cache(cfg: ModelConfig, B: int, seq_len: int, window=None):
    """Decode cache: per pattern position, stacked over n_blocks."""
    entries = []
    L_attn = _attn_cache_len(cfg, seq_len, window)
    for spec in cfg.block_pattern:
        if spec.mixer == "attn":
            e = L.init_kv_cache(cfg, B, L_attn)
        elif spec.mixer == "mamba":
            e = L.init_mamba_cache(cfg, B)
        else:
            e = L.init_rwkv6_cache(cfg, B)
        entries.append(
            jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (cfg.n_blocks,) + a.shape), e
            )
        )
    return tuple(entries)


def init_paged_cache(cfg: ModelConfig, n_pages: int, page: int):
    """Paged decode cache: a physical page pool per pattern position,
    stacked over n_blocks. Attention-only stacks — recurrent mixers
    carry per-slot state, not KV, and stay on the slab layout."""
    for spec in cfg.block_pattern:
        if spec.mixer != "attn":
            raise ValueError(
                f"paged KV cache requires an attention-only stack; "
                f"{cfg.name} has a {spec.mixer!r} mixer")
    return tuple(paged_pool.init(cfg.n_blocks, n_pages, page, cfg.n_kv_heads,
                                 cfg.head_dim, cfg.kv_cache_dtype, cfg.dtype)
                 for _ in cfg.block_pattern)


def decode_chunk(params, cfg: ModelConfig, tokens, cache, page_table, pos,
                 n_valid, *, window=None, full_logits=False):
    """C tokens per row against the paged cache — the serving engine's
    single compiled program (chunked prefill + batched decode mixed).

    tokens: (B, C) int32 — row b feeds ``n_valid[b]`` real tokens
    starting at absolute position ``pos[b]`` (decode rows feed 1, the
    rest padding). page_table: (B, max_pages) int32. Returns (logits of
    each row's last valid token (B, vocab), new_cache) — or, with
    ``full_logits``, the head over every fed position ((B, C, vocab);
    positions past ``n_valid`` are garbage the caller masks). The
    speculative verify step uses the full head: position i's logits
    score the draft token fed at i+1.

    Each layer attends with its own window and rotary setting
    (``LayerSpec``; ``window`` is the model-wide default). MoE layers run
    dropless and mask positions past ``n_valid``; a stack with MoE layers
    returns a third result, ``routed`` (n_blocks, MoE layers of the
    pattern, n_held) int32: the assignments each held expert computed.
    """
    vals = split_tree(params)[0] if _is_tagged_tree(params) else params
    x = _embed(vals, cfg, tokens)
    x = constrain(x, "batch", None, None)

    def block_fn(carry, binp):
        x, cache = carry
        bparams, layer = binp
        new_entries, routed = [], []
        for j, spec in enumerate(cfg.block_pattern):
            lp = bparams[j]
            h = L.apply_norm(lp["norm1"], x, cfg)
            y, nc = L.attention_decode_paged(
                lp["mixer"], h, cfg, cache[j], page_table, pos, n_valid,
                layer=layer, window=_window(spec, window), rope=spec.rope)
            new_entries.append(nc)
            x = x + _post(lp, "post_norm1", y, cfg)
            if spec.ffn != "none":
                h = L.apply_norm(lp["norm2"], x, cfg)
                y, _, r = _ffn(lp, h, cfg, spec, dropless=True,
                               n_valid=n_valid)
                if r is not None:
                    routed.append(r)
                x = x + _post(lp, "post_norm2", y, cfg)
        return (x, tuple(new_entries)), ((jnp.stack(routed),) if routed
                                         else ())

    # The stacked pools ride the carry: each layer writes its rows in
    # place and attends over the whole stack, so no layer's pool is ever
    # sliced out or copied back.
    (x, new_cache), routed = jax.lax.scan(
        block_fn, (x, cache),
        (vals["blocks"], jnp.arange(cfg.n_blocks, dtype=jnp.int32)))
    x = L.apply_norm(vals["final_norm"], x, cfg)
    if full_logits:
        return (_head(vals, cfg, x), new_cache) + routed
    logits = _head(vals, cfg, L.gather_last(x, jnp.asarray(
        n_valid, jnp.int32) - 1))
    return (logits[:, 0], new_cache) + routed


def prefill(params, cfg: ModelConfig, tokens, *, media=None, cache_len=None,
            window=None, last_pos=None):
    """Forward over the prompt, building the decode cache.

    Returns (last-position logits (B,vocab), cache). ``last_pos`` (scalar or
    (B,) int32) selects which position's logits to return per example —
    the serving path right-pads prompts to a fixed compile shape and reads
    the logits of each prompt's true final token (causality makes the
    positions up to it identical to an unpadded prefill).
    """
    vals = split_tree(params)[0] if _is_tagged_tree(params) else params
    x = _embed(vals, cfg, tokens)
    if media is not None:
        x = jnp.concatenate([media.astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    cache_len = cache_len or S
    L_attn = _attn_cache_len(cfg, cache_len, window)
    positions = _positions(cfg, B, S, 0 if media is None else media.shape[1])

    def block_fn(x, bparams):
        x, aux, kvs = _apply_block_full(
            cfg, bparams, x, positions=positions, window=window,
            collect_kv=True,
        )
        caches = []
        for spec, kv in zip([s for s in cfg.block_pattern], kvs):
            if spec.mixer == "attn":
                k, v = kv
                caches.append(L.cache_from_prefill(cfg, k[:, -L_attn:],
                                                   v[:, -L_attn:], L_attn))
            else:
                caches.append(_state_to_cache(cfg, spec, kv, x.dtype))
        return x, tuple(caches)

    x, caches = jax.lax.scan(block_fn, x, vals["blocks"])
    x = L.apply_norm(vals["final_norm"], x, cfg)
    logits = _head(vals, cfg, L.gather_last(x, last_pos))
    return logits[:, 0], caches


def _state_to_cache(cfg, spec, state, dtype):
    if spec.mixer == "mamba":
        return {"conv": state["conv"], "ssm": state["ssm"]}
    return {"shift": state["shift"].astype(jnp.dtype(cfg.dtype)),
            "wkv": state["wkv"]}


def decode_step(params, cfg: ModelConfig, token, cache, pos, *, window=None):
    """One decode step. token: (B,1) int32; pos: absolute position —
    scalar int32, or (B,) int32 when each row is an independent sequence
    at its own offset (continuous-batching serving).

    Returns (logits (B,vocab), new_cache).
    """
    vals = split_tree(params)[0] if _is_tagged_tree(params) else params
    x = _embed(vals, cfg, token)
    x = constrain(x, "batch", None, None)

    def block_fn(x, binp):
        bparams, bcache = binp
        new_entries = []
        for j, spec in enumerate(cfg.block_pattern):
            lp = bparams[j]
            h = L.apply_norm(lp["norm1"], x, cfg)
            if spec.mixer == "attn":
                y, nc = L.attention_decode(
                    lp["mixer"], h, cfg, bcache[j], pos=pos,
                    window=_window(spec, window), rope=spec.rope
                )
            elif spec.mixer == "mamba":
                y, nc = L.apply_mamba_step(lp["mixer"], h, cfg, bcache[j])
            else:
                y, nc = L.apply_rwkv6_step(lp["mixer"], h, cfg, bcache[j])
            new_entries.append(nc)
            x = x + _post(lp, "post_norm1", y, cfg)
            if spec.ffn != "none":
                h = L.apply_norm(lp["norm2"], x, cfg)
                y, _, _ = _ffn(lp, h, cfg, spec, dropless=True)
                x = x + _post(lp, "post_norm2", y, cfg)
        return x, tuple(new_entries)

    x, new_cache = jax.lax.scan(block_fn, x, (vals["blocks"], cache))
    x = L.apply_norm(vals["final_norm"], x, cfg)
    logits = _head(vals, cfg, x)
    return logits[:, 0], new_cache
