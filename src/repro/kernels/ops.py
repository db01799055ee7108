"""Public jit'd wrappers for the compute hot-spots.

Backend selection lives in ``kernels/dispatch.py``: every op below
registers an :class:`~repro.kernels.dispatch.OpSpec` naming its pure-JAX
implementation, its (lazily imported) Pallas kernel, and capability
flags — ``supports_int8``/``supports_int4`` for quantized operands,
``min_size`` for launch-overhead gates. The public functions here are
thin shims that route through ``dispatch.resolve``.

On TPU the Pallas kernels are used; on CPU (this container) the
memory-safe pure-JAX implementations are used for model execution and
dry-run lowering (so ``cost_analysis`` reflects the real math), while
the Pallas kernels are validated separately with ``interpret=True``
against ``kernels/ref.py``. Set ``REPRO_USE_PALLAS=interpret`` to route
model execution through the Pallas kernels in interpret mode (slow;
used by kernel integration tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import dispatch as _dispatch
from repro.kernels import paged_pool as _paged_pool
from repro.kernels import quant as _quant
from repro.kernels import ref as _ref

# Back-compat alias (pre-registry callers peeked at the env directly).
_pallas_mode = _dispatch.pallas_mode


# --------------------------------------------------------------------------- #
# Running a kernel under a mesh.
# --------------------------------------------------------------------------- #
_Q_AXES = ("batch", None, "act_heads", None)  # (B, S, H, D) queries / KV


def _per_shard(fn, args, names):
    """Call the Pallas kernel ``fn(*args)`` on each device's shard.

    XLA cannot partition a Mosaic kernel, so under a ``use_rules`` scope
    whose mesh has more than one device the call runs inside
    ``shard_map``: batch on the rules' batch axes, heads on the model
    axes (``names`` gives each argument's logical axes; the output is
    laid out like ``args[0]``). A query head's KV head is ``h // G`` on
    every shard only if query and KV heads split alike, so heads stay
    whole when they do not (e.g. fewer KV heads than model shards).
    """
    from repro.dist import current_rules

    rules = current_rules()
    if not _sharded(rules):
        return fn(*args)
    mesh = rules.mesh
    specs = [rules.spec_for(n, jnp.shape(a)) for n, a in zip(names, args)]
    heads = {s[n.index("act_heads")] for s, n in zip(specs, names)
             if "act_heads" in n}
    if len(heads) > 1:
        specs = [P(*(None if a == "act_heads" else e for a, e in zip(n, s)))
                 for s, n in zip(specs, names)]
    return jax.shard_map(fn, mesh=mesh, in_specs=tuple(specs),
                         out_specs=specs[0], check_vma=False)(*args)


def _sharded(rules=None):
    """Whether a kernel call runs per shard under ``shard_map``."""
    from repro.dist import current_rules

    mesh = getattr(rules or current_rules(), "mesh", None)
    return getattr(mesh, "devices", None) is not None and mesh.devices.size > 1


# --------------------------------------------------------------------------- #
# Attention.
# --------------------------------------------------------------------------- #
def attention(q, k, v, *, causal=True, window=None, q_offset=0, k_offset=0,
              scale=None, chunk=512):
    """Multi-head (GQA) attention; flash kernel on TPU, chunked jnp off-TPU.

    q: (B, Sq, H, D); k, v: (B, Sk, K, D). Softmax accumulators in fp32.
    """
    impl, interpret = _dispatch.resolve("attention")
    if interpret is None:
        return impl(
            q, k, v, causal=causal, window=window, q_offset=q_offset,
            k_offset=k_offset, scale=scale, chunk=chunk,
        )
    fn = functools.partial(
        impl, causal=causal, window=window, q_offset=q_offset,
        k_offset=k_offset, scale=scale, interpret=interpret)
    return _per_shard(fn, (q, k, v), (_Q_AXES,) * 3)


def _chunked_attention(q, k, v, *, causal, window, q_offset, k_offset, scale,
                       chunk, block_skip=True):
    """Online-softmax attention over KV chunks (O(S) memory).

    §Perf hillclimb A (block skipping): with static offsets, query chunks
    only visit the KV chunks their causal/window band intersects, instead
    of scanning all of them with masking — for a 32k causal prefill that
    halves attention FLOPs, and for sliding-window prefill it drops them to
    O(S*W). Falls back to the masked full scan for traced offsets
    (sequence-parallel shard_map path).
    """
    B, Sq, H, D = q.shape
    _, Sk, K, _ = k.shape
    G = H // K
    scale = scale if scale is not None else D ** -0.5
    qf_all = (q.astype(jnp.float32) * scale).reshape(B, Sq, K, G, D)

    ck = min(chunk, Sk)
    n_chunks = -(-Sk // ck)
    pad = n_chunks * ck - Sk
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = jnp.moveaxis(
        kp.reshape(B, n_chunks, ck, K, D).astype(jnp.float32), 1, 0)
    vc = jnp.moveaxis(
        vp.reshape(B, n_chunks, ck, K, D).astype(jnp.float32), 1, 0)

    def run_range(qf, q_lo, chunk_lo, chunk_hi):
        """Attend queries qf (B,nq,K,G,D) at positions q_offset+q_lo+i to
        KV chunks [chunk_lo, chunk_hi).

        The body dynamic-indexes into the SHARED kc/vc stacks (scanning
        only chunk indices) — materializing kc[lo:hi] slices per query
        chunk would keep O(n_q^2) KV copies live at once."""
        nq = qf.shape[1]
        qpos = q_offset + q_lo + jnp.arange(nq)

        def body(carry, cidx):
            m, l, acc = carry
            k_i = jax.lax.dynamic_index_in_dim(kc, cidx, 0, keepdims=False)
            v_i = jax.lax.dynamic_index_in_dim(vc, cidx, 0, keepdims=False)
            logits = jnp.einsum("bqkgd,bskd->bqkgs", qf, k_i)
            kidx = cidx * ck + jnp.arange(ck)
            kpos = k_offset + kidx
            mask = (kidx[None, :] < Sk) & (kpos[None, :] >= 0)
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if window is not None:
                mask &= kpos[None, :] > qpos[:, None] - window
            logits = jnp.where(mask[None, :, None, None, :], logits, -1e30)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bqkgs,bskd->bqkgd", p, v_i
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, nq, K, G), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, nq, K, G), jnp.float32)
        acc0 = jnp.zeros((B, nq, K, G, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            body, (m0, l0, acc0), jnp.arange(chunk_lo, chunk_hi)
        )
        return acc / jnp.maximum(l, 1e-30)[..., None]

    skippable = (
        block_skip and causal and isinstance(q_offset, int)
        and isinstance(k_offset, int) and Sq > ck
    )
    if not skippable:
        out = run_range(qf_all, 0, 0, n_chunks)
        return out.reshape(B, Sq, H, D).astype(q.dtype)

    cq = ck  # query chunk = kv chunk size
    n_q = -(-Sq // cq)
    outs = []
    for qi in range(n_q):
        q_lo = qi * cq
        q_hi = min(Sq, q_lo + cq)
        qf = qf_all[:, q_lo:q_hi]
        # band of kv chunks this query chunk can see
        hi_pos = q_offset + q_hi - 1 - k_offset      # newest visible key
        chunk_hi = min(n_chunks, hi_pos // ck + 1)
        if window is not None:
            lo_pos = max(q_offset + q_lo - window + 1 - k_offset, 0)
            chunk_lo = min(max(lo_pos // ck, 0), chunk_hi)
        else:
            chunk_lo = 0
        if chunk_hi <= chunk_lo:
            outs.append(jnp.zeros((B, q_hi - q_lo, K, G, D), jnp.float32))
            continue
        outs.append(run_range(qf, q_lo, chunk_lo, chunk_hi))
    out = jnp.concatenate(outs, axis=1)
    return out.reshape(B, Sq, H, D).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, slot_pos, *, pos, window=None,
                     scale=None, k_scale=None, v_scale=None):
    """Single-token attention against a (possibly ring-buffer) KV cache.

    q: (B, 1, H, D). k_cache/v_cache: (B, L, K, D) in bf16 or int8.
    slot_pos: (B, L) int32 — absolute position stored in each slot (-1 empty).
    k_scale/v_scale: (B, L, K) dequant scales when the cache is int8.
    pos: scalar int32, or (B,) int32 when each batch row decodes at its own
    position (continuous-batching serving: every slot holds an independent
    sequence at an independent offset).
    """
    impl, _ = _dispatch.resolve("decode_attention")
    return impl(q, k_cache, v_cache, slot_pos, pos=pos, window=window,
                scale=scale, k_scale=k_scale, v_scale=v_scale)


def _decode_attention_jnp(q, k_cache, v_cache, slot_pos, *, pos, window,
                          scale, k_scale, v_scale):
    B, _, H, D = q.shape
    _, L, K, _ = k_cache.shape
    G = H // K
    scale = scale if scale is not None else D ** -0.5
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)
    if k_scale is not None:
        kf = kf * k_scale[..., None].astype(jnp.float32)
    if v_scale is not None:
        vf = vf * v_scale[..., None].astype(jnp.float32)
    qf = (q.astype(jnp.float32) * scale).reshape(B, K, G, D)
    logits = jnp.einsum("bkgd,blkd->bkgl", qf, kf)  # (B,K,G,L)
    # (1,1) for scalar pos, (B,1) for per-row pos; both broadcast over (B,L)
    posb = jnp.asarray(pos, jnp.int32).reshape(-1, 1)
    valid = (slot_pos >= 0) & (slot_pos <= posb)
    if window is not None:
        valid &= slot_pos > posb - window
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgl,blkd->bkgd", probs, vf)
    return out.reshape(B, 1, H, D).astype(q.dtype)


def paged_attention(q, pool, page_table, *, pos, n_valid, layer,
                    window=None, scale=None):
    """Ragged decode attention against layer ``layer`` of a paged KV pool.

    q: (B, C, H, D) — C tokens per row this step (decode rows feed 1,
    chunked-prefill rows up to C; ``n_valid`` masks the rest).
    pool: the chunk program's pool stacked over layers, bf16, int8 or
    int4-packed (``kernels/paged_pool.py``); the new tokens' K/V are
    already written into their pages (``paged_pool.insert`` runs before
    attention). page_table: (B, max_pages) int32 physical page ids (-1
    unmapped). pos: (B,) absolute position of each row's first token
    this step.

    On TPU (or REPRO_USE_PALLAS=interpret) the Pallas kernel visits only
    the pages each row occupies, dequantizing in-kernel with fp32
    accumulation; the jnp fallback gathers the mapped pages and masks —
    O(max_len) per row, correctness-equal. Both read the layer's pages
    as ``paged_pool.layer_view`` hands them.
    """
    D = q.shape[-1]
    quantized = _paged_pool.geometry(pool, D)[3]
    impl, interpret = _dispatch.resolve("paged_attention", quantized=quantized)
    kp, vp, kp_scale, vp_scale, page_table = _paged_pool.layer_view(
        pool, page_table, layer, D, kernel=interpret is not None,
        sharded=_sharded())
    if interpret is None:
        return impl(q, kp, vp, page_table, pos=pos, n_valid=n_valid,
                    window=window, scale=scale, kp_scale=kp_scale,
                    vp_scale=vp_scale)

    def fn(q, kp, vp, page_table, pos, n_valid, *scales):
        ks, vs = scales or (None, None)
        return impl(q, kp, vp, page_table, pos=pos, n_valid=n_valid,
                    window=window, scale=scale, kp_scale=ks, vp_scale=vs,
                    interpret=interpret)

    pages = (None, "act_heads", None, None)
    args = (q, kp, vp, page_table, pos, n_valid)
    names = (_Q_AXES, pages, pages, ("batch", None), ("batch",), ("batch",))
    if kp_scale is not None:
        args += (kp_scale, vp_scale)
        names += (pages[:3], pages[:3])
    return _per_shard(fn, args, names)


def _paged_attention_jnp(q, kp, vp, page_table, *, pos, n_valid, window,
                         scale, kp_scale, vp_scale):
    B, C, H, D = q.shape
    P, K, page, hd = kp.shape
    G = H // K
    scale = scale if scale is not None else D ** -0.5
    npg = page_table.shape[1]
    pt = jnp.asarray(page_table, jnp.int32)
    safe = jnp.clip(pt, 0, P - 1)
    if kp_scale is not None:
        # int8 or int4-packed pool: dequantize the gathered pages
        # (unpacks nibbles when hd == D // 2).
        kf = _quant.dequantize(kp[safe], kp_scale[safe], D)
        vf = _quant.dequantize(vp[safe], vp_scale[safe], D)
    else:
        kf = kp[safe].astype(jnp.float32)  # (B, npg, K, page, hd)
        vf = vp[safe].astype(jnp.float32)
    kf = jnp.swapaxes(kf, 2, 3).reshape(B, npg * page, K, D)
    vf = jnp.swapaxes(vf, 2, 3).reshape(B, npg * page, K, D)
    qf = (q.astype(jnp.float32) * scale).reshape(B, C, K, G, D)
    logits = jnp.einsum("bckgd,blkd->bckgl", qf, kf)
    kpos = jnp.arange(npg * page, dtype=jnp.int32)
    posv = jnp.asarray(pos, jnp.int32).reshape(B)
    qpos = posv[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    lim = posv + jnp.asarray(n_valid, jnp.int32).reshape(B)
    mapped = jnp.repeat(pt >= 0, page, axis=1)  # (B, L)
    valid = mapped[:, None, :] & (kpos[None, None, :] < lim[:, None, None])
    valid &= kpos[None, None, :] <= qpos[:, :, None]
    if window is not None:
        valid &= kpos[None, None, :] > qpos[:, :, None] - window
    logits = jnp.where(valid[:, :, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bckgl,blkd->bckgd", probs, vf)
    return out.reshape(B, C, H, D).astype(q.dtype)


# --------------------------------------------------------------------------- #
# LSTM cell (GNMT hot spot, C9).
# --------------------------------------------------------------------------- #
def lstm_cell(x_proj, h_prev, c_prev, w_h, b):
    impl, interpret = _dispatch.resolve("lstm_cell")
    if interpret is None:
        return impl(x_proj, h_prev, c_prev, w_h, b)
    return impl(x_proj, h_prev, c_prev, w_h, b, interpret=interpret)


# --------------------------------------------------------------------------- #
# LARS fused update (C1/C6 hot spot).
# --------------------------------------------------------------------------- #
def lars_update(w, g, m, *, lr, weight_decay, momentum, eta, eps=1e-9,
                scaled_momentum=True):
    impl, interpret = _dispatch.resolve("lars_update", size=w.size)
    kw = dict(lr=lr, weight_decay=weight_decay, momentum=momentum, eta=eta,
              eps=eps, scaled_momentum=scaled_momentum)
    if interpret is None:
        return impl(w, g, m, **kw)
    return impl(w, g, m, interpret=interpret, **kw)


# --------------------------------------------------------------------------- #
# MoE gating (top-k + capacity dispatch).
# --------------------------------------------------------------------------- #
def moe_gating(x, router_w, *, top_k, capacity):
    impl, _ = _dispatch.resolve("moe_gating")
    return impl(x, router_w, top_k=top_k, capacity=capacity)


# --------------------------------------------------------------------------- #
# Grouped matmul of the dropless MoE layer.
# --------------------------------------------------------------------------- #
def moe_gmm(lhs, rhs, group_sizes):
    """Rows grouped by expert, each group times its expert's matrix.

    lhs: (m, k), its rows sorted by group; rhs: (G, k, n); group_sizes:
    (G,) int32 with sum <= m. Row r of group g gives lhs[r] @ rhs[g]
    (float32 accumulation, lhs's dtype out); rows past the last group
    are unspecified (the jnp implementation writes zeros, the kernel
    leaves them unwritten), so the caller masks them. On TPU the Pallas
    kernel (``kernels/moe_gmm.py``, named ``moe_gmm`` in a trace) visits
    only the row tiles the groups cover.
    """
    impl, interpret = _dispatch.resolve("moe_gmm")
    if interpret is None:
        return impl(lhs, rhs, group_sizes)
    return impl(lhs, rhs, group_sizes, interpret=interpret)


def _moe_gmm_jnp(lhs, rhs, group_sizes):
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=jnp.float32
                              ).astype(lhs.dtype)


# --------------------------------------------------------------------------- #
# Mamba selective scan.
# --------------------------------------------------------------------------- #
def mamba_scan(u, dt, A, B, C, D):
    """lax.scan selective scan: O(S) memory, sequential over time.

    Shapes as in kernels.ref.mamba_scan. Returns (y, final_state).
    """
    impl, interpret = _dispatch.resolve("mamba_scan")
    if interpret is None:
        return impl(u, dt, A, B, C, D)
    return impl(u, dt, A, B, C, D, interpret=interpret)


def _mamba_scan_jnp(u, dt, A, B, C, D):
    u32 = u.astype(jnp.float32)
    dt32 = dt.astype(jnp.float32)
    A32 = A.astype(jnp.float32)
    B32 = B.astype(jnp.float32)
    C32 = C.astype(jnp.float32)
    D32 = D.astype(jnp.float32)
    Bt, S, Di = u.shape
    N = A.shape[-1]

    def step(h, inp):
        u_t, dt_t, B_t, C_t = inp  # (Bt,Di), (Bt,Di), (Bt,N), (Bt,N)
        da = jnp.exp(dt_t[..., None] * A32[None])  # (Bt,Di,N)
        h = da * h + dt_t[..., None] * B_t[:, None, :] * u_t[..., None]
        y = jnp.einsum("bdn,bn->bd", h, C_t) + D32 * u_t
        return h, y

    from repro.models.scan_utils import chunked_scan

    h0 = jnp.zeros((Bt, Di, N), jnp.float32)
    xs = (
        jnp.moveaxis(u32, 1, 0),
        jnp.moveaxis(dt32, 1, 0),
        jnp.moveaxis(B32, 1, 0),
        jnp.moveaxis(C32, 1, 0),
    )
    # chunked+checkpointed: a plain scan would stash (S,Bt,Di,N) fp32 for
    # the backward pass (gigabytes per layer at 4k tokens).
    h, ys = chunked_scan(step, h0, xs, chunk=256)
    y = jnp.moveaxis(ys, 0, 1).astype(u.dtype)
    return y, h


def mamba_step(h, u_t, dt_t, A, B_t, C_t, D):
    """Single decode step of the selective scan. h: (Bt, Di, N)."""
    da = jnp.exp(dt_t.astype(jnp.float32)[..., None] * A.astype(jnp.float32))
    h = da * h + dt_t.astype(jnp.float32)[..., None] * B_t.astype(jnp.float32)[
        :, None, :
    ] * u_t.astype(jnp.float32)[..., None]
    y = jnp.einsum("bdn,bn->bd", h, C_t.astype(jnp.float32)) + D.astype(
        jnp.float32
    ) * u_t.astype(jnp.float32)
    return h, y.astype(u_t.dtype)


# --------------------------------------------------------------------------- #
# Registry: one OpSpec per hot-spot. Capability flags route quantized
# calls; min_size keeps tiny tensors off the kernel-launch path.
# --------------------------------------------------------------------------- #
_dispatch.register(
    name="attention",
    jnp=_chunked_attention,
    # Forward is the Pallas kernel; its custom VJP's backward is an XLA
    # computation that recomputes the probabilities from the kernel's
    # saved log-sum-exp, tile by tile — a stated choice, not a fallback.
    pallas="repro.kernels.flash_attention:flash_attention",
)
_dispatch.register(
    name="decode_attention",
    jnp=_decode_attention_jnp,  # slab-cache decode; no kernel (paged is the
                                # serving path, slab stays oracle-grade jnp)
)
_dispatch.register(
    name="paged_attention",
    jnp=_paged_attention_jnp,
    pallas="repro.kernels.paged_attention:paged_attention",
    supports_int8=True,
    supports_int4=True,
)
_dispatch.register(
    name="lstm_cell",
    jnp=_ref.lstm_cell,
    pallas="repro.kernels.lstm_cell:lstm_cell",
)
_dispatch.register(
    name="lars_update",
    jnp=_ref.lars_update,
    pallas="repro.kernels.lars:lars_update",
    min_size=1024,  # below this the fused-update win loses to launch cost
)
_dispatch.register(
    name="moe_gating",
    jnp=_ref.moe_gating,
)
_dispatch.register(
    name="moe_gmm",
    jnp=_moe_gmm_jnp,
    pallas="repro.kernels.moe_gmm:moe_gmm",
)
_dispatch.register(
    name="mamba_scan",
    jnp=_mamba_scan_jnp,
    pallas="repro.kernels.mamba:mamba_scan",
)
