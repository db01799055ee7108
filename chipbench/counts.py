"""Operations and bytes that the algorithm needs, from shapes alone.

These are the yardstick's counts, not the program's: a kernel that
reads a KV page once per query head, or a step that recomputes its
forward pass, does more work than counted here, and its roofline share
or MFU shows that as lost time. Attention counts each causal
(query, key) pair once; grouped-query attention reads each KV head's
keys and values once. Counts take the configuration's dims
(``chipbench.weights.dims``).
"""
from __future__ import annotations

from typing import Iterable, Tuple

from chipbench.weights import dims

BF16 = 2


def _matmul_params(n: dict) -> int:
    """Weights one token multiplies through in one layer."""
    d, f, H, K, hd = n["d"], n["f"], n["H"], n["K"], n["hd"]
    return d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * f


def _causal_pairs(pos: int, n: int) -> int:
    """(query, key) pairs of n queries at positions pos..pos+n-1."""
    return n * pos + n * (n + 1) // 2


# --------------------------------------------------------------------------- #
# Kernels.
# --------------------------------------------------------------------------- #
def paged_attention(cfg: dict, rows: Iterable[Tuple[int, int]],
                    kv_bytes: int = BF16) -> Tuple[float, float]:
    """One layer's paged-attention call over ``rows`` = (pos, n_valid):
    each row's n_valid queries at positions pos.. attend causally to
    its pos + n_valid keys. Returns (flops, bytes)."""
    n = dims(cfg)
    H, K, hd = n["H"], n["K"], n["hd"]
    flops = byts = 0
    for pos, nv in rows:
        flops += 4 * H * hd * _causal_pairs(pos, nv)
        byts += 2 * (pos + nv) * K * hd * kv_bytes   # K and V, once per KV head
        byts += 2 * nv * H * hd * BF16               # queries in, output out
    return float(flops), float(byts)


def flash_forward(cfg: dict, batch: int, seq: int) -> Tuple[float, float]:
    """One layer's causal flash-attention forward over (batch, seq):
    QK^T and PV over the lower triangle; q, k, v read and the output
    and its log-sum-exp (float32) written once."""
    n = dims(cfg)
    H, K, hd = n["H"], n["K"], n["hd"]
    flops = 4 * batch * H * hd * _causal_pairs(0, seq)
    byts = (batch * seq * (H + 2 * K) * hd * BF16
            + batch * seq * H * hd * BF16 + batch * seq * H * 4)
    return float(flops), float(byts)


# --------------------------------------------------------------------------- #
# Steps.
# --------------------------------------------------------------------------- #
def forward_flops(cfg: dict, rows: Iterable[Tuple[int, int]],
                  head_tokens: int) -> float:
    """Forward FLOPs of feeding ``rows`` = (pos, n_valid) through every
    layer, plus the output head on ``head_tokens`` positions."""
    n = dims(cfg)
    per_tok = 2 * _matmul_params(n)
    attn = 4 * n["H"] * n["hd"]
    total = 0
    for pos, nv in rows:
        total += n["L"] * (nv * per_tok + attn * _causal_pairs(pos, nv))
    return float(total + head_tokens * 2 * n["d"] * n["V"])


def chunk_step(cfg: dict, rows: Iterable[Tuple[int, int, bool]]) -> float:
    """Model FLOPs of one serving step: ``rows`` = (pos, n_valid,
    emits); the head is needed only where a row emits a token."""
    rows = list(rows)
    return forward_flops(cfg, [(p, nv) for p, nv, _ in rows],
                         sum(1 for *_, e in rows if e))


def train_step(cfg: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one training step: forward and backward (twice
    the forward) over batch x seq tokens, the head on the seq - 1
    positions that have a target. Recomputation is not counted."""
    return 3.0 * forward_flops(cfg, [(0, seq)] * batch, batch * (seq - 1))


def least_time(flops: float, byts: float, peak: dict) -> Tuple[float, str]:
    """Roofline: the least seconds the chip could take, and its bound."""
    tf = flops / peak["bf16_flops"]
    tb = byts / peak["hbm_bytes_per_s"]
    return (tf, "compute") if tf >= tb else (tb, "memory")
