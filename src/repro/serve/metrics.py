"""Serving metrics: throughput, time-to-first-token and per-class
latency percentiles.

MLPerf-Inference-style reporting (Reddi et al., 2019): the offline
scenario cares about total throughput (tokens/s), the server scenario
about request latency and time-to-first-token. Each engine step leaves a
``StepTrace`` of its device work and the tokens it fed.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

from repro.serve import slo as slo_mod


def percentile(xs: List[float], q: float) -> float:
    """Nearest-rank percentile (q in [0,100]); 0.0 on empty input."""
    if not xs:
        return 0.0
    s = sorted(xs)
    rank = max(1, -(-len(s) * q // 100))  # ceil(n*q/100), >= 1
    return s[min(int(rank), len(s)) - 1]


@dataclasses.dataclass
class StepTrace:
    """One engine step's device work.

    kind: 'prefill' | 'decode' (slab engine); the paged engine's single
    mixed program reports 'decode' when every active row fed one token
    and 'mixed' while any row is still chunk-prefilling, plus 'encode'
    for enc-dec admissions. pool_util: fraction of the page pool in use
    after the step (paged engine only). n_fed: tokens the step's program
    fed to active rows (prompt tokens, or one per decode row plus its
    drafts); n_positions: token positions the dispatched program
    computes (B x C for the chunk program), so n_fed / n_positions is the
    share of the step's work that was real. n_kv_pages: page slots the
    paged attention kernel visits in one layer (sum over active rows of
    ceil((pos + n_valid) / page)); over B x max_pages it is the share of
    the page table the step's attention reads.
    """

    kind: str
    wall_s: float
    n_tokens: int  # tokens produced by this step
    pool_util: Optional[float] = None
    n_fed: int = 0
    n_positions: int = 0
    n_kv_pages: int = 0


@dataclasses.dataclass
class ServeReport:
    """Aggregated outcome of one engine run."""

    requests: List[Any]          # FINISHED Request objects
    steps: List[StepTrace]
    elapsed_s: float
    preemptions: int = 0         # paged engine: pool-pressure evictions
    # -- cross-request prefix cache (paged engine, serve.prefix) -------- #
    prefix_hit_rate: Optional[float] = None  # skipped / total prefill toks
    pages_shared: int = 0        # cached pages mapped into admitted slots
    prefill_tokens_skipped: int = 0  # prompt tokens served from cache
    cow_copies: int = 0          # shared pages privatized before a write
    # -- speculative decoding (paged engine, serve.speculative) --------- #
    spec_accept_rate: Optional[float] = None  # accepted / proposed drafts
    draft_tokens: int = 0        # draft tokens proposed across the run

    # ------------------------------------------------------------------ #
    @property
    def tokens_generated(self) -> int:
        return sum(len(r.tokens) for r in self.requests)

    @property
    def tokens_per_s(self) -> float:
        return self.tokens_generated / max(self.elapsed_s, 1e-9)

    # -- SLO accounting (serve.slo; MLPerf Server scenario + goodput) --- #
    @property
    def slo_violations(self) -> int:
        """Finished requests that missed any budget of their class
        (TTFT or end-to-end, in engine steps); untagged never violate."""
        return sum(not slo_mod.met_slo(r) for r in self.requests)

    @property
    def slo_goodput(self) -> float:
        """Fraction of requests that met every budget they carried
        (1.0 for an untagged workload): goodput, not raw throughput."""
        if not self.requests:
            return 1.0
        return 1.0 - self.slo_violations / len(self.requests)

    @property
    def goodput(self) -> float:
        """Top-level goodput: the per-class goodputs weighted by each
        class's request count — one number per report, so fleet-level
        aggregation (``repro.fleet.FleetReport``) never re-derives class
        structure. Equals the single class's goodput when the workload
        carries one class, and 1.0 when it carries none (untagged
        requests never violate)."""
        by_class: Dict[str, List[Any]] = {}
        for r in self.requests:
            name = r.slo.name if getattr(r, "slo", None) else "best-effort"
            by_class.setdefault(name, []).append(r)
        total = sum(len(rs) for rs in by_class.values())
        if not total:
            return 1.0
        weighted = sum(
            (1.0 - sum(not slo_mod.met_slo(r) for r in rs) / len(rs))
            * len(rs)
            for rs in by_class.values())
        return weighted / total

    def per_class(self) -> Dict[str, Dict[str, Any]]:
        """Per-SLO-class breakdown: request count, end-to-end and TTFT
        p50/p99 (wall ms), budget violations and class goodput. Only
        classes present in the workload appear; untagged requests are
        grouped under ``"best-effort"``."""
        by_class: Dict[str, List[Any]] = {}
        for r in self.requests:
            name = r.slo.name if getattr(r, "slo", None) else "best-effort"
            by_class.setdefault(name, []).append(r)
        out = {}
        for name, rs in by_class.items():
            lats = [r.latency_s for r in rs if r.latency_s is not None]
            ttfts = [r.ttft_s for r in rs if r.ttft_s is not None]
            bad = sum(not slo_mod.met_slo(r) for r in rs)
            out[name] = {
                "requests": len(rs),
                "p50_ms": round(percentile(lats, 50) * 1e3, 3),
                "p99_ms": round(percentile(lats, 99) * 1e3, 3),
                "ttft_p50_ms": round(percentile(ttfts, 50) * 1e3, 3),
                "ttft_p99_ms": round(percentile(ttfts, 99) * 1e3, 3),
                "violations": bad,
                "goodput": round(1.0 - bad / max(len(rs), 1), 4),
            }
        return out

    # ------------------------------------------------------------------ #
    def summary(self) -> Dict[str, Any]:
        ttfts = [r.ttft_s for r in self.requests if r.ttft_s is not None]
        decode_steps = [s for s in self.steps if s.kind == "decode"]
        utils = [s.pool_util for s in self.steps if s.pool_util is not None]
        extra = {}
        if utils:
            extra = {
                "pool_util_mean": round(sum(utils) / len(utils), 4),
                "pool_util_peak": round(max(utils), 4),
            }
        if self.prefix_hit_rate is not None:
            extra.update(
                prefix_hit_rate=round(self.prefix_hit_rate, 4),
                pages_shared=self.pages_shared,
                prefill_tokens_skipped=self.prefill_tokens_skipped,
                cow_copies=self.cow_copies,
            )
        if self.spec_accept_rate is not None:
            extra.update(
                spec_accept_rate=round(self.spec_accept_rate, 4),
                draft_tokens=self.draft_tokens,
            )
        if any(getattr(r, "slo", None) is not None for r in self.requests):
            extra.update(
                goodput=round(self.goodput, 4),
                slo_goodput=round(self.slo_goodput, 4),
                slo_violations=self.slo_violations,
            )
        return {
            **extra,
            "requests": len(self.requests),
            "tokens": self.tokens_generated,
            "elapsed_s": round(self.elapsed_s, 4),
            "tokens_per_s": round(self.tokens_per_s, 2),
            "ttft_p50_ms": round(percentile(ttfts, 50) * 1e3, 3),
            "decode_steps": len(decode_steps),
            "mean_batch_occupancy": round(
                sum(s.n_tokens for s in decode_steps)
                / max(len(decode_steps), 1), 2),
        }

    def format(self) -> str:
        s = self.summary()
        return (
            f"{s['requests']} requests, {s['tokens']} tokens in "
            f"{s['elapsed_s']:.2f}s ({s['tokens_per_s']:.1f} tok/s), "
            f"ttft p50 {s['ttft_p50_ms']:.1f}ms, "
            f"mean occupancy {s['mean_batch_occupancy']:.1f}"
        )
