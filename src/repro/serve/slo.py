"""SLO classes and latency-aware scheduling policy.

MLPerf-Inference (Reddi et al., 2019) pins each scenario to a latency
constraint — the Server scenario only counts queries answered inside
the bound; the ML Fleet Efficiency paper (arXiv:2502.06982) generalises
that to *goodput*: the fraction of work that met its SLO, not just the
raw throughput. This module gives requests a *priority class* with
optional TTFT / end-to-end latency budgets and derives the scheduling
policy from them.

Budgets are denominated in **engine steps**, not wall-clock seconds:
one step is one scheduling round (one chunk/decode dispatch), so the
same workload produces the same slack arithmetic on any machine —
deterministic and property-testable (tests/test_scenarios.py). Wall
clock still flows into the per-class latency percentiles of
:class:`repro.serve.metrics.ServeReport`.

Policy, in two places:

* **Preemption under pool pressure** (``Engine._chunk_plan`` growth):
  the victim is the slot with the **most slack** — the request that can
  best absorb a recompute-resume round-trip. Untagged requests have
  infinite slack, and ties break youngest-first (max admit seq), so a
  workload with no SLO classes preempts exactly like the pre-SLO
  engine.
* **Admission** (``PagedScheduler`` ``on_shortfall`` hook): a
  latency-critical candidate that cannot get pages may evict a running
  request of a strictly *lower* class (greater priority number) with
  more slack than its own. A candidate whose budget is already **blown**
  never preempts anybody — evicting live work cannot un-miss its SLO
  (the admission oracle in tests/test_scenarios.py).

All pure Python / jax-free, like the scheduler it advises.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, Mapping, Optional, Tuple

INF = float("inf")

#: Effective priority of a request with no SLO class: strictly worse
#: than any registered class, so tagged traffic outranks best-effort —
#: and an all-untagged workload degenerates to pure FIFO (every
#: priority equal), preserving pre-SLO scheduling exactly.
BEST_EFFORT_PRIORITY = 1 << 30


@dataclasses.dataclass(frozen=True)
class SLOClass:
    """A named latency class.

    ``priority``: lower number = more latency-critical (0 is the most
    urgent). ``ttft_steps`` / ``latency_steps``: budgets in engine steps
    from arrival to first token / retirement; ``None`` means unbounded
    (the class is accounted in per-class percentiles but can never
    violate, e.g. batch traffic).
    """

    name: str
    priority: int = 0
    ttft_steps: Optional[int] = None
    latency_steps: Optional[int] = None

    def __post_init__(self):
        if self.priority < 0:
            raise ValueError("priority must be >= 0")
        for field in ("ttft_steps", "latency_steps"):
            v = getattr(self, field)
            if v is not None and v < 1:
                raise ValueError(f"{field} must be >= 1 (or None)")


INTERACTIVE = SLOClass("interactive", priority=0,
                       ttft_steps=8, latency_steps=48)
STANDARD = SLOClass("standard", priority=1,
                    ttft_steps=32, latency_steps=160)
BATCH = SLOClass("batch", priority=2)  # unbounded: pure best-effort

CLASSES: Dict[str, SLOClass] = {
    c.name: c for c in (INTERACTIVE, STANDARD, BATCH)}


def get_class(name: str) -> SLOClass:
    try:
        return CLASSES[name]
    except KeyError:
        raise ValueError(
            f"unknown SLO class {name!r}; known: {sorted(CLASSES)}"
        ) from None


# --------------------------------------------------------------------------- #
# Per-request arithmetic. Requests carry ``slo`` (an SLOClass or None)
# plus step stamps ``s_arrival`` / ``s_first_token`` / ``s_done`` set by
# the engine (see serve.request).
# --------------------------------------------------------------------------- #
def priority_of(req) -> int:
    slo = getattr(req, "slo", None)
    return slo.priority if slo is not None else BEST_EFFORT_PRIORITY


def deadline(req) -> float:
    """Step by which the request must retire; inf when unbudgeted."""
    slo = getattr(req, "slo", None)
    if slo is None or slo.latency_steps is None:
        return INF
    return req.arrival_step + slo.latency_steps


def slack(req, step: int) -> float:
    """Steps to spare at ``step``: deadline minus now minus the steps
    the request still needs (one per remaining token). Negative means
    the budget cannot be met even with a slot all to itself."""
    d = deadline(req)
    if d == INF:
        return INF
    remaining = req.max_new_tokens - len(req.tokens)
    return d - step - remaining


def blown(req, step: int) -> bool:
    """True when the latency budget is already unmeetable at ``step``."""
    return slack(req, step) < 0


def met_slo(req) -> bool:
    """Post-hoc: did a finished request meet every budget it carried?
    Untagged and unbudgeted requests always did."""
    slo = getattr(req, "slo", None)
    if slo is None:
        return True
    if (slo.ttft_steps is not None and req.s_first_token is not None
            and req.s_first_token - req.arrival_step > slo.ttft_steps):
        return False
    if (slo.latency_steps is not None and req.s_done is not None
            and req.s_done - req.arrival_step > slo.latency_steps):
        return False
    return True


# --------------------------------------------------------------------------- #
# Victim selection.
# --------------------------------------------------------------------------- #
def choose_victim(active: Mapping[int, object], step: int,
                  admit_seq: Mapping[int, int]) -> int:
    """Growth-pressure victim among ``active`` (slot -> request): the
    slot with the most slack; ties (e.g. all untagged -> all infinite)
    break to the youngest admission, reproducing the pre-SLO
    youngest-first policy exactly."""
    if not active:
        raise ValueError("no active slots to preempt")
    return max(active, key=lambda s: (slack(active[s], step),
                                      admit_seq[s]))


def admission_victim(candidate, running: Iterable[Tuple[int, object]],
                     step: int,
                     admit_seq: Mapping[int, int]) -> Optional[int]:
    """Admission-pressure victim for ``candidate``, or None.

    Never preempts when the candidate's own budget is already blown
    (the oracle: evicting live work cannot rescue a missed SLO).
    Eligible victims run at a strictly lower class (greater priority
    number) *and* hold strictly more slack than the candidate — equal
    classes never displace each other at admission, so two interactive
    requests cannot livelock trading one slot."""
    if blown(candidate, step):
        return None
    cand_pri = priority_of(candidate)
    cand_slack = slack(candidate, step)
    best = None
    for slot, req in running:
        if priority_of(req) <= cand_pri:
            continue
        s = slack(req, step)
        if s <= cand_slack:
            continue
        key = (s, admit_seq[slot])
        if best is None or key > best[0]:
            best = (key, slot)
    return None if best is None else best[1]
