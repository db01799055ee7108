"""Model FLOPs of the tokens the serving steps of the traced window fed
(chipbench.counts.chunk_step, rebuilt per step from the tokens
observed), per cent of the window times the chip's bf16 peak. Moves
itl_p95_ms: the step is the time between a request's tokens."""
from chipbench import counts
from chipbench.readers import traced_rows


def read(run):
    rows = traced_rows(run)
    lo, hi = run.get("window", (0.0, 0.0))
    if not rows or hi <= lo:
        return None
    flops = sum(counts.chunk_step(run["config"], r) for r in rows)
    return 100.0 * flops / ((hi - lo) * run["peak"]["bf16_flops"])
