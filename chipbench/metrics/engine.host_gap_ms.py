"""Milliseconds of device idle a serving step spends in the engine's own
host code: idle seconds of the traced window (chipbench.trace.idle_gaps)
that intersect the engine's spans repro.serve.schedule, .prepare and
.emit, per repro.serve.device span (one a chunk step) that starts in the
window. Moves itl_p95_ms: the time between a request's tokens is the
device step plus this gap. A program without these spans reads
nothing."""
from chipbench import scopes as S

HOST = ("repro.serve.schedule", "repro.serve.prepare", "repro.serve.emit")


def read(run):
    if run.get("kind") != "serve":
        return None
    tr = S.of_run(run)
    if tr is None:
        return None
    gap = S.host_gap(tr, *run["window"], HOST, "repro.serve.device")
    return None if gap is None else 1e3 * gap
