"""Per cent of the engine's chunk steps that carried prefill (kind
"mixed" in ServeReport.steps) over the measured run. Moves ttft_p50_ms:
a prompt streams prefill_chunk tokens a step, so TTFT is paid in these
steps."""


def read(run):
    rep = run.get("report")
    if rep is None:
        return None
    kinds = [s.kind for s in rep.steps if s.kind in ("mixed", "decode")]
    if not kinds:
        return None
    return 100.0 * kinds.count("mixed") / len(kinds)
