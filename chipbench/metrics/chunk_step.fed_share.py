"""Per cent of the token positions the serving steps computed that held
a token fed to an active row: the engine's own count, sum of
StepTrace.n_fed over sum of StepTrace.n_positions (the chunk program's
B x C), over the run's chunk steps (ServeReport.steps, warm-up traffic
included). Moves itl_p95_ms: a step costs the same whether its
positions are real or padding. A program that records no fed tokens
reads nothing."""


def read(run):
    rep = run.get("report")
    if run.get("kind") != "serve" or rep is None:
        return None
    steps = [s for s in rep.steps if s.kind in ("mixed", "decode")]
    total = sum(getattr(s, "n_positions", 0) for s in steps)
    if not total:
        return None
    return 100.0 * sum(s.n_fed for s in steps) / total
