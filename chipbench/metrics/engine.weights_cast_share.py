"""Per cent of the parameter tree's elements the serving engine computes
from its compute-dtype copy, made once when the engine is built
(ServeReport.weights_cast_share). Moves ttft_p50_ms, which both serving
cells report: a leaf left in float32 is cast to bfloat16 again in every
chunk step, a read and a write of its bytes in HBM, and a prompt waits
through dozens of chunk steps for its first token. A program that
records no share reads nothing."""


def read(run):
    rep = run.get("report")
    if run.get("kind") != "serve" or rep is None:
        return None
    return getattr(rep, "weights_cast_share", None)
