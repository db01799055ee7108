"""Per cent of the traced training window in which a collective ran on
a device and no compute did (chipbench.trace.exposed, over leaf ops),
averaged over the cell's devices. Moves train_tok_s. A trace that holds
no collective, as a one-chip cell's, reads nothing."""
from chipbench import trace as T


def read(run):
    if run.get("kind") != "train" or "trace" not in run:
        return None
    lo, hi = run["window"]
    got = [T.exposed(ops, lo, hi) for ops in run["trace"].devices.values()]
    if hi <= lo or not any(total for total, _ in got):
        return None
    return 100.0 * sum(e for _, e in got) / len(got) / (hi - lo)
