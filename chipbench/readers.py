"""Arithmetic shared by the per-layer metric readers (``metrics/``).

Each reader gets the run's record (``run``): its ``kind``, the
configuration, the peaks of its device, and for a traced run the
reduced trace with the traced ``window`` on the trace's clock. A reader
that finds nothing to read returns None, and its metric is left out.
"""
from __future__ import annotations

from chipbench import counts
from chipbench import trace as T
from chipbench.harness import device_summary
from chipbench.weights import dims


def idle_share(run, kind: str):
    """Per cent of the traced window in which no operation ran, averaged
    over the cell's devices."""
    if run.get("kind") != kind or "trace" not in run:
        return None
    lo, hi = run["window"]
    if not run["trace"].devices or hi <= lo:
        return None
    d = device_summary(run["trace"], lo, hi)
    return 100.0 * (1.0 - d["busy_s"] / d["window_s"])


def traced_rows(run):
    """The rows of each serving step inside the traced window."""
    if run.get("kind") != "serve" or run.get("rows") is None:
        return None
    return [r for r, (_, _, traced) in zip(run["rows"], run["steps"])
            if traced]


def pallas_op(*shapes) -> str:
    """A pattern for a Pallas kernel's operation: a TPU custom call
    whose HLO text holds an array of each of ``shapes`` (a dim may be a
    regular expression, as for any number of pages). Both kernels are
    unnamed custom calls in the trace, so their shapes tell them apart."""
    need = "".join(r"(?=.*\[" + ",".join(map(str, s)) + r"\])"
                   for s in shapes)
    return r"^(?=.*tpu_custom_call)" + need


def kernel_roofline(run, pattern: str, least_time_of_calls: float):
    """Per cent: the least time the calls could take over the time the
    trace gives the kernel (summed over the cell's devices)."""
    lo, hi = run["window"]
    spent = sum(T.kernel_time(ops, pattern, lo, hi)[0]
                for ops in run["trace"].devices.values())
    if spent <= 0 or least_time_of_calls <= 0:
        return None
    return 100.0 * least_time_of_calls / spent


def kernel_calls(run, pattern: str) -> int:
    lo, hi = run["window"]
    return sum(T.kernel_time(ops, pattern, lo, hi)[1]
               for ops in run["trace"].devices.values())


def layers(run) -> int:
    return dims(run["config"])["L"]


def least(flops, byts, run) -> float:
    return counts.least_time(flops, byts, run["peak"])[0]
