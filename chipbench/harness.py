"""What every cell shares: finding a cell's files by name, the device
check, the compile clock, per-layer metric readers and the result line.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (``chipbench/configs/<config>.json``) and a traffic mix
(``chipbench/traffic/<traffic>.json``); the mix's ``kind`` names the
driver (``chipbench/drivers/<kind>.py``) and each per-layer metric is a
reader ``chipbench/metrics/<metric>.py`` with ``read(run) -> float |
None``. Nothing here knows a cell, a mix or a metric by name.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE = os.path.join(ROOT, ".chipbench_cache")


class Failure(Exception):
    """A run that must print no result and exit non-zero."""


def log(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


# --------------------------------------------------------------------------- #
# Files found by name.
# --------------------------------------------------------------------------- #
def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(base: str = HERE) -> dict:
    path = os.path.join(os.path.dirname(base), "BENCHMARK.json")
    if not os.path.exists(path):
        raise Failure(f"no BENCHMARK.json at {path}")
    return _json(path)


def load_cell(name: str, base: str = HERE) -> Dict[str, Any]:
    """The cell's entry, its configuration and its traffic mix."""
    bench = load_benchmark(base)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Failure(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    return {"cell": cell, "bench": bench,
            "config": load_config(cell["config"], base),
            "traffic": load_traffic(cell["traffic"], base)}


def load_config(name: str, base: str = HERE) -> dict:
    return _json(os.path.join(base, "configs", f"{name}.json"))


def load_traffic(name: str, base: str = HERE) -> dict:
    return _json(os.path.join(base, "traffic", f"{name}.json"))


def load_limits(workload: str, base: str = HERE) -> dict:
    """The cell's limit on each compared number (``limits/<cell>.json``)."""
    return _json(os.path.join(base, "limits", f"{workload}.json"))["limits"]


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_driver(kind: str, base: str = HERE):
    return _module(os.path.join(base, "drivers", f"{kind}.py"),
                   f"chipbench_driver_{kind}")


def load_metric(name: str, base: str = HERE):
    return _module(os.path.join(base, "metrics", f"{name}.py"),
                   "chipbench_metric_" + name.replace(".", "_"))


def cell_metrics(bench: dict, cell: str, group: str) -> List[dict]:
    """The metrics of ``group`` ("end_to_end" | "per_layer") this cell
    reports: those without a ``workloads`` key, and those listing it."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def read_per_layer(bench: dict, cell: str, run: dict,
                   base: str = HERE) -> Dict[str, dict]:
    out = {}
    for m in cell_metrics(bench, cell, "per_layer"):
        value = load_metric(m["name"], base).read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# --------------------------------------------------------------------------- #
# Device, peaks, compile cache and compile clock.
# --------------------------------------------------------------------------- #
def peaks(device_kind: str, base: str = HERE) -> dict:
    table = _json(os.path.join(base, "peaks.json"))
    if device_kind not in table["devices"]:
        raise Failure(f"device kind {device_kind!r} is not in the peaks "
                      f"table ({sorted(table['devices'])})")
    return table["devices"][device_kind]


def use_cache() -> str:
    """JAX's persistent compile cache at a fixed path in the checkout;
    every program is cached, however small or quick to compile."""
    import jax

    path = os.path.join(CACHE, "jax")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def devices(chips: int):
    """The cell's devices: TPUs only, at least ``chips`` of them."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:  # no backend at all
        raise Failure(f"JAX finds no accelerator: {e}")
    if devs[0].platform != "tpu":
        raise Failure(f"no TPU: JAX's first device is {devs[0].platform}")
    if len(devs) < chips:
        raise Failure(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


class CompileClock:
    """XLA compile seconds and count (cache retrievals included), from
    JAX's own compile-duration events, each with its host time."""

    def __init__(self):
        import jax

        self.events: List[tuple] = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.perf_counter(), secs))

    def seconds(self, t0: float = -1e300, t1: float = 1e300) -> float:
        return sum(s for t, s in self.events if t0 <= t <= t1)

    def count(self, t0: float = -1e300, t1: float = 1e300) -> int:
        return sum(1 for t, _ in self.events if t0 <= t <= t1)


def peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest device."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def device_info(devs, peak: Optional[int] = None) -> dict:
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    if peak is not None:
        info["memory_peak_bytes"] = peak
    return info


# --------------------------------------------------------------------------- #
# Tracing.
# --------------------------------------------------------------------------- #
def trace_dir(workload: str) -> str:
    path = os.path.join(CACHE, "trace", workload)
    if os.path.isdir(path):
        import shutil

        shutil.rmtree(path)
    os.makedirs(path)
    return path


def device_summary(trace, lo: float, hi: float) -> dict:
    """busy_s (averaged over the traced devices) and window_s."""
    from chipbench import trace as T

    busy = [T.busy(((o.start, o.end) for o in ops), lo, hi)
            for ops in trace.devices.values()]
    return {"busy_s": sum(busy) / max(len(busy), 1), "window_s": hi - lo}


def breakdown(trace, lo: float, hi: float) -> dict:
    """Device operations by their own time (nested ops not counted
    twice), and idle gaps by the host span open (first traced device)."""
    from chipbench import trace as T

    if not trace.devices:
        return {"device_ops": [], "idle_gaps": []}
    ops = trace.devices[sorted(trace.devices)[0]]
    gaps = T.idle_gaps(((o.start, o.end) for o in ops), lo, hi)
    return {"device_ops": T.top(T.self_times(ops, lo, hi)),
            "idle_gaps": T.top(T.attribute(gaps, trace.spans))}


# --------------------------------------------------------------------------- #
# The result.
# --------------------------------------------------------------------------- #
def finish(*, correct: bool, attempted: int, failed: int, metrics: dict,
           device: dict, checks: Dict[str, dict],
           breakdown: Optional[dict] = None) -> None:
    """Every compared number beside its limit, as the last lines of
    standard error; then the result as the last line of standard output
    (``checks`` last)."""
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
