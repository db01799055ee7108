"""The program's own names in a profiler trace.

The program marks its work in two ways, and both land in the trace the
benchmark already records:

* host spans it opens itself (``jax.profiler.TraceAnnotation`` names
  starting ``repro.``, e.g. ``repro.serve.device``), kept apart from the
  benchmark's ``chipbench.*`` spans as ``Trace.program_spans``;
* name scopes (``jax.named_scope``: ``kv_insert``, ``attention``,
  ``attention_bwd``, ``optimizer``) in the op-name path XLA keeps in each
  instruction's metadata. The device events carry only the instruction's
  text, so the path comes from the optimized HLO modules the profiler
  stores in the trace's ``/host:metadata`` plane (stat "Hlo Proto"),
  looked up by the module that ran around the op (the device plane's
  "XLA Modules" line) and the instruction's name. A fusion carries its
  root instruction's path.

``load`` reads these next to what ``chipbench.trace.load`` reads; the
functions below reduce them over leaf ops (``chipbench.trace.leaves``),
so a loop's or a call's event never counts its body twice. A trace of a
program that opens no such span and names no scope gives empty spans and
scopes, and the readers built on it read nothing.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

from chipbench import harness as H
from chipbench import trace as T

PROGRAM_PREFIX = "repro."
_WRAPPED = re.compile(r"^[\w-]+\((.*)\)$")  # jvp(x), transpose(jvp(x))


@dataclasses.dataclass
class Op(T.Op):
    scope: str = ""  # op-name path, e.g. "jit(step)/while/body/kv_insert/add"


@dataclasses.dataclass
class Trace(T.Trace):
    program_spans: List[Tuple[str, float, float]] = dataclasses.field(
        default_factory=list)  # the program's own host spans (repro.*)


# --------------------------------------------------------------------------- #
# Scopes.
# --------------------------------------------------------------------------- #
def names(scope: str) -> set:
    """The names on an op-name path, each unwrapped from the transforms
    around it ("transpose(jvp(attention_bwd))" -> "attention_bwd")."""
    out = set()
    for part in scope.split("/"):
        while True:
            m = _WRAPPED.match(part)
            if not m:
                break
            part = m.group(1)
        out.add(part)
    return out


def scoped_seconds(ops: Iterable[T.Op], scopes: Sequence[str], lo: float,
                   hi: float) -> float:
    """Seconds inside [lo, hi] of the leaf ops under any of ``scopes``."""
    want = set(scopes)
    return sum(min(op.end, hi) - max(op.start, lo) for op in T.leaves(ops)
               if op.end > lo and op.start < hi
               and want & names(getattr(op, "scope", "")))


def scope_share(tr: Trace, scopes: Sequence[str], lo: float,
                hi: float) -> Optional[float]:
    """Per cent of the busy device seconds of [lo, hi] spent in leaf ops
    under any of ``scopes`` (all devices summed); None where no op of
    the window carries one of them. Ops with no scope count in the
    denominator only."""
    inside = busy = 0.0
    for ops in tr.devices.values():
        inside += scoped_seconds(ops, scopes, lo, hi)
        busy += T.busy(((o.start, o.end) for o in ops), lo, hi)
    if inside <= 0 or busy <= 0:
        return None
    return 100.0 * inside / busy


def by_scope(ops: Iterable[T.Op], lo: float, hi: float) -> Dict[str, float]:
    """Leaf-op seconds inside [lo, hi] by op-name path without the op
    itself ("jit(step)/while/body/kv_insert")."""
    out: Dict[str, float] = defaultdict(float)
    for op in T.leaves(ops):
        if op.end > lo and op.start < hi:
            path = getattr(op, "scope", "").rpartition("/")[0]
            out[path or "(no scope)"] += min(op.end, hi) - max(op.start, lo)
    return dict(out)


# --------------------------------------------------------------------------- #
# Host spans.
# --------------------------------------------------------------------------- #
def host_gap(tr: Trace, lo: float, hi: float, host: Sequence[str],
             per: str) -> Optional[float]:
    """Device-idle seconds of [lo, hi] that fall inside the program spans
    named ``host`` (interval intersection), per span named ``per`` that
    starts in the window, averaged over the devices; None without such a
    span."""
    n = sum(1 for name, s, _ in tr.program_spans
            if name == per and lo <= s <= hi)
    spans = T.union((max(s, lo), min(e, hi)) for name, s, e
                    in tr.program_spans if name in host)
    if not n or not tr.devices:
        return None
    idle = [T.intersection(T.idle_gaps(((o.start, o.end) for o in ops),
                                       lo, hi), spans)
            for ops in tr.devices.values()]
    return sum(idle) / len(idle) / n


# --------------------------------------------------------------------------- #
# Loading.
# --------------------------------------------------------------------------- #
def _varint(buf, i: int) -> Tuple[int, int]:
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return v, i


def fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message; a length-delimited
    value is a memoryview, a varint an int, fixed-width values bytes."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = bytes(buf[i:i + size]), i + size
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield key >> 3, v


def _sub(buf, number: int) -> list:
    return [v for f, v in fields(buf) if f == number]


def _text(buf, number: int) -> str:
    got = _sub(buf, number)
    return bytes(got[0]).decode() if got else ""


def op_paths(raw: bytes) -> Dict[str, Dict[str, str]]:
    """Module name as the trace gives it ("jit_step(123)") -> instruction
    name -> op-name path, from the HLO modules of the ``/host:metadata``
    plane of a serialized XSpace (XSpace.planes 1; XPlane.name 2,
    .event_metadata 4, .stat_metadata 5; XEventMetadata.name 2, .stats 5;
    XStat.metadata_id 1, .bytes_value 6; HloProto.hlo_module 1;
    HloModuleProto.computations 3; HloComputationProto.instructions 2;
    HloInstructionProto.name 1, .metadata 7; OpMetadata.op_name 2)."""
    out: Dict[str, Dict[str, str]] = {}
    for plane in _sub(raw, 1):
        if _text(plane, 2) != "/host:metadata":
            continue
        stat_names = {}
        for entry in _sub(plane, 5):
            md = _sub(entry, 2)
            if md:
                ids = _sub(md[0], 1)
                stat_names[ids[0] if ids else 0] = _text(md[0], 2)
        for entry in _sub(plane, 4):
            md = _sub(entry, 2)
            if not md:
                continue
            paths: Dict[str, str] = {}
            for stat in _sub(md[0], 5):
                ids = _sub(stat, 1)
                proto = _sub(stat, 6)
                if (not proto or stat_names.get(ids[0] if ids else 0)
                        != "Hlo Proto"):
                    continue
                for module in _sub(proto[0], 1):
                    for comp in _sub(module, 3):
                        for ins in _sub(comp, 2):
                            meta = _sub(ins, 7)
                            path = _text(meta[0], 2) if meta else ""
                            if path:
                                paths[_text(ins, 1)] = path
            if paths:
                out[_text(md[0], 2)] = paths
    return out


def _within(ops: List[Op], modules: List[Tuple[str, float, float]],
            paths: Dict[str, Dict[str, str]]) -> None:
    """Give each op the path its instruction has in the module whose
    execution encloses it; the device names a module run as the metadata
    plane names the module ("jit_chunk_step(<program id>)")."""
    modules = sorted(modules, key=lambda m: m[1])
    k = 0
    for op in ops:  # by start
        while k < len(modules) and modules[k][2] < op.start:
            k += 1
        if k < len(modules) and modules[k][1] <= op.start:
            op.scope = paths.get(modules[k][0], {}).get(op.name, "")


def load(path: str) -> Trace:
    """What ``chipbench.trace.load`` reads, each op with its scope, and
    the program's own host spans (``repro.*``)."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    paths = op_paths(raw)
    data = ProfileData.from_serialized_xspace(raw)
    devices: Dict[str, List[Op]] = {}
    spans: List[Tuple[str, float, float]] = []
    program: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops: List[Op] = []
            modules: List[Tuple[str, float, float]] = []
            for line in plane.lines:
                if line.name not in ("XLA Ops", "XLA Modules"):
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    e = s + ev.duration_ns * 1e-9
                    if line.name == "XLA Ops":
                        ops.append(Op(T._short(ev.name), s, e, ev.name))
                    else:
                        modules.append((ev.name, s, e))
            if ops:
                ops.sort(key=lambda o: o.start)
                _within(ops, modules, paths)
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    span = (ev.name, s, s + ev.duration_ns * 1e-9)
                    if ev.name.startswith(T.SPAN_PREFIX):
                        spans.append(span)
                    elif ev.name.startswith(PROGRAM_PREFIX):
                        program.append(span)
    return Trace(devices, spans, program)


def of_run(run: dict) -> Optional[Trace]:
    """The traced run's trace with the program's names, loaded once and
    kept in the run's record: the newest trace the benchmark wrote
    (``.chipbench_cache/trace/<cell>/``), if its ``chipbench.traced``
    span is the run's window. None for an untraced run."""
    if "scoped" not in run:
        run["scoped"] = None
        if "trace" in run and "window" in run:
            found = glob.glob(os.path.join(H.CACHE, "trace", "*", "**",
                                           "*.xplane.pb"), recursive=True)
            if found:
                tr = load(max(found, key=os.path.getmtime))
                if tr.window("chipbench.traced") == tuple(run["window"]):
                    run["scoped"] = tr
    return run["scoped"]


def run_share(run: dict, kind: str, scopes: Sequence[str]) -> Optional[float]:
    """``scope_share`` over a traced run's window, for runs of ``kind``."""
    if run.get("kind") != kind:
        return None
    tr = of_run(run)
    return None if tr is None else scope_share(tr, scopes, *run["window"])
