#!/usr/bin/env python3
"""Run one benchmark cell once on the chip(s) of this machine.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration, traffic mix, limits and per-layer metric readers are
files under ``chipbench/`` found by name (see ``chipbench/harness.py``).
Set-up (weights from the seed, compilation from the persistent cache
in ``.chipbench_cache/``, warm-up) is timed as ``setup_s``; the window
then runs ``--seconds`` with nothing compiling. ``--trace 0`` reports
the cell's end-to-end metrics, ``--trace 1`` profiles the window and
reports its per-layer metrics. Either way the outputs of the timed path
are checked against the plain reference, and the last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (``breakdown`` when traced) and ``checks``,
each compared number beside its limit.

No TPU, fewer chips than the cell asks for, or a device kind missing
from ``chipbench/peaks.json``: a message on standard error, no result,
exit code 1.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import harness as H  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        ctx = H.load_cell(args.workload)
        ctx["limits"] = H.load_limits(args.workload)
        H.use_cache()
        ctx["clock"] = H.CompileClock()
        ctx["devs"] = H.devices(ctx["cell"]["chips"])
        ctx["peak"] = H.peaks(ctx["devs"][0].device_kind)
        ctx.update(args=args, t_start=T_START, base=H.HERE)
        H.log(f"{args.workload}: {ctx['devs'][0].device_kind} x "
              f"{len(ctx['devs'])}, seed {args.seed}, {args.seconds} s, "
              f"trace {args.trace}")
        result = H.load_driver(ctx["traffic"]["kind"]).run(ctx)
    except H.Failure as e:
        H.log(f"failed: {e}")
        return 1
    except Exception:  # noqa: BLE001 — any fault: no result, non-zero
        traceback.print_exc()
        return 1
    H.finish(**result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
