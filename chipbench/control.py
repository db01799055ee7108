#!/usr/bin/env python3
"""Readings that set a cell's limits, on the chip at the cell's size.

    python chipbench/control.py --workload <cell> --seeds 1,2,3 \
        [--seconds 10] [--controls int8,fp8] [--faults half_batch]

For each seed, in one process: the cell's set-up from that seed, the
timed path driven as a run drives it (a serving cell serves a short
window at its own load; a training cell takes its first steps), then
the compared numbers for the program, and for each control: the plain
reference put in the program's place and computed a precision below
the configuration's bfloat16 (int8 or float8 operands). A training cell
also reads the half-batch fault (``--faults half_batch``): the reference
over half of each batch's rows in the program's place. One JSON line
per seed: each compared number, and ``correct.<who>`` for the program,
each control and each fault under the cell's limits
(``limits/<cell>.json``), judged as a run judges the program. The lower
reading of a limit is the largest the program gives, the upper the
smallest a control or fault gives; each control and fault has to come
out not correct.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import harness as H  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--controls", default="fp8,int8")
    ap.add_argument("--faults", default="half_batch")
    args = ap.parse_args(argv)
    ctx = H.load_cell(args.workload)
    limits = H.load_limits(args.workload)
    H.use_cache()
    H.devices(ctx["cell"]["chips"])
    drv = H.load_driver(ctx["traffic"]["kind"])
    controls = [c for c in args.controls.split(",") if c]
    for seed in (int(s) for s in args.seeds.split(",")):
        if ctx["traffic"]["kind"] == "serve_open_loop":
            serve = drv.Serve(ctx["config"], ctx["traffic"], seed)
            m = serve.window(args.seconds, seed)
            c = serve.check(m, seed, ["f32"] + controls)
            enough = c["tokens"] >= ctx["traffic"]["check_tokens"]
            out = {"seed": seed, "failed": m["failed"],
                   "requests": c["requests"], "tokens": c["tokens"]}
            for p, v in c["max_logit_gap"].items():
                who = "program" if p == "f32" else p
                out[f"max_logit_gap.{who}"] = v
                out[f"correct.{who}"] = enough and v <= limits[
                    "max_logit_gap"]
        else:
            tr = drv.Train(ctx["config"], ctx["traffic"], seed,
                           ctx["cell"]["chips"])
            prog = tr.first_steps()
            tr.free()
            ref = tr.reference(against=prog.pop("g1"), keep_grad=True)
            g1 = ref.pop("g1")
            out = {"seed": seed, "losses": prog["losses"],
                   "ref_losses": ref["losses"]}
            got = {"program": drv.compare(prog, ref, ref["diff"])}
            for p in controls:
                r = tr.reference(p, against=g1)
                got[p] = drv.compare(r, ref, r["diff"])
            if "half_batch" in args.faults.split(","):
                r = tr.reference(rows=ctx["traffic"]["batch"] // 2,
                                 against=g1)
                got["half_batch"] = drv.compare(r, ref, r["diff"])
            for who, nums in got.items():
                out.update({f"{k}.{who}": v for k, v in nums.items()})
                out[f"correct.{who}"] = all(v <= limits[k]
                                            for k, v in nums.items())
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
