#!/usr/bin/env python3
"""Find a serving cell's knee: one set-up, then one open-loop window at
each offered rate, on the chip of this machine.

    python chipbench/sweep.py --workload yi-9b-8l.chat --rates 4,8,12 \
        --seconds 20 --seed 1

For each rate one JSON line: requests due and without a first token,
TTFT p50/p95, inter-token p95, the mean step, and the queue wait (steps
from submission to admission, the admission step inferred from the
first token and the prompt's chunks) of the window's first and last
quarter of requests. A wait that stays near 0 means the queue did not
grow; the knee is the highest rate at which it stays so, and a cell's
mix then runs at about four fifths of it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import numpy as np  # noqa: E402

from chipbench import harness as H  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    ctx = H.load_cell(args.workload)
    H.use_cache()
    H.devices(ctx["cell"]["chips"])
    drv = H.load_driver(ctx["traffic"]["kind"])
    serve = drv.Serve(ctx["config"], ctx["traffic"], args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        m = serve.window(args.seconds, args.seed, rate=rate)
        C, reqs, win = m["chunk"], m["requests"], m["window"]
        wait = {}
        for i in win:
            k = m["first_step"][i]
            if k is not None:
                admitted = k - (-(-reqs[i].prompt_len // C)) + 1
                wait[i] = admitted - reqs[i].arrival_step
        q = max(1, len(win) // 4)
        mean = lambda xs: float(np.mean(xs)) if xs else None
        pct = lambda xs, p: float(np.percentile(xs, p) * 1e3) if xs else None
        step_s = [e - s for s, e, _ in m["steps"]]
        print(json.dumps({
            "rate_per_s": rate, "due": len(win), "failed": m["failed"],
            "ttft_p50_ms": pct(m["ttft_s"], 50),
            "ttft_p95_ms": pct(m["ttft_s"], 95),
            "itl_p95_ms": pct(m["itl_s"], 95),
            "step_ms_mean": mean(step_s) * 1e3 if step_s else None,
            "wait_steps_first_quarter": mean([wait[i] for i in win[:q]
                                              if i in wait]),
            "wait_steps_last_quarter": mean([wait[i] for i in win[-q:]
                                             if i in wait]),
            "steps": len(m["steps"]),
            "tail_s": m["t_end"] - m["t_window_end"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
