#!/usr/bin/env python3
"""Print what a cell's last trace holds, to name kernels for the
readers: planes, the heaviest device operations (own seconds, count,
name and the head of their HLO text) and the benchmark's host spans.

    python chipbench/inspect_trace.py <workload> [n]
"""
from __future__ import annotations

import os
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import harness as H  # noqa: E402
from chipbench import trace as T  # noqa: E402


def main(argv) -> int:
    n = int(argv[1]) if len(argv) > 1 else 40
    tr = T.load(T.find(os.path.join(H.CACHE, "trace", argv[0])))
    for dev, ops in sorted(tr.devices.items()):
        own = T.self_times(ops, -1e300, 1e300)
        cnt, text = defaultdict(int), {}
        for o in ops:
            cnt[o.name] += 1
            text[o.name] = o.text
        print(f"{dev}: {len(ops)} ops, first {ops[0].start:.6f} last "
              f"{ops[-1].end:.6f}")
        for name, s in sorted(own.items(), key=lambda kv: -kv[1])[:n]:
            print(f"  {s:10.6f} s  x{cnt[name]:6d}  {text[name][:300]}")
    spans = defaultdict(list)
    for name, s, e in tr.spans:
        spans[name].append((s, e))
    for name, iv in sorted(spans.items()):
        print(f"span {name}: {len(iv)}, first {iv[0][0]:.6f}, total "
              f"{sum(e - s for s, e in iv):.6f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
