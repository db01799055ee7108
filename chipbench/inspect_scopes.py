#!/usr/bin/env python3
"""Print the program's own names in a cell's last trace: its host spans
(``repro.*``: count and total seconds) and the heaviest op-name paths
of the device's leaf ops (chipbench.scopes), so that a kernel reads as
``.../attention/paged_attention`` and not as ``closed_call.12``.

    python chipbench/inspect_scopes.py <workload> [n]
"""
from __future__ import annotations

import os
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import harness as H  # noqa: E402
from chipbench import scopes as S  # noqa: E402
from chipbench import trace as T  # noqa: E402


def main(argv) -> int:
    n = int(argv[1]) if len(argv) > 1 else 25
    tr = S.load(T.find(os.path.join(H.CACHE, "trace", argv[0])))
    lo, hi = tr.window("chipbench.traced") or (-1e300, 1e300)
    spans = defaultdict(list)
    for name, s, e in tr.program_spans:
        if lo <= s <= hi:
            spans[name].append(e - s)
    for name, d in sorted(spans.items()):
        print(f"span {name}: {len(d)}, total {sum(d):.6f} s, mean "
              f"{1e3 * sum(d) / len(d):.3f} ms")
    for dev, ops in sorted(tr.devices.items()):
        busy = T.busy(((o.start, o.end) for o in ops), lo, hi)
        print(f"{dev}: busy {busy:.6f} s")
        for path, s in T.top(S.by_scope(ops, lo, hi), n):
            print(f"  {s:10.6f} s  {100 * s / busy:6.2f} %  {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
