"""Paper Fig 5.3 companion: measured per-iteration cost of the distributed
solvers at several device counts (fake CPU devices — measures the
per-iteration WORK overhead of pipelining at zero comm latency; the
latency-dependent speedup is modeled in bench_overlap).

Expectation (validates paper Table 3.1): p-BiCGSafe pays a bounded
per-iteration overhead (extra recurrence AXPYs) relative to ssBiCGSafe2 —
the price paid to make the reduction hideable.  On a zero-latency fabric
the ratio is <~1.6x; the latency model shows where hiding wins it back.
"""
from __future__ import annotations

import json
import os

from .common import fmt_table, run_cpu_child, write_json


def run(quick: bool = False):
    counts = [1, 4] if quick else [1, 2, 4, 8]
    rows, recs = [], {}
    child = os.path.join(os.path.dirname(__file__), "_scaling_child.py")
    for nd in counts:
        rec = json.loads(run_cpu_child([child, nd], timeout=1800))
        recs[nd] = rec
        ratio = rec["p-bicgsafe"]["per_iter_us"] / \
            rec["ssbicgsafe2"]["per_iter_us"]
        rows.append([nd,
                     f"{rec['ssbicgsafe2']['per_iter_us']:.0f}",
                     f"{rec['p-bicgsafe']['per_iter_us']:.0f}",
                     f"{ratio:.2f}x"])
    print("\n== bench_scaling (zero-latency per-iteration work) ==")
    print(fmt_table(rows, ["devices", "ss us/iter", "p us/iter",
                           "p overhead"]))
    write_json("bench_scaling.json", recs)
    return recs


if __name__ == "__main__":
    run()
