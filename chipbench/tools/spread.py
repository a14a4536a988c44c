"""Spreads of a cell's two sets of runs, by the rule the bounds are set from:

    python -m chipbench.tools.spread <dir with set1_<seed>.out and set2_<seed>.out>

For each end-to-end metric and each set, the distance between the first and
third quartile (``statistics.quantiles(values, n=4)``) as a share of the
median; the wider of the two sets; and the same with each set's run farthest
from its median left out (how a check reads tightness).
"""

from __future__ import annotations

import glob
import json
import os
import statistics
import sys


def spread(values: list) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def without_farthest(values: list) -> list:
    med = statistics.median(values)
    far = max(values, key=lambda v: abs(v - med))
    out = list(values)
    out.remove(far)
    return out


def main(argv=None) -> int:
    d = (argv or sys.argv[1:])[0]
    sets: dict = {}
    for path in sorted(glob.glob(os.path.join(d, "set*_*.out"))):
        which = os.path.basename(path).split("_")[0]
        with open(path) as fh:
            line = json.loads(fh.read().strip().splitlines()[-1])
        if not line["correct"]:
            print(f"{path}: correct is false")
        for name, m in line["metrics"].items():
            sets.setdefault(name, {}).setdefault(which, []).append(m["value"])
    for name, by_set in sets.items():
        rows = []
        for which, vals in sorted(by_set.items()):
            rows.append((which, len(vals), statistics.median(vals), spread(vals),
                         spread(without_farthest(vals))))
        widest = max(r[3] for r in rows)
        print(f"{name}: widest spread {100 * widest:.3f}%  (five times: {500 * widest:.2f}%)")
        for which, n, med, sp, sp_wo in rows:
            print(f"   {which}: n={n} median {med:.6g} spread {100 * sp:.3f}% "
                  f"without the farthest run {100 * sp_wo:.3f}%")
    return 0


if __name__ == "__main__":
    sys.exit(main())
