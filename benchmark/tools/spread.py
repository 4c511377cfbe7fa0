#!/usr/bin/env python3
"""The spread of each metric over the sets that ``runset.sh`` left under
``chiprun_out/sets/<cell>/``: per set the median and (Q3 - Q1) / median,
quartiles as ``statistics.quantiles(values, n=4)`` gives them.

    python3 benchmark/tools/spread.py <cell> <tag> [<tag> ...]
"""
import glob
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    cell, tags = sys.argv[1], sys.argv[2:]
    for tag in tags:
        values: dict = {}
        for path in sorted(glob.glob(os.path.join(
                ROOT, "chiprun_out", "sets", cell, f"{tag}.*.t0.log"))):
            with open(path, errors="replace") as fh:
                last = fh.read().strip().splitlines()[-1]
            try:
                result = json.loads(last)
            except ValueError:
                print(f"{path}: no result line")
                continue
            if not result["correct"]:
                print(f"{path}: correct is false")
            for name, m in result["metrics"].items():
                values.setdefault(name, []).append(m["value"])
        for name, vs in sorted(values.items()):
            med = statistics.median(vs)
            q1, _, q3 = statistics.quantiles(vs, n=4) if len(vs) > 1 \
                else (med, med, med)
            print(f"{cell} {tag} {name}: n={len(vs)} median={med:.6g} "
                  f"spread={(q3 - q1) / med:.5f} "
                  f"values={[round(v, 4) for v in vs]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
