"""Child process of ``test_bench_files.py``: the toy cell of a benchmark
tree given as the first argument, run by that tree's own harness.

Loads the cell, runs it once past the look for a chip, then runs it under
the kind ``toy_forged`` with each list of checks given after the tree
(comma-separated).  Prints one JSON object: the cell's metric names, the
result of the run, and for each forged list the error that refused it (or
``null`` where it was taken).
"""
import json
import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
TREE = Path(sys.argv[1]).resolve()
sys.path.insert(0, str(TREE))

from bench import run as harness  # noqa: E402

CELL = "toy.glass"
SEED = 2**31 + 19


def main():
    assert Path(harness.__file__).resolve().is_relative_to(TREE), harness.__file__
    cell = harness.load_cell(CELL)
    out = {"end_to_end": [m["name"] for m in cell.end_to_end],
           "per_layer": [m["name"] for m in cell.per_layer],
           "run": harness.run_cell(CELL, SEED, 0.5, False, require_chip=False),
           "forged": {}}
    for report in sys.argv[2:]:
        forged = {"traffic": {"kind": "toy_forged",
                              "report": [c for c in report.split(",") if c]}}
        try:
            harness.run_cell(CELL, SEED, 0.5, False, require_chip=False, overrides=forged)
            out["forged"][report] = None
        except ValueError as err:
            out["forged"][report] = str(err)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
