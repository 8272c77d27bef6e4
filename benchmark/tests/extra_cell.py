"""A cell that is not in the committed BENCHMARK.json, added the way a later PR
adds one: a copy of BENCHMARK.json with one more `workloads` entry, for
`run.py --benchmark <copy>`. The tests rehearse such cells on the CPU; by hand,
for a chip run of the mixed stream:

    python3 benchmark/tests/extra_cell.py benchmark/_out/BENCHMARK.mixed.json
    python3 benchmark/run.py --benchmark benchmark/_out/BENCHMARK.mixed.json \\
        --workload handel4096-51thr-failing.mixed-levels --seed <n> --seconds 30 --trace <0|1>
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

OPEN_BURST = {
    "name": "handel4096-99thr.open-burst", "config": "handel4096-99thr",
    "traffic": "open-poisson-levels", "chips": 1, "why": "rehearsed only",
}
MIXED_LEVELS = {
    "name": "handel4096-51thr-failing.mixed-levels",
    "config": "handel4096-51thr-failing", "traffic": "closed256-mixed-levels",
    "chips": 1,
    "why": "256 clients, closed loop, 1-8 candidates, levels 1-12 minus the 1024 "
           "failing ids and 0-8 more: range8, range64 and range1024 candidates in "
           "one stream; class-pure launch planning",
}


def write_benchmark(path, *entries: dict) -> str:
    """BENCHMARK.json with `entries` appended to `workloads` and to the
    `workloads` list of every per-layer metric the entry's configuration's
    committed cells report; returns `path`."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for entry in entries:
        like = [w["name"] for w in bench["workloads"]
                if w["config"] == entry["config"]]
        for m in bench["per_layer"]:
            if set(like) & set(m["workloads"]):
                m["workloads"].append(entry["name"])
        bench["workloads"].append(entry)
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(bench, f)
    return str(path)


if __name__ == "__main__":
    print(write_benchmark(sys.argv[1], OPEN_BURST, MIXED_LEVELS))
