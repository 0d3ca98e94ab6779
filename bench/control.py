#!/usr/bin/env python3
"""Read the control of one or more cells of one configuration on a chip.

    python3 bench/control.py --workloads <cell>[,<cell>...] --seeds 1,2,3

The control is the plain reference computed at the precision below the one
the configuration states (three bf16 passes for float32 at ``highest``), put
in the program's place; the comparison has to find it not correct.  For each
seed this fits the reference and the control once and prints, per cell, one
JSON line with the numbers the cell compares.  The benchmark's own runs do
not run it; its readings set the upper end of each limit (``PERF.md``).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import deploy, harness  # noqa: E402
from bench.reference import algorithm2  # noqa: E402


def readings(cells, seed: int, overrides=None):
    """``{cell: values}`` of the control for one seed; the cells share one
    configuration."""
    bench = harness.benchmark()
    specs = [harness.find_cell(bench, c) for c in cells]
    if len({s["config"] for s in specs}) != 1:
        raise ValueError("the cells must share one configuration")
    overrides = {k: v for k, v in (overrides or {}).items() if k != "traffic"}
    config = deploy.load_config(specs[0]["config"], overrides)
    Xtr, ytr, Xte, _ = deploy.make_data(config, seed)
    ref = algorithm2.fit(Xtr, ytr, config["method"], config["svm"], "highest")
    low = algorithm2.fit(Xtr, ytr, config["method"], config["svm"], "high")
    out = {}
    for spec in specs:
        traffic = deploy.load_json(deploy.BENCH / "traffic" / f"{spec['traffic']}.json")
        driver = deploy.load_module("drivers", traffic["driver"])
        ctx = deploy.Context(config, traffic, seed)
        out[spec["name"]] = driver.control(ctx, ref, low, (Xtr, ytr, Xte))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workloads", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    harness.enable_compile_cache()
    harness.chips(1)
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        for cell, values in readings(args.workloads.split(","), seed).items():
            print(json.dumps({"control": cell, "seed": seed, "values": values,
                              "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
