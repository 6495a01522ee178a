#!/usr/bin/env python
"""Readings that set the limits of ``correct`` (not run by the driver).

    python benchmark/calibrate.py --workload <cell> --seeds 1,2,... \\
        --control-seeds 7,8,9 --seconds 3

One process: the program on every seed, then the control (the plain
reference in the program's place, one precision down or with the
configuration's guarantee broken, ``build(..., control=True)``) on
every control seed, each at the cell's own sizes and load with a short
window.  Prints one line per run and, last, each number compared with
its lower reading (the largest over the program's seeds) and its upper
reading (the smallest over the control's).
"""

from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None, chips=run.require_chips, root=run.ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    cell = run.spec.load_cell(args.workload, root)
    try:
        devices = chips(cell.chips)
    except run.NoChip as e:
        print(f"calibrate.py: {e}", file=sys.stderr)
        return 2
    from incubator_brpc_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    lower, upper = {}, {}
    for control, seeds, into in ((False, args.seeds, lower),
                                 (True, args.control_seeds, upper)):
        for seed in (int(s) for s in seeds.split(",")):
            res = run.run_once(cell, devices, seed, args.seconds, False,
                               control=control, emit=lambda _l: None)
            print(json.dumps({"control": control, "seed": seed,
                              "correct": res["correct"],
                              "completed": res["attempted"] - res["failed"],
                              "checks": res["checks"]}), flush=True)
            for k, c in res["checks"].items():
                if c.get("at_least"):
                    continue
                pick = min if control else max
                into[k] = pick(into.get(k, c["value"]), c["value"])
    print(json.dumps({"workload": cell.name, "lower": lower,
                      "upper": upper}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
