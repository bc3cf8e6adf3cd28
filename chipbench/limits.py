"""Read, on the chip and in one process, what the limits of a cell's ``correct``
are set from: over ``--seeds`` the program's numbers against the reference (the
lower reading is their largest), and the control's and the planted faults' (the
upper reading is their smallest).

``python chipbench/limits.py --workload <name> --seeds 1,2,3 [--seconds s]``

A benchmark run never calls this. It drives the same set-up, window, release and
check as ``run.py`` and then asks the driver for its controls.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:] = [ROOT] + [p for p in sys.path if os.path.abspath(p or ".") not in (HERE, ROOT)]

from chipbench import faults, lib, run


class NoHooks:
    def tick(self, elapsed):
        pass

    def close(self):
        pass


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=0.0)
    parser.add_argument("--controls", type=int, default=3,
                        help="on how many of the first seeds the control and the faults are read too")
    parser.add_argument("--fault", help="a fault of chipbench/faults.py to plant in the program")
    args = parser.parse_args(argv)

    run.enable_cache()
    workload, config = lib.load_cell(args.workload)
    driver = lib.load_module("drivers", workload["driver"])
    lower: dict = {}
    upper: dict = {}
    for index, seed in enumerate(int(s) for s in args.seeds.split(",")):
        ctx = run.Context(args.workload, workload, config, seed, int(workload["chips"]))
        run.find_devices(ctx.chips)
        with faults.planted(args.fault):
            state = driver.setup(ctx)
            if args.seconds > 0:
                driver.window(ctx, state, args.seconds, NoHooks())
            driver.release(state)
        numbers = driver.check(ctx, state)
        line = {"seed": seed, "program": numbers}
        for name, value in numbers.items():
            if not name.startswith("_"):
                lower[name] = max(lower.get(name, 0.0), value)
        if index < args.controls:
            for label, got in driver.control_readings(ctx, state).items():
                line[label] = got
                for name, value in got.items():
                    if not name.startswith("_"):
                        key = f"{label}.{name}"
                        upper[key] = min(upper.get(key, float("inf")), value)
        print(json.dumps(line), flush=True)
        del state
        lib.free_device_memory()
    print(json.dumps({"lower_readings": lower, "upper_readings": upper}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
