"""Read the numbers ``correct`` compares, over several seeds in one
process: the program as it is, or the control — the program with its
own lower-precision path switched on (``control`` in the configuration
file), which has to come out as not correct.

    python3 -m perfbench.tools.readings --workload <cell> --variant control --seeds 1,2,3 --seconds 10

The limits in the configuration files are set from these readings: above
the largest the sound program gives, below the smallest the control
gives. The benchmark's own runs never run the control.
"""

import argparse
import importlib
import json
import sys

from perfbench.tools._common import open_cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--variant", choices=("program", "control"),
                    default="program")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell, device = open_cell(args.workload, args.rehearse)
    family = importlib.import_module(
        f"perfbench.families.{cell['config']['family']}"
    )
    seen = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        system = family.build(cell["config"], device, seed, args.variant)
        system.warm_up(cell["traffic"], args.seconds)
        record = system.run_window(cell["traffic"], args.seconds)
        system.release()
        check = system.check(record)
        row = {c["name"]: c["value"] for c in check["comparisons"]}
        row.update({c["name"] + "_leaf": c["leaf"]
                    for c in check["comparisons"] if "leaf" in c})
        for c in check["comparisons"]:
            seen.setdefault(c["name"], []).append(c["value"])
        print(json.dumps({"variant": args.variant, "seed": seed, **row,
                          "limits": {c["name"]: c["limit"]
                                     for c in check["comparisons"]}}),
              flush=True)
        del system
    print(json.dumps({
        "variant": args.variant,
        "largest": {k: max(v) for k, v in seen.items()},
        "smallest": {k: min(v) for k, v in seen.items()},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
