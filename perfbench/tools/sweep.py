"""Find the knee of an open-loop cell: offer a few fixed rates, one
window each, in one process, and print how the backlog moved.

    python3 -m perfbench.tools.sweep --workload <cell> --rates 8,10,12 --seconds 20

The knee is the highest rate at which the backlog (requests submitted
and still waiting for their first token) at the end of the window is no
larger than at its middle. It is found once, on the chip, and written
into the traffic file as a number; a run never searches for it.
"""

import argparse
import importlib
import json
import sys

from perfbench.stats import percentile
from perfbench.tools._common import open_cell


def backlog(reqs, t: float) -> int:
    return sum(
        1 for r in reqs
        if r["submit_s"] is not None and r["submit_s"] <= t
        and not (r["token_s"] and r["token_s"][0] <= t)
    )


def mean_backlog(reqs, lo: float, hi: float, points: int = 21) -> float:
    ts = [lo + (hi - lo) * i / (points - 1) for i in range(points)]
    return sum(backlog(reqs, t) for t in ts) / points


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell, device = open_cell(args.workload, args.rehearse)
    family = importlib.import_module(
        f"perfbench.families.{cell['config']['family']}"
    )
    system = None
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = {**cell["traffic"], "rate_per_s": rate}
        if system is None:
            system = family.build(cell["config"], device, args.seed)
        system.seed = args.seed + i
        system.warm_up(mix, args.seconds)
        rec = system.run_window(mix, args.seconds)
        reqs, s = rec["requests"], args.seconds
        done = [r for r in reqs if r["token_s"]]
        ttft = [r["token_s"][0] - r["due_s"] for r in done]
        tpot = [(r["token_s"][-1] - r["token_s"][0]) / (len(r["token_s"]) - 1)
                for r in done if len(r["token_s"]) > 1]
        print(json.dumps({
            "rate_per_s": rate, "requests": len(reqs),
            "backlog_mid": backlog(reqs, 0.5 * s),
            "backlog_end": backlog(reqs, s),
            "mean_backlog_40_50": mean_backlog(reqs, 0.4 * s, 0.5 * s),
            "mean_backlog_90_100": mean_backlog(reqs, 0.9 * s, s),
            "ttft_p50_ms": 1e3 * percentile(ttft, 50),
            "ttft_p95_ms": 1e3 * percentile(ttft, 95),
            "tpot_p50_ms": 1e3 * percentile(tpot, 50),
            "tpot_p95_ms": 1e3 * percentile(tpot, 95),
            "drained_at_s": max(r["token_s"][-1] for r in done),
            "failed": sum(r["finish_reason"] not in ("length", "eos")
                          for r in reqs),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
