"""Write a trace as the plain structure ``perfbench.trace`` reduces,
cut to the first events of each line — the form ``perfbench/tests``
keeps a small recorded trace in.

    python3 -m perfbench.tools.dump_trace <trace_dir> <out.json> [events_per_line]
"""

import json
import sys

from perfbench import trace as tr


def main(argv) -> int:
    limit = int(argv[3]) if len(argv) > 3 else 200
    raw = tr.load(tr.find_xplane(argv[1]), max_events_per_line=limit)
    with open(argv[2], "w") as f:
        json.dump(raw, f)
    for plane in raw["planes"]:
        print(plane["name"], [(l["name"], len(l["events"]))
                              for l in plane["lines"]])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
