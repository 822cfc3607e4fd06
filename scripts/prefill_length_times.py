"""Time a serving cell's batch-1 prefill at every length of its ladder
(``tpudl.serve.api.prefill_lengths``; ISSUE 34), on the chip: what a
length costs to run, and what it costs set-up to have.

    chiprun -- python scripts/prefill_length_times.py --workload mistral-7b-l16.shortchat-steady

Builds the cell's session as the benchmark does (``perfbench``'s family
and configuration, weights from ``--seed``), then prints one JSON line
a length: the session's own program, milliseconds a call (median of
``--repeats`` calls after a warm-up, each blocked on, a full prompt of
that length; and how many kernels and bytes the session holds turned,
``tpudl.serve.weights``), and the seconds a second ``jax.jit`` of the
same contract takes to trace, to lower and to compile (with a warm
compile cache that is the cache's read and the load onto the chip): the
parts of ``setup_s`` one more length adds. Refuses to run without a TPU: a time
from a CPU is not a device time.
"""

from __future__ import annotations

import argparse
import importlib
import json
import pathlib
import statistics
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=34)
    parser.add_argument("--repeats", type=int, default=20)
    args = parser.parse_args(argv)

    from perfbench import run

    run.place_compile_cache()
    import jax
    import numpy as np

    from perfbench.device import require_chips
    from perfbench.manifest import Manifest
    from tpudl.models.generate import named
    from tpudl.obs import registry
    from tpudl.serve.api import left_pad

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"prefill_length_times: needs a TPU, JAX found {device}")
    cell = Manifest(str(ROOT)).cell(args.workload)
    family = importlib.import_module(
        f"perfbench.families.{cell['config']['family']}"
    )
    t = time.perf_counter()
    system = family.build(cell["config"], require_chips(1), args.seed)
    built_s = time.perf_counter() - t
    engine = system.session.engine
    # Whether the session holds kernels turned (tpudl.serve.weights).
    gauges = registry().snapshot()["gauges"]
    rng = np.random.default_rng(args.seed)
    for rows in engine.prefill_lengths:
        ids, mask = left_pad(rng.integers(1, 1000, size=rows), rows)
        line = {"workload": args.workload, "rows": rows,
                "device": device.device_kind, "build_s": built_s,
                "weights_relaid_leaves": gauges.get(
                    "serve_weights_relaid_leaves"),
                "weights_relaid_bytes": gauges.get(
                    "serve_weights_relaid_bytes")}
        jax.block_until_ready(engine.prefill_call(engine.params, ids, mask))
        times = []
        for _ in range(args.repeats):
            t = time.perf_counter()
            jax.block_until_ready(
                engine.prefill_call(engine.params, ids, mask)
            )
            times.append(1e3 * (time.perf_counter() - t))
        line["ms"] = statistics.median(times)
        line["ms_min"] = min(times)
        # The same contract under a second jit: what one more length
        # adds to set-up, part by part.
        # (a new function object under the program's own name, so
        # that JAX's trace cache misses and the compile cache hits)
        contract = engine.prefill_call.__wrapped__
        again = jax.jit(named(
            lambda *a, _f=contract: _f(*a), contract.__name__
        ))
        t0 = time.perf_counter()
        traced = again.trace(engine.params, ids, mask)
        t1 = time.perf_counter()
        lowered = traced.lower()
        t2 = time.perf_counter()
        lowered.compile()
        t3 = time.perf_counter()
        line.update(trace_s=t1 - t0, lower_s=t2 - t1, compile_s=t3 - t2)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
