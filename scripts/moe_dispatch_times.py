"""Time the two dispatch forms of ``tpudl.ops.moe.DroplessMoE`` on the
chip, one layer at a time, at the row counts a serving program traces
it with (PERF.md records the table; ISSUE 30: 256 experts of width 512
over a hidden size of 2,048, 8 a token, at 64 decode rows and at a
4,096-row prefill).

    chiprun -- python scripts/moe_dispatch_times.py [--rows 64 512 4096]

Prints one JSON line a (rows, form): milliseconds a call, median of
``--repeats`` timed calls after a warm-up, the call blocked on. A form
that does not fit the chip at a row count says so and goes on. Refuses
to run without a TPU: a time from a CPU is not a device time.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--rows", type=int, nargs="+", default=[64, 512, 4096])
    parser.add_argument("--experts", type=int, default=256)
    parser.add_argument("--per-token", type=int, default=8)
    parser.add_argument("--hidden", type=int, default=2048)
    parser.add_argument("--width", type=int, default=512)
    parser.add_argument("--repeats", type=int, default=20)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from tpudl.ops.moe import DroplessMoE

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"moe_dispatch_times: needs a TPU, JAX found {device}")
    for rows in args.rows:
        x = jax.random.normal(
            jax.random.key(rows), (1, rows, args.hidden), jnp.bfloat16
        )
        real = jnp.ones((1, rows), bool)
        params = None
        for form in ("dense", "sorted"):
            layer = DroplessMoE(
                num_experts=args.experts, experts_per_token=args.per_token,
                intermediate_size=args.width,
                shared_intermediate_size=0, routed_scaling_factor=2.5,
                dispatch=form,
            )
            if params is None:
                # Served weights are bfloat16 already (the router stays
                # float32): cast once, outside the timed call.
                params = jax.jit(lambda: jax.tree_util.tree_map_with_path(
                    lambda path, leaf: leaf if "router" in jax.tree_util.keystr(
                        path) else leaf.astype(jnp.bfloat16),
                    layer.init(jax.random.key(0), x, real)["params"],
                ))()
            call = jax.jit(lambda p, x: layer.apply(
                {"params": p}, x, real, mutable=["moe_stats"]
            )[0])
            line = {"rows": rows, "form": form, "device": device.device_kind}
            try:
                call(params, x).block_until_ready()
                times = []
                for _ in range(args.repeats):
                    t = time.perf_counter()
                    call(params, x).block_until_ready()
                    times.append(1e3 * (time.perf_counter() - t))
                line["ms"] = statistics.median(times)
                line["ms_min"] = min(times)
            except Exception as e:  # does not fit, or does not compile
                line["error"] = f"{type(e).__name__}: {str(e)[:200]}"
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
