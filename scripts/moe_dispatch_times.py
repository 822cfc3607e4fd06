"""Time the two dispatch forms of ``tpudl.ops.moe.DroplessMoE`` on the
chip, one layer at a time, at the row counts a serving program traces
it with, and the sorted form's three grouped matmuls ALONE on sorted
rows of the same shape, once through ``jax.lax.ragged_dot`` and once
through the kernel of ``tpudl.ops.grouped_matmul`` called directly.
PERF.md records the tables: ISSUE 30, 256 experts of width 512 over a
hidden size of 2,048, 8 a token (the defaults; Laguna's shape), at 64
decode rows and at a 4,096-row prefill; ISSUE 42, that shape and
xing4's at the two prefill lengths; ISSUE 45, those and GLM's share
(16 of 256 experts held) at its 8,192-row prefill:

    chiprun -- python scripts/moe_dispatch_times.py [--rows 64 512 4096]
    chiprun -- python scripts/moe_dispatch_times.py --experts 64 \
        --per-token 4 --hidden 3584 --width 1024 --rows 2048 4096
    chiprun -- python scripts/moe_dispatch_times.py --experts 256 \
        --per-token 8 --hidden 2048 --width 512 --rows 2048 4096
    chiprun -- python scripts/moe_dispatch_times.py --experts 256 \
        --held 16 --per-token 8 --hidden 6144 --width 2048 --rows 8192

Prints one JSON line a (rows, form) and a (rows, grouped matmul):
milliseconds a call, median of ``--repeats`` timed calls after a
warm-up, the call blocked on. The layer takes the kernel or
``ragged_dot`` by its own rule (``grouped_kernel`` in the line says
which; with the kernel the down projection puts its rows back in
assignment order itself). The matmuls alone are gate and up over the
sorted rows, ``silu(gate) * up``, and down with a float32 result in
sorted order, over the groups a top-k of random scores gives (with
``--held``, the choices of the first experts alone: the other rows lie
behind the last group). A last line a row count,
``sorted_outside_matmuls``, is the layer's sorted form less the
kernel's three matmuls alone: the router, the sort, the gather into
sorted order, the gates and the way back to token order. A form that
does not fit the chip at a row count says so and goes on. Refuses to
run without a TPU: a time from a CPU is not a device time.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))


def _timed(call, repeats):
    """``call()`` once to compile, then timed: median and least ms."""
    call().block_until_ready()
    times = []
    for _ in range(repeats):
        t = time.perf_counter()
        call().block_until_ready()
        times.append(1e3 * (time.perf_counter() - t))
    return {"ms": statistics.median(times), "ms_min": min(times)}


def _report(line, call, repeats, note=lambda: {}):
    try:
        line.update(_timed(call, repeats))
    except Exception as e:  # does not fit, or does not compile
        line["error"] = f"{type(e).__name__}: {str(e)[:200]}"
    line.update(note())
    print(json.dumps(line), flush=True)
    return line.get("ms")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--rows", type=int, nargs="+", default=[64, 512, 4096])
    parser.add_argument("--experts", type=int, default=256)
    parser.add_argument(
        "--held", type=int, default=None,
        help="experts this share holds, the first of --experts (default: all)",
    )
    parser.add_argument("--per-token", type=int, default=8)
    parser.add_argument("--hidden", type=int, default=2048)
    parser.add_argument("--width", type=int, default=512)
    parser.add_argument("--repeats", type=int, default=20)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from tpudl.obs import registry
    from tpudl.ops.grouped_matmul import grouped_matmul
    from tpudl.ops.moe import SORTED_DISPATCH_ROWS, DroplessMoE

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"moe_dispatch_times: needs a TPU, JAX found {device}")
    took_kernel = registry().counter("serve_moe_grouped_kernel")
    held = args.held or args.experts
    for rows in args.rows:
        x = jax.random.normal(
            jax.random.key(rows), (1, rows, args.hidden), jnp.bfloat16
        )
        real = jnp.ones((1, rows), bool)
        params, layer_ms = None, {}
        for form in ("dense", "sorted"):
            layer = DroplessMoE(
                num_experts=args.experts, experts_per_token=args.per_token,
                intermediate_size=args.width,
                shared_intermediate_size=0, routed_scaling_factor=2.5,
                experts_held=(0, held), dispatch=form,
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
            before = took_kernel.value
            layer_ms[form] = _report(
                line, lambda: call(params, x), args.repeats,
                lambda: {"grouped_kernel": took_kernel.value > before},
            )
        if rows <= SORTED_DISPATCH_ROWS:
            continue  # no served program sorts so few rows
        # The three grouped matmuls alone, on rows that lie sorted.
        keys = jax.random.split(jax.random.key(rows + 1), 3)
        _, chosen = jax.lax.top_k(
            jax.random.normal(keys[0], (rows, args.experts)), args.per_token
        )
        sizes = jnp.zeros((args.experts,), jnp.int32).at[
            chosen.reshape(-1)
        ].add(1)[:held]
        sorted_rows = jax.random.normal(
            keys[1], (rows * args.per_token, args.hidden), jnp.bfloat16
        )
        experts = params["gate_proj"], params["up_proj"], params["down_proj"]
        wg, wu, wd = (e["kernel"] for e in experts)
        for name, grouped in (
            ("ragged_dot", jax.lax.ragged_dot), ("kernel", grouped_matmul)
        ):
            def three(lhs, wg, wu, wd, sizes, grouped=grouped):
                act = jax.nn.silu(grouped(lhs, wg, sizes)) * grouped(
                    lhs, wu, sizes
                )
                return grouped(
                    act, wd, sizes, preferred_element_type=jnp.float32
                )

            call = jax.jit(three)
            line = {
                "rows": rows, "assignments": rows * args.per_token,
                "grouped_matmuls": name, "device": device.device_kind,
            }
            layer_ms[name] = _report(
                line, lambda: call(sorted_rows, wg, wu, wd, sizes),
                args.repeats,
            )
        if layer_ms.get("sorted") and layer_ms.get("kernel"):
            print(json.dumps({
                "rows": rows, "sorted_outside_matmuls": True,
                "ms": layer_ms["sorted"] - layer_ms["kernel"],
                "device": device.device_kind,
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
