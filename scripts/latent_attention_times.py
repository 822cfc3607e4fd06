"""Check and time the latent paged-attention kernel on the chip against
the gather path it replaces (``tpudl.ops.paged_attention``), at the
sarvam cell's sizes: 128 slots x 80 pages of 16 positions, a pool held
``[10241, 8, 1152]`` bfloat16 (two positions of 576 a held row), 64
heads, and the cell's pattern of live positions (a prompt of median 128
left-padded to the 512 window, then a uniform share of an answer of
median 256). PERF.md records the table (ISSUE 31).

    chiprun -- python scripts/latent_attention_times.py [--pages-per-block 8 16 32]

Prints one JSON line a (pattern, path): the largest and the mean gap
between the kernel's and the gather path's ``u`` (both bfloat16 on the
chip), and milliseconds a call, the median of ``--repeats`` timed
programs of ``--layers`` calls each after a warm-up. Refuses to run
without a TPU: a time from a CPU is not a device time.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

SLOTS, PAGES, PAGE, WINDOW = 128, 80, 16, 512
HEADS, RANK, ROPE, FOLD = 64, 512, 64, 2


def patterns(rng):
    """name -> (start [B], lens [B]) as a decode dispatch sees them."""
    import numpy as np

    def lognormal(median, sigma, low, high):
        draw = median * np.exp(sigma * rng.standard_normal(SLOTS))
        return np.clip(draw, low, high).astype(np.int32)

    prompt = lognormal(128, 0.7, 32, 512)
    answer = lognormal(256, 0.5, 64, 768)
    done = (answer * rng.uniform(size=SLOTS)).astype(np.int32)
    zeros = np.zeros(SLOTS, np.int32)
    return {
        "cell": (WINDOW - prompt, WINDOW + done),
        "full": (zeros, zeros + PAGES * PAGE - 1),
        "idle": (zeros, zeros),
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--pages-per-block", type=int, nargs="+",
                        default=[8, 16, 32])
    parser.add_argument("--layers", type=int, default=5)
    parser.add_argument("--repeats", type=int, default=20)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import tpudl.ops.paged_attention as pa
    from tpudl.models.paged import PagedView

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"latent_attention_times: needs a TPU, JAX found {device}")
    rng = np.random.default_rng(31)
    width = RANK + ROPE
    pages = SLOTS * PAGES + 1
    pool = jax.random.normal(
        jax.random.key(0), (pages, PAGE // FOLD, FOLD * width), jnp.bfloat16
    )
    queries = jax.random.normal(
        jax.random.key(1), (args.layers, SLOTS, 1, HEADS, width), jnp.bfloat16
    )
    table = jnp.asarray(
        rng.permutation(np.arange(1, pages)).reshape(SLOTS, PAGES), jnp.int32
    )

    def program(impl):
        def run(queries, pool, table, start, lens):
            view = PagedView(table, start, lens, PAGE, False)
            return jnp.stack([
                pa.paged_latent_attention(
                    q, pool, view, rank=RANK, scale=width ** -0.5, impl=impl,
                ) for q in queries
            ])
        return jax.jit(run)

    def timed(call, *inputs):
        call(*inputs).block_until_ready()
        times = []
        for _ in range(args.repeats):
            t = time.perf_counter()
            call(*inputs).block_until_ready()
            times.append(1e3 * (time.perf_counter() - t) / args.layers)
        return statistics.median(times), min(times)

    for name, (start, lens) in patterns(rng).items():
        start, lens = jnp.asarray(start), jnp.asarray(lens)
        inputs = (queries, pool, table, start, lens)
        live = int((lens - start + 1).sum())
        gather = program("reference")
        want = gather(*inputs).astype(jnp.float32)
        line = {"pattern": name, "path": "gather", "live_positions": live,
                "device": device.device_kind}
        line["ms"], line["ms_min"] = timed(gather, *inputs)
        print(json.dumps(line), flush=True)
        for ppb in args.pages_per_block:
            pa.LATENT_PAGES_PER_BLOCK = ppb
            jax.clear_caches()
            line = {"pattern": name, "path": "kernel", "pages_per_block": ppb,
                    "live_positions": live, "device": device.device_kind}
            try:
                call = program("fused")
                got = call(*inputs).astype(jnp.float32)
                gap = jnp.abs(got - want)
                line["gap_max"] = float(gap.max())
                line["gap_mean"] = float(gap.mean())
                line["want_abs_mean"] = float(jnp.abs(want).mean())
                line["ms"], line["ms_min"] = timed(call, *inputs)
            except Exception as e:  # does not compile, or does not fit
                line["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
