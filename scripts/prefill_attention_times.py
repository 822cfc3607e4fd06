"""Time the causal attention of a long prefill's chunk over itself on the
chip, one layer's call at a time, at the shapes of the cells whose
prefills are attended in blocks (ISSUE 46 and ISSUE 48, step 0):

    glm             1 x 8,192 rows, 64 heads, keys 192 + 64 = 256, values
                    256, an indexer's choice [1, 8192, 8192] that keeps
                    about 2,048 keys a query, 1,920 rows of left padding
    xing4-*         1 x 2,048 and 1 x 4,096 rows, 32 heads, keys 128 + 64 =
                    192 (zero-padded to 256 by the program), values 128
    mimo-full-*     1 x 8,192 and 1 x 16,384 rows, 64 query heads on 4 KV
                    heads, keys 192, values 128, about half the rows padding
    mimo-window-*   the same rows, 64 on 8, a window of 128 and a sink
                    (the XLA blocks only: the kernel takes neither)
    laguna-full     1 x 4,096 rows, 48 on 8, 128 / 128
    laguna-window   1 x 4,096 rows, 64 on 8, a window of 512 (the blocks only)

and by three paths: ``blocked`` (``llama._blocked_attention``'s XLA
blocks, the rule answered "no"), ``flash_fwd`` (``flash_attention._fwd``
as it is, at the nearest shape it takes: equal widths and head counts,
causal, the key-validity row, no choice, its transposes inside the timed
program; the latent shapes only) and ``kernel``
(``flash_attention.prefill_attention``, the query heads of a group a
grid step) at each ``--blocks`` pair. PERF.md §6 (PR 46, PR 48) records
the tables; PR 48's also hold a band walk and one head a grid step, as
that PR's first build had them and the tree does not.

    chiprun -- python scripts/prefill_attention_times.py [--shapes glm xing4-4096] [--blocks 512x512 1024x1024] [--no-padding]

Prints one JSON line a (shape, path, blocks): milliseconds a call
(the median and the least of ``--repeats`` after a warm-up) and the
largest and mean gap on the real rows to the XLA blocks run in float32.
Refuses to run without a TPU: a time from a CPU is not a device time.
"""

from __future__ import annotations

import argparse
import importlib
import json
import pathlib
import statistics
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

#: name -> (rows, query heads, KV heads, key width, value width, keys
#: chosen a query (0: no choice), rows of left padding, window, sink)
SHAPES = {
    "glm": (8192, 64, 64, 256, 256, 2048, 1920, 0, False),
    "xing4-2048": (2048, 32, 32, 192, 128, 0, 448, 0, False),
    "xing4-4096": (4096, 32, 32, 192, 128, 0, 960, 0, False),
    "mimo-full-8192": (8192, 64, 4, 192, 128, 0, 3776, 0, False),
    "mimo-full-16384": (16384, 64, 4, 192, 128, 0, 5760, 0, False),
    "mimo-window-8192": (8192, 64, 8, 192, 128, 0, 3776, 128, True),
    "mimo-window-16384": (16384, 64, 8, 192, 128, 0, 5760, 128, True),
    "laguna-full": (4096, 48, 8, 128, 128, 0, 1088, 0, False),
    "laguna-window": (4096, 64, 8, 128, 128, 0, 1088, 512, False),
}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--shapes", nargs="+", default=list(SHAPES),
                        choices=list(SHAPES))
    parser.add_argument("--blocks", nargs="+",
                        default=["512x512", "512x1024", "1024x1024"],
                        help="block_q x block_k pairs for the Pallas paths")
    parser.add_argument("--paths", nargs="+",
                        default=["blocked", "flash_fwd", "kernel"])
    parser.add_argument("--no-padding", action="store_true",
                        help="every row real, whatever the shape says")
    parser.add_argument("--repeats", type=int, default=5)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from tpudl.models import llama

    # (``tpudl.ops.flash_attention`` the attribute is the function.)
    fa = importlib.import_module("tpudl.ops.flash_attention")

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"prefill_attention_times: needs a TPU, JAX found {device}")
    blocks = [tuple(int(n) for n in pair.split("x")) for pair in args.blocks]

    def timed(call, *inputs):
        out = call(*inputs)
        out.block_until_ready()
        times = []
        for _ in range(args.repeats):
            t = time.perf_counter()
            call(*inputs).block_until_ready()
            times.append(1e3 * (time.perf_counter() - t))
        return out, statistics.median(times), min(times)

    def xla_blocks(q, k, v, valid, scale, chosen, window, sink):
        # Today's path whatever the rule says of this chip.
        return llama._blocked_attention(
            q, k, v, valid, window, llama.PREFILL_BLOCK, scale, chosen, sink
        )

    for name in args.shapes:
        s, h, hkv, dk, dv, kept, pad, window, has_sink = SHAPES[name]
        pad = 0 if args.no_padding else pad
        keys = jax.random.split(jax.random.key(46), 5)
        q = jax.random.normal(keys[0], (1, s, h, dk), jnp.bfloat16)
        k = jax.random.normal(keys[1], (1, s, hkv, dk), jnp.bfloat16)
        v = jax.random.normal(keys[2], (1, s, hkv, dv), jnp.bfloat16)
        valid = (jnp.arange(s) >= pad)[None]
        sink = jax.random.normal(keys[4], (h,)) if has_sink else None
        chosen = None
        if kept:
            # About ``kept`` of the keys a query can see, drawn evenly.
            seen = (jnp.arange(s) - pad + 1).clip(1)[:, None]
            chosen = (
                jax.random.uniform(keys[3], (1, s, s)) * seen < kept
            )
        scale = dk ** -0.5
        real = valid[0]
        base = {"shape": name, "rows": s, "heads": h, "kv_heads": hkv,
                "key_width": dk, "value_width": dv, "chosen": kept,
                "pad_rows": pad, "window": window, "sink": has_sink,
                "device": device.device_kind}

        def f32(q, k, v, valid, chosen, sink):
            with jax.default_matmul_precision("highest"):
                return xla_blocks(
                    q.astype(jnp.float32), k.astype(jnp.float32),
                    v.astype(jnp.float32), valid, scale, chosen, window, sink,
                )
        want = jax.jit(f32)(q, k, v, valid, chosen, sink)[0, real]

        def report(line, call, *inputs):
            try:
                got, line["ms"], line["ms_min"] = timed(call, *inputs)
                got = got.reshape(1, s, h, dv)[0, real]
                gap = jnp.abs(got.astype(jnp.float32) - want)
                line["gap_max"] = float(gap.max())
                line["gap_mean"] = float(gap.mean())
                line["want_abs_mean"] = float(jnp.abs(want).mean())
            except Exception as e:  # does not compile, or does not fit
                line["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            print(json.dumps(line), flush=True)

        if "blocked" in args.paths:
            report(
                {**base, "path": "blocked"},
                jax.jit(lambda q, k, v, valid, chosen, sink: xla_blocks(
                    q, k, v, valid, scale, chosen, window, sink)),
                q, k, v, valid, chosen, sink,
            )
        # A latent model's program hands the kernel keys zero-padded to
        # whole lanes (``llama._kernel_operands``); a grouped-query
        # model's keys are as wide as its heads. The existing forward
        # takes one width: values padded to the keys' (more work than
        # the shape has), and no choice (less).
        latent = h == hkv
        wide = max(-(-dk // 128) * 128, dv) if latent else dk
        qw, kw = (jnp.pad(x, ((0, 0),) * 3 + ((0, wide - dk),)) for x in (q, k))
        kvm = valid.astype(jnp.float32)
        seed = jnp.zeros((2,), jnp.uint32)
        for bq, bk in blocks if "flash_fwd" in args.paths and latent else ():
            if bq > s or bk > s:
                continue
            vw = jnp.pad(v, ((0, 0),) * 3 + ((0, wide - dv),))

            def flash(q, k, v, kvm, bq=bq, bk=bk):
                o, _, _ = fa._fwd(q, k, v, kvm, seed, True, scale, bq, bk, False)
                return o[:, :, :s, :dv].transpose(0, 2, 1, 3)
            report(
                {**base, "path": "flash_fwd", "block_q": bq, "block_k": bk,
                 "note": f"widths {wide}/{wide}, no choice"},
                jax.jit(flash), qw, kw, vw, kvm,
            )
        # (A window or a sink: the kernel takes neither.)
        for bq, bk in blocks if "kernel" in args.paths and not window else ():
            if bq > s or bk > s:
                continue

            # The kernel reads keys and values and writes its result
            # as [B, S, H x D] and reads the query with its positions
            # minor ([B, H x D, S]): given and taken so, the reshapes
            # around the call are none (a program's producers write
            # them so; an ARGUMENT held [B, S, H, D] is copied first).
            def kernel(qt, k, v, valid, chosen, bq=bq, bk=bk):
                q = qt.reshape(1, h, -1, s).transpose(0, 3, 1, 2)
                k, v = (x.reshape(1, s, hkv, -1) for x in (k, v))
                return fa.prefill_attention(
                    q, k, v, valid, scale, chosen, block_q=bq, block_k=bk,
                ).reshape(1, s, -1)
            report(
                {**base, "path": "kernel", "block_q": bq, "block_k": bk},
                jax.jit(kernel),
                qw.transpose(0, 2, 3, 1).reshape(1, -1, s),
                kw.reshape(1, s, -1), v.reshape(1, s, -1), valid, chosen,
            )
    return 0


if __name__ == "__main__":
    sys.exit(main())
