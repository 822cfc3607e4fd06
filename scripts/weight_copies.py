"""Name a trace's weight-shaped ``copy_...`` rows: compile the decode
program and each prefill length of the benchmark's decoder
configurations for a described v5e (two layers each, bfloat16 weights as
the cells hold them) and print, for each, what the compiler answers when
the weights' layouts are left to it (``tpudl.serve.weights
.asked_layouts``: the matrices it wants another way round than their
shapes declare), which of them a session holds turned
(``tpudl.models.turned``), and the weight-shaped copies in each
program's ENTRY with the weights as given and as a session holds them:

    python scripts/weight_copies.py [configuration ...]

A compile in the sandbox, no chip: it says what a program does to its
weights in every call, nothing about times.
"""

import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from lowered_text import decoder_programs  # noqa: E402  (sets JAX_PLATFORMS)


def _counted(rows) -> str:
    counts = collections.Counter(rows)
    return ", ".join(
        f"{n} x {row}" for row, n in sorted(counts.items())
    ) or "none"


def main(root: str, only) -> int:
    import jax
    import jax.numpy as jnp

    # A configuration's decode program first: it heads the report.
    programs = sorted(
        decoder_programs(root, jnp.bfloat16),
        key=lambda p: (p[0], p[1] != "decode"),
    )
    from tpudl.models.turned import turned_nodes
    from tpudl.serve.api import prefill_lengths
    from tpudl.serve.weights import asked_layouts, held, weight_copies

    for name, kind, fn, (params, *rest) in programs:
        if only and name not in only:
            continue
        serving, leaves, nbytes = held(params)
        if kind == "decode":
            donate = (1,)
            runs = [("decode", rest)]
            turned = turned_nodes(serving)
            print(f"{name}: a session holds {leaves} kernels "
                  f"({nbytes / 1e6:.1f} MB) turned; asked, the decode "
                  f"program's compiler wants these another way round:")
            for path, shape, order in asked_layouts(fn, params, rest, donate):
                print(f"    {path} {shape} -> {order}"
                      f"{'' if path in turned else '   (held as declared)'}")
        else:
            donate = ()
            ids = rest[0]
            runs = [
                (f"prefill {rows}", [jax.ShapeDtypeStruct(
                    (1, rows), ids.dtype, sharding=ids.sharding)] * 2)
                for rows in prefill_lengths(ids.shape[1])
            ]
        jitted = jax.jit(fn, donate_argnums=donate)
        for what, args in runs:
            copies = [
                _counted(weight_copies(
                    jitted.lower(tree, *args).compile().as_text(), params))
                for tree in (params, serving)
            ]
            print(f"  {what}: as given {copies[0]}; as held {copies[1]}",
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        sys.argv[1:],
    ))
