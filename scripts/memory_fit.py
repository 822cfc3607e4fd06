"""Memory-fit report for configs[4] at its DECLARED scale — no allocation.

BASELINE.json configs[4] declares "Llama-3-8B LoRA fine-tune, FSDP->GSPMD
sharding on v5p-64". No 64-chip slice (or 8B of HBM) is needed to validate
that deployment: every per-device buffer size is a pure function of the
abstract parameter tree (``jax.eval_shape`` — zero bytes materialized),
the sharding rules (strategy_rules("lora") = LORA_RULES +
TP_TRANSFORMER_RULES, exactly what notebooks/nlp/finetune_lora.py trains
with), and the mesh shape (cfg.mesh.fit(64): dp=4, fsdp=8, tp=2 over 64
fake CPU devices). This script builds the real NamedShardings — including
the per-dimension divisibility clamping of tpudl.parallel.sharding — and
sums ``shard_shape`` bytes per device for:

- parameters (f32 masters; the frozen 8B base + LoRA adapters + head);
- AdamW moments — ONLY trainable (LoRA/head) leaves carry any, because
  lora_optimizer routes frozen leaves to set_to_zero (the memory win
  that makes 8B LoRA fit small meshes at all);
- peak activations at cfg.seq_len (2048), as a documented analytic
  UPPER BOUND for the per-layer-remat + flash-attention configuration
  the LoRA vertical runs (notebooks/nlp/finetune_lora.py): stored
  residual-stream inputs for every layer plus the live recompute /
  gradient working set of one block, batch sharded over (dp, fsdp) and
  projection dims over tp;
- the largest transient all-gathered kernel (fsdp gathers a full bf16
  copy of one layer's weight at a time).

Exit is nonzero if the total exceeds the fit bar (half of v5p HBM — the
other half is headroom for XLA temporaries, collectives buffers, and the
infeed), so this doubles as a CI guard. Run:

    python scripts/memory_fit.py            # v5p-64, llama3_8b_lora
    python scripts/memory_fit.py --devices 16 --hbm-gb 95 --json
"""

from __future__ import annotations

import argparse
import json
import math
import os
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))


def _setup_fake_devices(n: int):
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    devices = jax.devices("cpu")
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} fake CPU devices, got {len(devices)}; set XLA_FLAGS "
            f"before the first jax use"
        )
    return devices[:n]


def _tree_device_bytes(tree, shardings) -> int:
    """Per-device bytes of an abstract tree under NamedShardings: the sum
    of each leaf's shard_shape footprint (every device holds exactly one
    shard of every leaf; replicated leaves count full size)."""
    import jax

    total = 0
    for leaf, sh in zip(jax.tree.leaves(tree), jax.tree.leaves(shardings)):
        if not hasattr(leaf, "shape"):
            continue
        total += math.prod(sh.shard_shape(leaf.shape)) * leaf.dtype.itemsize
    return total


def activation_upper_bound_bytes(
    cfg_model, batch_per_device: int, seq_local: int, tp: int
) -> int:
    """Documented analytic UPPER BOUND on per-device activation bytes for
    one train step of the remat+flash Llama block stack (bf16 activations,
    2 bytes):

    - stored residuals: per-layer remat keeps each block's input
      [b, s, H] alive for the backward -> L * b * s * H;
    - live working set of the block being (re)computed + differentiated,
      with tp sharding the projection outputs: q/k/v/o + attention
      workspace ~= 4H/tp + GQA kv 2*(H*kv/H)/tp, gated MLP ~= 3I/tp,
      plus ~4H of residual/norm/gradient mirrors (unsharded by tp).
    Flash attention keeps no [S, S] term at any length.
    """
    H, I, L = (
        cfg_model.hidden_size,
        cfg_model.intermediate_size,
        cfg_model.num_layers,
    )
    kv_frac = cfg_model.num_kv_heads / cfg_model.num_heads
    stored = L * H
    live = (4 * H + 2 * H * kv_frac + 3 * I) / tp + 4 * H
    return int(batch_per_device * seq_local * (stored + live) * 2)


def report(config_name: str, n_devices: int, hbm_gb: float) -> dict:
    devices = _setup_fake_devices(n_devices)
    import jax
    import jax.numpy as jnp

    from tpudl.config import get_config
    from tpudl.models.lora import lora_optimizer, trainable_param_count
    from tpudl.models.registry import build_model
    from tpudl.parallel.sharding import strategy_rules, tree_shardings
    from tpudl.runtime.mesh import make_mesh
    from tpudl.train.optim import make_optimizer

    cfg = get_config(config_name)
    spec = cfg.mesh.fit(n_devices)
    mesh = make_mesh(spec, devices=devices)
    model = build_model(cfg.model, cfg.num_classes)
    rules = strategy_rules(cfg.strategy)

    params = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, cfg.seq_len), jnp.int32)),
        jax.random.key(0),
    )["params"]
    tx = lora_optimizer(make_optimizer(cfg.optim), params, ("classifier",))
    opt_state = jax.eval_shape(tx.init, params)

    p_bytes = _tree_device_bytes(params, tree_shardings(mesh, params, rules))
    o_bytes = _tree_device_bytes(
        opt_state, tree_shardings(mesh, opt_state, rules)
    )

    dp, fsdp, tp, sp = (
        mesh.shape["dp"],
        mesh.shape["fsdp"],
        mesh.shape["tp"],
        mesh.shape["sp"],
    )
    b_local = max(cfg.global_batch_size // (dp * fsdp), 1)
    a_bytes = activation_upper_bound_bytes(
        model.cfg, b_local, cfg.seq_len // sp, tp
    )
    # fsdp all-gathers one layer's kernels at a time; the largest single
    # gathered bf16 kernel is the transient to budget for.
    gather_bytes = 2 * max(
        math.prod(leaf.shape)
        for leaf in jax.tree.leaves(params)
        if hasattr(leaf, "shape") and len(leaf.shape) >= 2
    )

    trainable, total = trainable_param_count(params, ("classifier",))
    total_bytes = p_bytes + o_bytes + a_bytes + gather_bytes
    fit_bar = hbm_gb * 1e9 / 2  # half of HBM: the rest is XLA headroom
    out = {
        "config": cfg.name,
        "model": cfg.model,
        "platform": "cpu (forced host devices, by design: a bytes "
        "model of shardings, nothing runs on an accelerator)",
        "devices": n_devices,
        "mesh": {k: int(v) for k, v in mesh.shape.items()},
        "global_batch": cfg.global_batch_size,
        "seq_len": cfg.seq_len,
        "params_total": total,
        "params_trainable": trainable,
        "bytes_per_device": {
            "params": p_bytes,
            "opt_moments": o_bytes,
            "activations_upper_bound": a_bytes,
            "largest_allgathered_kernel": gather_bytes,
            "total": total_bytes,
        },
        "hbm_bytes": int(hbm_gb * 1e9),
        "fit_bar_bytes": int(fit_bar),
        "fits": total_bytes < fit_bar,
    }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", default="llama3_8b_lora")
    ap.add_argument("--devices", type=int, default=64,
                    help="slice size (default 64: the declared v5p-64)")
    ap.add_argument("--hbm-gb", type=float, default=95.0,
                    help="per-chip HBM (v5p: 95 GB)")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    out = report(args.config, args.devices, args.hbm_gb)
    if args.json:
        print(json.dumps(out))
    else:
        bb = out["bytes_per_device"]
        print(f"{out['config']} ({out['model']}) on {out['devices']} devices, "
              f"mesh {out['mesh']}")
        print(f"  platform: {out['platform']}")
        print(f"  params: {out['params_total'] / 1e9:.2f}B total, "
              f"{out['params_trainable'] / 1e6:.1f}M trainable (LoRA+head)")
        for k in ("params", "opt_moments", "activations_upper_bound",
                  "largest_allgathered_kernel", "total"):
            print(f"  {k:>28}: {bb[k] / 1e9:8.3f} GB/device")
        print(f"  fit bar (HBM/2): {out['fit_bar_bytes'] / 1e9:.1f} GB -> "
              f"{'FITS' if out['fits'] else 'DOES NOT FIT'}")
    return 0 if out["fits"] else 1


if __name__ == "__main__":
    sys.exit(main())
