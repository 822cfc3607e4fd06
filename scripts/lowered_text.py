"""Lower the prefill and paged decode programs of the benchmark's decoder
configurations for a described v5e (the kernels taken), two layers each,
and write each program's text, so that two checkouts can be compared:

    ln -sfn <checkout> /root/scratch/same                    # once a checkout
    python /root/scratch/same/scripts/lowered_text.py /root/scratch/same <out_dir>
    diff -r <out_a> <out_b>

A program's compile-cache key holds its lowered text, and the text of a
Pallas kernel (its Mosaic payload) holds the path and the line numbers of
the kernel's whole call chain, this script's frame among them. So hand
BOTH checkouts in under one path (a symlink that is pointed at one, then
at the other, this file copied into the one that lacks it): a
configuration whose programs a change must leave alone then gives files
that are equal byte for byte. ``DEBUG_INFO=1`` keeps every operation's
location too (what a line moved shows up as, outside the kernels).
Nothing runs; this says nothing about results or times.
"""

import importlib
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"


def decoder_programs(root: str, param_dtype=None):
    """``(name, kind, fn, args)`` for the prefill (at the prompt window)
    and the paged decode program of every decoder configuration
    ``BENCHMARK.json`` names, two layers each, their arguments shapes
    placed on one device of a described v5e, the kernels taken.
    ``args[0]`` is the parameters, in ``param_dtype`` or as
    ``model.init`` declares them (float32: what the texts compared
    across checkouts have always held)."""
    sys.path.insert(0, root)
    os.chdir(root)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    import tpudl.ops.attention
    import tpudl.ops.paged_attention
    from tpudl.models.generate import paged_decode_fn, prefill_fn
    from tpudl.models.llama import LlamaConfig, LlamaForCausalLM
    from tpudl.serve.cache import PagedKVCache

    for module in (tpudl.ops.attention, tpudl.ops.paged_attention):
        module.is_tpu_backend = lambda: True
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree, dtype=None):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, dtype or a.dtype, sharding=chip), tree)

    with open("BENCHMARK.json") as f:
        files = [c["file"] for c in json.load(f)["configs"]]
    for file in files:
        with open(file) as f:
            cfg = json.load(f)
        if not cfg["family"].endswith("_serve"):
            continue
        name = os.path.basename(file)[:-len(".json")]
        for key in ("num_hidden_layers", "num_layers"):
            # Two layers: every kind a configuration has but Laguna's,
            # whose five ARE its kinds (a list of one kind is no list).
            if key in cfg and len(set(cfg.get("layer_types", ()))) < 2:
                cfg[key] = max(2, cfg.get("first_k_dense_replace", 0) + 1)
                # Lists that name every layer are cut with the depth (an
                # indexer layer and one that shares its choice stay).
                for listed in ("indexer_types", "mlp_layer_types"):
                    if listed in cfg:
                        cfg[listed] = cfg[listed][:cfg[key]]
        sess = cfg["session"]
        if cfg["family"] == "decoder_serve":
            model_cfg = LlamaConfig(
                vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
                num_layers=cfg["num_hidden_layers"],
                num_heads=cfg["num_attention_heads"],
                num_kv_heads=cfg["num_key_value_heads"],
                intermediate_size=cfg["intermediate_size"],
                max_seq_len=sess["max_seq_len"], rope_theta=cfg["rope_theta"],
                rms_norm_eps=cfg["rms_norm_eps"], dtype=jnp.bfloat16)
        else:
            family = importlib.import_module(
                f"perfbench.families.{cfg['family']}")
            model_cfg = family.model_config(
                cfg, sess["max_seq_len"], jnp.bfloat16)
        model = LlamaForCausalLM(model_cfg)
        params = jax.eval_shape(lambda: model.init(
            jax.random.key(0), jnp.ones((1, 8), jnp.int32))["params"])
        params = on_chip(params, param_dtype)
        prefill = prefill_fn(model)
        ids = jax.ShapeDtypeStruct(
            (1, sess["prompt_window"]), jnp.int32, sharding=chip)
        yield name, "prefill", prefill, (params, ids, ids)
        # The cache template as ``ServeSession.from_model`` derives it:
        # one prefilled row, traced at the batch-1 shape the window's
        # prompts run at, at every slot (a batch of ``slots`` rows is
        # another program: past ``PREFILL_SCORE_BYTES`` where no
        # session's is, so it would trace a kernel that no cell runs).
        slots = sess["num_slots"]
        _, row, *_ = jax.eval_shape(prefill, params, ids, ids)
        template = jax.tree.map(
            lambda leaf: jax.ShapeDtypeStruct(
                (slots, *leaf.shape[1:]), leaf.dtype
            ) if leaf.ndim >= 2 else leaf,
            row,
        )

        def pools_and_addressing():
            cache = PagedKVCache(template, page_size=sess["page_size"])
            return cache.cache, cache.dispatch_args()

        pools, addressing = on_chip(jax.eval_shape(pools_and_addressing))
        vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=chip)
        yield (name, "decode", paged_decode_fn(model, sess["page_size"], False),
               (params, pools, vec, vec, *addressing))


def main(root: str, out: str) -> int:
    debug = bool(int(os.environ.get("DEBUG_INFO", "0")))
    import jax

    os.makedirs(out, exist_ok=True)
    for name, kind, fn, args in decoder_programs(root):
        text = jax.jit(fn).lower(*args).as_text(debug_info=debug)
        with open(os.path.join(out, f"{name}.{kind}.txt"), "w") as f:
            f.write(text)
        if kind == "decode":
            print(name, "lowered", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(os.path.abspath(sys.argv[1]), os.path.abspath(sys.argv[2])))
