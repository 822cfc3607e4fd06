"""Latency x precision x backend parity grid (ROADMAP item 4).

The reference repo's entire behavioral signature is *export a model ->
run it on multiple backends -> measure latency -> verify numerical
parity* (reference notebooks/cv/onnx_experiments.py). This benchmark
generalizes that into a first-class matrix over the serving decoder:

- **precision** rows: ``f32`` (the reference), ``bf16`` compute,
  ``int8`` weights (tpudl.quant), ``int8+kv8`` (int8 weights composed
  with the PR-8 paged int8 KV cache), ``fp8`` (e4m3 weights),
  ``prefix`` (f32 paged + radix prefix sharing — EXACT parity: COW
  addressing must never change tokens), ``spec`` (speculative
  decoding, int8 self-draft — margin-mode parity: the chunked verify
  program may flip genuine near-ties), and ``lora``/``lora8``
  (multi-tenant adapter serving, tpudl.serve.lora: a heterogeneous
  batch gated PER ADAPTER against the sequential merged-into-base
  reference — exact for f32 adapter pages, margin atol for int8
  pages; both the Pallas segmented kernel in interpret mode and the
  XLA composite fallback are gated);
- **backend** columns: ``compiled`` (live jitted ServeSession) and
  ``exported`` (StableHLO artifacts through
  tpudl.export.decode.export_serving_decoder -> from_artifacts; paged
  cells export the page-pool contract and from_artifacts recovers the
  geometry from avals); prefix/spec cells skip the exported column
  loudly (they need live chunk/draft programs).

Every cell runs ``assert_serving_parity`` against the f32 reference
model at a per-cell tolerance: exact token equality for f32 cells,
atol (teacher-forced logit-margin) mode for reduced-precision cells —
a wide-margin divergence is a bug in ANY cell, a near-tie flip is the
quantization contract.

Latency per cell is measured on a SIMULATED device: each decode step
sleeps ``bytes_moved / sim_bandwidth`` on top of the real host
dispatch (the serve_load.py idiom — this 1-vCPU container has no
accelerator, and the sim bandwidth is deliberately low so the
bytes-bound regime is visible at tiny-model scale). Next to measured
TPOT the cell reports the idealized **bytes-moved ceiling**
(weights + resident KV read once per token, scaled to a real HBM
bandwidth — the speedup ceiling, following fused_epilogue.py's bytes
model): quantization can never beat the byte ratio, and the grid shows
how much of it each cell captures.

    python -m benchmarks.parity_grid --smoke     # CPU container
    python -m benchmarks.parity_grid             # full grid

bench.py records ``serve_tpot_int8_weights_ms`` /
``quant_weight_bytes_ratio`` / ``parity_grid_cells_passed`` from
``measure_parity_grid()`` each round (banked from r06 onward).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

PROMPT_LEN = 8
MAX_SEQ_LEN = 96
#: Idealized device HBM bandwidth the ceiling column is quoted at
#: (~a TPU v5e). The SIM bandwidth below is separate and deliberately
#: tiny — see module docstring.
HBM_GBPS = 819.0

#: Per-cell parity tolerance: None = exact token equality (the f32
#: contract), else assert_serving_parity's teacher-forced logit-margin
#: atol (quantized/bf16 compute may flip genuine near-ties only).
#: ``prefix`` (f32 paged + radix prefix sharing) is EXACT — a request
#: seated against a cached prefix must produce byte-identical tokens
#: to a cold run; ``spec`` (speculative decoding, int8 self-draft)
#: rides margin mode — the chunked verify program may flip genuine
#: near-ties vs the single-token program, wide margins still fire.
CELL_ATOL = {
    "f32": None,
    "bf16": 0.15,
    "int8": 0.06,
    "int8+kv8": 0.10,
    "fp8": 0.06,
    "prefix": None,
    "spec": 0.06,
    # Multi-tenant adapter serving (tpudl.serve.lora): per-adapter
    # parity vs the sequential one-adapter-at-a-time MERGED reference.
    # ``lora`` (f32 adapter pages) is EXACT — segmented addressing
    # must never change tokens; ``lora8`` (int8 pages) rides margin
    # mode at a wider atol than the weight cells because the page
    # quantization error is amplified by the adapter's alpha/rank
    # scaling before it reaches the logits (the cell runs alpha=4).
    "lora": None,
    "lora8": 0.1,
}
PRECISIONS = (
    "f32", "bf16", "int8", "int8+kv8", "fp8", "prefix", "spec",
    "lora", "lora8",
)
BACKENDS = ("compiled", "exported")
#: Speculation window for the ``spec`` row.
SPEC_K = 3
#: Tenant count / rank for the multi-tenant ``lora``/``lora8`` cells.
LORA_TENANTS = 3
LORA_RANK = 2
LORA8_ALPHA = 4.0


class CellUnrunnable(RuntimeError):
    """A cell the exported backend cannot run (prefix/spec and adapter
    cells need live programs). Deliberately distinct from plain
    RuntimeError so run_grid's skip path can never absorb a genuine
    cell failure (jaxlib's XlaRuntimeError subclasses RuntimeError —
    a broken cell must fail the benchmark, not report as a skip)."""


def build_reference(max_seq_len: int = MAX_SEQ_LEN):
    """The f32 reference (tiny Llama, deterministic on CPU) every
    cell's parity is gated against."""
    import jax
    import jax.numpy as jnp

    from tpudl.models.llama import LLAMA_TINY, LlamaForCausalLM

    cfg = LLAMA_TINY(dtype=jnp.float32, max_seq_len=max_seq_len)
    model = LlamaForCausalLM(cfg)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, PROMPT_LEN), jnp.int32)
    )["params"]
    return model, params


def _precision_variant(model, params, precision: str):
    """(model, params, session kwargs) for one precision row."""
    import jax.numpy as jnp

    from tpudl.quant import quantize_model

    if precision == "f32":
        return model, params, {}
    if precision == "bf16":
        return (
            model.clone(
                cfg=dataclasses.replace(model.cfg, dtype=jnp.bfloat16)
            ),
            params,
            {},
        )
    if precision == "int8":
        m, p = quantize_model(model, params, "int8")
        return m, p, {}
    if precision == "int8+kv8":
        m, p = quantize_model(model, params, "int8")
        return m, p, {"paged": True, "kv_dtype": "int8"}
    if precision == "fp8":
        m, p = quantize_model(model, params, "fp8_e4m3")
        return m, p, {}
    if precision == "prefix":
        # Page size must divide into the shared prefix (PROMPT_LEN/2)
        # for full-block hits to exist at this tiny prompt window.
        return model, params, {
            "paged": True, "prefix_share": True, "page_size": 4,
        }
    if precision == "spec":
        return model, params, {"paged": True, "spec_k": SPEC_K}
    raise ValueError(f"unknown precision {precision!r}")


def _make_requests(n, cell: str, seed=0, max_new=(4, 16), vocab=512,
                   shared_prefix: int = 0):
    """``shared_prefix`` > 0 gives every request one common prefix of
    that many tokens plus a ragged unique tail — the workload shape
    that exercises the radix cell's hit path (request 0 seeds, the
    rest seat against cached pages)."""
    from tpudl.serve import Request

    rng = np.random.default_rng(seed)
    if not shared_prefix:
        # The pre-existing cells' exact draw, untouched: banked grid
        # latencies stay comparable across rounds.
        return [
            Request(
                request_id=f"{cell}-{i}",
                input_ids=rng.integers(
                    1, vocab, size=int(rng.integers(2, PROMPT_LEN + 1))
                ).tolist(),
                max_new_tokens=int(rng.integers(*max_new)),
            )
            for i in range(n)
        ]
    prefix = rng.integers(1, vocab, size=shared_prefix).tolist()
    out = []
    for i in range(n):
        tail = rng.integers(
            1, vocab,
            size=int(rng.integers(1, PROMPT_LEN - shared_prefix + 1)),
        ).tolist()
        out.append(Request(
            request_id=f"{cell}-{i}",
            input_ids=prefix + tail,
            max_new_tokens=int(rng.integers(*max_new)),
        ))
    return out


def _cell_bytes(params_v, session) -> dict:
    """The cell's bytes-moved-per-token model: every weight byte plus
    the resident KV pool read once per decode step (decode is
    bandwidth-bound; this is the idealized floor the ceiling column
    scales to HBM speed).

    Speculative cells amortize: one window moves k draft reads (draft
    weights + draft KV) plus one target read, and emits up to k
    tokens — bytes/token is the window total over k, the
    full-acceptance ceiling the measured acceptance discounts.
    Prefix cells keep the f32 paged model (sharing changes RESIDENT
    bytes per request and prefill compute, not per-decode-token
    traffic)."""
    from tpudl.quant import weight_bytes_report

    report = weight_bytes_report(params_v)
    kv_bytes = session.engine.cache.nbytes
    per_token = report["total_bytes"] + int(kv_bytes)
    spec = session.engine.speculator
    if spec is not None:
        draft_read = spec.weight_bytes + spec.cache.nbytes
        per_token = (
            spec.k * draft_read + report["total_bytes"] + int(kv_bytes)
        ) // spec.k
    return {
        "weight_bytes": report["total_bytes"],
        "kv_bytes": int(kv_bytes),
        "bytes_per_token": per_token,
        "quant_ratio": report["quant_ratio"],
        "quantized_layer_bytes": report["quantized_layer_bytes"],
        "quantized_layer_f32_bytes": report["quantized_layer_f32_bytes"],
    }


def build_cell_session(
    model_v,
    params_v,
    backend: str,
    num_slots: int,
    session_kwargs: dict,
):
    """One cell's ServeSession: live-jitted or round-tripped through
    the StableHLO artifact pair. Raises CellUnrunnable for an exported
    prefix/spec cell (callers skip the cell)."""
    from tpudl.serve import ServeSession

    if backend == "compiled":
        return ServeSession.from_model(
            model_v, params_v, prompt_len=PROMPT_LEN,
            num_slots=num_slots, **session_kwargs,
        )
    if backend != "exported":
        raise ValueError(f"unknown backend {backend!r}")
    if session_kwargs.get("prefix_share") or session_kwargs.get("spec_k"):
        # Sharing needs the live chunked suffix-prefill program and
        # speculation the live draft+verify pair — neither is part of
        # the exported artifact contract (yet).
        raise CellUnrunnable(
            "prefix/spec cells need live programs; serve compiled-only"
        )
    from tpudl.export.decode import export_serving_decoder

    # The paged decode contract round-trips through StableHLO: the
    # page pools are the cache avals, the host addressing arrays
    # ride as extra inputs, and from_artifacts recovers the whole
    # geometry from shapes (ROADMAP item 6's exported-paged cell).
    pre, dec = export_serving_decoder(
        model_v, params_v, num_slots=num_slots,
        prompt_len=PROMPT_LEN,
        kv_dtype=session_kwargs.get("kv_dtype"),
    )
    return ServeSession.from_artifacts(pre, dec, params_v)


def _run_lora_cell(
    precision: str,
    backend: str,
    ref_model,
    ref_params,
    num_slots: int,
    n_parity: int,
    n_latency: int,
    latency_tokens: int,
    sim_bw_gbps: float,
    seed: int,
) -> dict:
    """The multi-tenant adapter cells: a heterogeneous batch (every
    slot a different tenant, plus a tenantless base request) gated
    per-adapter against the SEQUENTIAL one-adapter-at-a-time reference
    (each tenant's factors merged into the base, run through plain
    generate()). BOTH kernel paths are gated — the Pallas segmented
    kernel (interpret mode on this CPU container) and the XLA
    composite fallback — so the dispatch seam cannot hide a divergence
    the production TPU path would serve. Latency is measured on the
    composite session (interpret-mode Pallas pays a host overhead that
    is an artifact of THIS container, not of the kernel)."""
    import dataclasses as _dc

    from benchmarks.serve_load import _with_sim_latency, make_adapters
    from tpudl.export.latency import LatencyStats
    from tpudl.quant import weight_bytes_report
    from tpudl.serve import ServeSession
    from tpudl.serve.lora import assert_tenant_parity

    if backend != "compiled":
        raise CellUnrunnable(
            "adapter cells need the live segmented-LoRA programs; the "
            "exported artifact contract does not carry adapter pools "
            "yet — serve compiled-only"
        )
    int8 = precision == "lora8"
    alpha = LORA8_ALPHA if int8 else 16.0
    adapters = make_adapters(
        LORA_TENANTS, rank=LORA_RANK, seed=seed + 11,
        max_seq_len=MAX_SEQ_LEN,
    )
    atol = CELL_ATOL[precision]
    cell = f"{precision}/{backend}"

    def build(impl: str) -> "ServeSession":
        return ServeSession.from_model(
            ref_model, ref_params, prompt_len=PROMPT_LEN,
            num_slots=num_slots, adapters=adapters,
            adapter_dtype="int8" if int8 else None,
            adapter_alpha=alpha, adapter_impl=impl,
        )

    def tenant_requests(n, tag, rq_seed, max_new=(4, 16)):
        reqs = _make_requests(n, tag, seed=rq_seed, max_new=max_new)
        cycle = [None] + list(adapters)
        return [
            _dc.replace(r, tenant=cycle[i % len(cycle)])
            for i, r in enumerate(reqs)
        ]

    # -- parity gates: fused (interpret) AND composite vs the merged
    # sequential reference, per adapter ------------------------------
    fused = build("fused")
    assert_tenant_parity(
        fused, ref_model, ref_params, adapters,
        tenant_requests(n_parity, cell + "-fused", seed),
        atol=atol, alpha=alpha,
    )
    session = build("reference")
    assert_tenant_parity(
        session, ref_model, ref_params, adapters,
        tenant_requests(n_parity, cell, seed),
        atol=atol, alpha=alpha,
    )

    # -- bytes model + simulated-device latency ----------------------
    pool = session.engine.adapter_pool
    report = weight_bytes_report(ref_params)
    kv_bytes = session.engine.cache.nbytes
    # Per decode token: every weight byte + resident KV + the ACTIVE
    # slots' adapter pages (the gather touches the seated tenants'
    # rank units, not the whole pool).
    active_adapter = min(
        pool.nbytes, num_slots * LORA_RANK * pool.bytes_per_page
    )
    per_token = report["total_bytes"] + int(kv_bytes) + active_adapter
    bytes_model = {
        "weight_bytes": report["total_bytes"],
        "kv_bytes": int(kv_bytes),
        "adapter_bytes": int(pool.nbytes),
        "bytes_per_token": per_token,
        "quant_ratio": report["quant_ratio"],
        "quantized_layer_bytes": report["quantized_layer_bytes"],
        "quantized_layer_f32_bytes": report["quantized_layer_f32_bytes"],
    }
    sim_step_s = per_token / (sim_bw_gbps * 1e9)
    session.engine.decode_call = _with_sim_latency(
        session.engine.decode_call, sim_step_s
    )
    lat_reqs = tenant_requests(
        n_latency, cell + "-lat", seed + 1,
        max_new=(latency_tokens, latency_tokens + 1),
    )
    t0 = time.perf_counter()
    results = session.serve(lat_reqs)
    wall_s = time.perf_counter() - t0
    tpots = [r.tpot_s for r in results.values() if r.tpot_s is not None]
    assert tpots, f"cell {cell}: no TPOT samples"
    tpot = LatencyStats.from_seconds(tpots)
    tokens = sum(len(r.tokens) for r in results.values() if r.ok)
    return {
        "precision": precision,
        "backend": backend,
        "status": "pass",
        "atol": atol,
        **bytes_model,
        "sim_step_ms": round(sim_step_s * 1e3, 4),
        "tpot_ceiling_ms": round(
            per_token / (HBM_GBPS * 1e9) * 1e3, 6
        ),
        "tpot_measured": tpot.percentiles(),
        "tokens_per_sec": round(tokens / wall_s, 2),
        "adapters_resident": pool.stats()["resident"],
    }


def run_cell(
    precision: str,
    backend: str,
    ref_model,
    ref_params,
    num_slots: int = 4,
    n_parity: int = 6,
    n_latency: int = 6,
    latency_tokens: int = 16,
    sim_bw_gbps: float = 0.5,
    seed: int = 0,
) -> dict:
    """One grid cell: build the session, gate parity against the f32
    reference at the cell tolerance, then measure TPOT with the
    simulated device latency derived from the cell's OWN bytes model
    (so a cell that moves fewer bytes genuinely decodes faster on the
    simulated device, exactly as it would on HBM)."""
    from benchmarks.serve_load import _with_sim_latency
    from tpudl.export.latency import LatencyStats
    from tpudl.serve import assert_serving_parity

    if precision.startswith("lora"):
        return _run_lora_cell(
            precision, backend, ref_model, ref_params, num_slots,
            n_parity, n_latency, latency_tokens, sim_bw_gbps, seed,
        )
    model_v, params_v, session_kwargs = _precision_variant(
        ref_model, ref_params, precision
    )
    session = build_cell_session(
        model_v, params_v, backend, num_slots, session_kwargs
    )
    cell = f"{precision}/{backend}"
    bytes_model = _cell_bytes(params_v, session)
    sim_step_s = bytes_model["bytes_per_token"] / (sim_bw_gbps * 1e9)

    # -- parity gate (before the sim wrapper: the gate is about
    # tokens, and unslowed decode keeps the grid fast) --------------
    atol = CELL_ATOL[precision]
    shared_prefix = PROMPT_LEN // 2 if precision == "prefix" else 0
    assert_serving_parity(
        session, ref_model, ref_params,
        _make_requests(
            n_parity, cell, seed=seed, shared_prefix=shared_prefix
        ),
        atol=atol,
    )
    if precision == "prefix":
        hits = session.engine.cache.radix.stats()
        assert hits["nodes"] > 0, (
            "prefix cell never populated the radix tree — the parity "
            "gate did not exercise the shared path"
        )

    # -- simulated-device latency -----------------------------------
    session.engine.decode_call = _with_sim_latency(
        session.engine.decode_call, sim_step_s
    )
    if session.engine.speculator is not None:
        # Spec cells pace the verify dispatch at the TARGET's full
        # weight+KV read (one window always moves all of it — the
        # amortized bytes/token would understate measured TPOT against
        # the cell's own model) and the draft at its own measured read.
        target_read = (
            bytes_model["weight_bytes"] + bytes_model["kv_bytes"]
        )
        session.engine.verify_call = _with_sim_latency(
            session.engine.verify_call,
            target_read / (sim_bw_gbps * 1e9),
        )
        spec = session.engine.speculator
        draft_bytes = spec.weight_bytes + spec.cache.nbytes
        spec.decode_call = _with_sim_latency(
            spec.decode_call, draft_bytes / (sim_bw_gbps * 1e9)
        )
    lat_reqs = _make_requests(
        n_latency, cell + "-lat", seed=seed + 1,
        max_new=(latency_tokens, latency_tokens + 1),
        shared_prefix=shared_prefix,
    )
    t0 = time.perf_counter()
    results = session.serve(lat_reqs)
    wall_s = time.perf_counter() - t0
    tpots = [r.tpot_s for r in results.values() if r.tpot_s is not None]
    assert tpots, f"cell {cell}: no TPOT samples"
    tpot = LatencyStats.from_seconds(tpots)
    tokens = sum(len(r.tokens) for r in results.values() if r.ok)
    return {
        "precision": precision,
        "backend": backend,
        "status": "pass",
        "atol": atol,
        **bytes_model,
        "sim_step_ms": round(sim_step_s * 1e3, 4),
        "tpot_ceiling_ms": round(
            bytes_model["bytes_per_token"] / (HBM_GBPS * 1e9) * 1e3, 6
        ),
        "tpot_measured": tpot.percentiles(),
        "tokens_per_sec": round(tokens / wall_s, 2),
    }


def run_grid(
    precisions: Sequence[str] = PRECISIONS,
    backends: Sequence[str] = BACKENDS,
    num_slots: int = 4,
    n_parity: int = 6,
    n_latency: int = 6,
    latency_tokens: int = 16,
    sim_bw_gbps: float = 0.5,
    seed: int = 0,
    check: bool = True,
) -> dict:
    """The full matrix. ``check=True`` asserts the acceptance bars:
    every runnable cell's parity gate green (run_cell raises
    otherwise), and int8-weight cells hold >= 3.5x stored-bytes
    reduction on their quantized layers."""
    ref_model, ref_params = build_reference()
    cells: List[dict] = []
    skipped: List[dict] = []
    for precision in precisions:
        for backend in backends:
            try:
                cell = run_cell(
                    precision, backend, ref_model, ref_params,
                    num_slots=num_slots, n_parity=n_parity,
                    n_latency=n_latency, latency_tokens=latency_tokens,
                    sim_bw_gbps=sim_bw_gbps, seed=seed,
                )
            except CellUnrunnable as e:
                # Cells the exported contract does not carry skip
                # loudly, never silently pass.
                # Anything else — including XlaRuntimeError, a
                # RuntimeError subclass — propagates and FAILS the
                # benchmark.
                skipped.append({
                    "precision": precision, "backend": backend,
                    "status": f"skipped: {e}",
                })
                continue
            cells.append(cell)
    if check:
        for cell in cells:
            if cell["precision"].startswith("int8"):
                assert cell["quant_ratio"] is not None and (
                    cell["quant_ratio"] >= 3.5
                ), (
                    f"{cell['precision']}/{cell['backend']}: quantized "
                    f"layers hold only {cell['quant_ratio']}x fewer "
                    f"bytes (bar: 3.5x)"
                )
        assert cells, "no grid cell was runnable"
    f32 = next(
        (c for c in cells
         if c["precision"] == "f32" and c["backend"] == "compiled"),
        None,
    )
    for cell in cells:
        if f32 is not None:
            cell["bytes_vs_f32"] = round(
                f32["bytes_per_token"] / cell["bytes_per_token"], 3
            )
    return {
        "prompt_len": PROMPT_LEN,
        "max_seq_len": MAX_SEQ_LEN,
        "num_slots": num_slots,
        "sim_bw_gbps": sim_bw_gbps,
        "hbm_gbps": HBM_GBPS,
        "cells": cells,
        "skipped": skipped,
        "cells_passed": len(cells),
    }


def measure_parity_grid() -> dict:
    """The bench.py entry: the int8-weights compiled cell's
    simulated-device TPOT, the weight-bytes ratio on quantized layers,
    and how many grid cells passed their parity gate."""
    grid = run_grid()
    int8 = next(
        c for c in grid["cells"]
        if c["precision"] == "int8" and c["backend"] == "compiled"
    )
    return {
        "serve_tpot_int8_weights_ms": int8["tpot_measured"]["p50_ms"],
        "quant_weight_bytes_ratio": int8["quant_ratio"],
        "parity_grid_cells_passed": grid["cells_passed"],
    }


def format_grid(grid: dict) -> str:
    lines = [
        f"{'cell':>18} {'status':>8} {'bytes/tok':>10} {'vs f32':>7} "
        f"{'ceiling ms':>11} {'sim ms':>8} {'tpot p50':>9} {'atol':>6}",
    ]
    for cell in grid["cells"]:
        lines.append(
            f"{cell['precision'] + '/' + cell['backend']:>18} "
            f"{cell['status']:>8} {cell['bytes_per_token']:>10} "
            f"{cell.get('bytes_vs_f32', 1.0):>7} "
            f"{cell['tpot_ceiling_ms']:>11.6f} {cell['sim_step_ms']:>8} "
            f"{cell['tpot_measured']['p50_ms']:>9} "
            f"{str(cell['atol']):>6}"
        )
    for cell in grid["skipped"]:
        lines.append(
            f"{cell['precision'] + '/' + cell['backend']:>18} "
            f"{cell['status']}"
        )
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(
        description="Serving parity grid: latency x precision x "
        "backend, every cell gated by assert_serving_parity"
    )
    ap.add_argument("--smoke", action="store_true",
                    help="lean cell sizes for the CPU container "
                    "(fewer/shorter requests; same full cell matrix)")
    ap.add_argument("--precisions", nargs="*", default=None,
                    choices=list(PRECISIONS))
    ap.add_argument("--backends", nargs="*", default=None,
                    choices=list(BACKENDS))
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sim-bw-gbps", type=float, default=0.5,
                    help="simulated-device bandwidth for measured "
                    "TPOT (deliberately low so the bytes-bound regime "
                    "is visible at tiny-model scale)")
    args = ap.parse_args(argv)

    kwargs = {}
    if args.smoke:
        kwargs.update(n_parity=4, n_latency=4, latency_tokens=12)
    grid = run_grid(
        precisions=tuple(args.precisions or PRECISIONS),
        backends=tuple(args.backends or BACKENDS),
        num_slots=args.slots,
        sim_bw_gbps=args.sim_bw_gbps,
        seed=args.seed,
        **kwargs,
    )
    print(format_grid(grid))
    print(json.dumps(grid, indent=2))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
