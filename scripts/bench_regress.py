"""Noise-aware benchmark regression gate over the BENCH_r*.json bank.

The BENCH_r05 postmortem (BASELINE.md "0.923 regression" row) showed
exactly how a naive ratio lies: comparing one draw of a ±20%
one-sided-noise metric against the MAX of four prior draws reads as a
regression almost always, with no code change. This gate encodes the
corrected protocol:

- the baseline for each metric is the **median of the banked
  same-protocol history** (single draws compared against the center of
  single draws, never against an order statistic);
- each metric carries a **noise band**: the larger of a per-metric
  floor (wide for the short-step, jittery ResNet-18 metric,
  tight for the 170 ms BERT steps) and half the relative spread the
  bank itself exhibits — the bank's own noise is evidence;
- a metric is a REGRESSION only when the current draw falls outside
  the band on the bad side (below ``median x (1 - band)`` for
  higher-is-better, above ``median x (1 + band)`` for
  lower-is-better), with at least ``min_history`` banked points.

Usage:

    python scripts/bench_regress.py CURRENT.json           # gate a run
    python scripts/bench_regress.py --current-json '{...}' # inline

Exit status: 0 when no metric regresses (advisory rows still print),
1 on a real regression, 2 on usage errors. ``bench.py`` runs the same
evaluation in-process after printing its JSON line (advisory by
default; ``bench.py --strict`` propagates the nonzero exit).

The protocol's acceptance case is the r05 incident itself, replayed by
tests/test_bench_regress.py from the five rounds' values: history
r01-r04, current r05 — ResNet-18's 34,065 img/s MUST classify as
no-regression (it sits above the banked median), and a halved draw
MUST still be caught. The repo root holds no bank until a benchmark
writes one: every metric is then ``no-baseline``.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Dict, List, Optional

#: Protocol renames: historical keys folded onto one canonical metric
#: name, so a metric's history survives its key being renamed — but
#: ONLY where the measurement protocol stayed commensurable
#: (best-of-windows draws of the same workload).
ALIASES = {
    "resnet18_cifar10_train_throughput": "resnet18_images_per_sec_chip",
    "resnet18_images_per_sec_chip_best_of_windows":
        "resnet18_images_per_sec_chip",
    "bert_base_sst2_train_throughput": "bert_base_samples_per_sec_chip",
}

#: Per-metric noise-band floors (fraction of the baseline median).
#: resnet18: the BASELINE.md-documented ±20% one-sided ambient
#: drift on 9 ms steps (25.1k-36.9k same code, same day) — anything
#: tighter re-creates the r05 false alarm. Default floor 8%: the BERT
#: metrics hold ±1.5% but ratio bases move a few percent round to
#: round (recompiles, jax upgrades).
NOISE_BAND_FLOORS = {
    "resnet18_images_per_sec_chip": 0.25,
    "serve_tokens_per_sec": 0.20,
    "serve_p99_ttft_ms": 0.50,
    # Router sweep rides threads on 1 vCPU in the container: scheduler
    # jitter moves the routed throughput more than the engine's.
    "serve_tokens_per_sec_2rep": 0.25,
    "serve_scaling_efficiency": 0.15,
    # Deterministic byte accounting (cache layout arithmetic, not a
    # timing draw): any drift beyond rounding is a real layout change.
    "serve_kv_slots_per_gb": 0.05,
    # Parity-grid keys (benchmarks/parity_grid.py, banked from r06).
    # TPOT rides the simulated-device sleep + host dispatch on 1 vCPU;
    # the bytes ratio is arithmetic; cells_passed only moves when a
    # cell is added or breaks — a drop of even one cell must gate.
    "serve_tpot_int8_weights_ms": 0.50,
    "quant_weight_bytes_ratio": 0.05,
    "parity_grid_cells_passed": 0.01,
    "input_pipeline_images_per_sec_host": 0.20,
    "checkpoint_step_stall_ms": 0.50,
    "checkpoint_sync_save_ms": 0.50,
    "recovery_time_sec": 0.50,
    "step_dispatch_overhead_ms": 1.00,
    # Fleet-tier keys (benchmarks/serve_load.py --autoscale, banked
    # from r06). Recovery rides SLO window drains + thread scheduling
    # on 1 vCPU; the scrape is two localhost HTTP round trips whose
    # tail the container's scheduler owns.
    "autoscale_recovery_s": 0.60,
    "fleet_scrape_overhead_ms": 0.60,
    # Prefix-sharing + speculative keys (benchmarks/serve_load.py
    # --prefix/--spec, banked from r07). TTFT rides simulated prefill
    # sleeps queued across slots (scheduler-owned tail on 1 vCPU);
    # acceptance is a near-deterministic property of the int8
    # self-draft (greedy agreement), so a real drop means the draft or
    # the acceptance rule changed; spec tokens/sec rides the sim
    # device + host dispatch mix.
    "serve_ttft_shared_prefix_ms": 0.50,
    "spec_accepted_tokens_per_step": 0.15,
    "serve_tokens_per_sec_spec": 0.30,
    # Dispatch-hygiene count (tpudl.analysis wired into serve_load's
    # steady state, banked from r07): expected EXACTLY 0 — it is a
    # count of silent regressions, not a timing draw, so it gates
    # zero-tolerance (see ZERO_TOLERANCE below).
    "serve_steady_state_recompiles": 0.01,
    # Multi-tenant LoRA keys (benchmarks/serve_load.py --tenants,
    # banked from r09). Adapters-per-GB is pool-layout arithmetic
    # (deterministic like the KV capacity key); batched tokens/sec
    # rides the sim device + host dispatch mix at 8 slots on 1 vCPU;
    # the isolation ratio is a ratio of two p99 tails of
    # scheduler-owned TTFTs, so its band stays wide (the in-benchmark
    # 1.3x assertion is the real gate).
    "serve_adapters_per_gb": 0.05,
    "serve_tokens_per_sec_64adapters": 0.30,
    "serve_tenant_isolation_p99_ratio": 0.50,
    # Serving fault-tolerance keys (benchmarks/serve_load.py --chaos,
    # banked from r08). Both ride command-pickup latency on the
    # replica loop thread: on 1 vCPU the scheduler owns their tail
    # (the drain races a simulated-device generation; the gap is one
    # loop hand-off plus a decode step), so the bands stay wide.
    "serve_drain_p99_ms": 0.60,
    "failover_token_gap_ms": 0.60,
    # Mixed-precision training keys (benchmarks/train_precision.py +
    # the bf16-policy BERT variant, banked from r09). The bytes ratio
    # is pure arithmetic over the rule-class sites (drift = the rules
    # stopped matching); the parity cell count only moves when a cell
    # is added or a band breaks — one lost cell must gate; the bf16
    # MFU variant rides the same jitter as the headline BERT
    # metrics.
    "train_fp8_bytes_ratio": 0.05,
    "train_precision_parity_cells": 0.01,
    "bert_base_mfu_bf16": 0.10,
    # Durable request-log keys (benchmarks/serve_load.py, banked from
    # r16). The overhead ratio is two p99 TTFT tails of the same
    # scheduler-owned closed loop (writer thread adds a contender on
    # 1 vCPU), so its band stays wide; bytes-per-request is compact-JSON
    # record arithmetic over a fixed request mix — near-deterministic,
    # drift means the schema or the mix changed.
    "requestlog_overhead_p99_ttft_ratio": 0.50,
    "requestlog_bytes_per_request": 0.08,
    # Data-flywheel keys (benchmarks/serve_load.py, banked from r18).
    # Refresh latency is a handful of tiny train steps plus a pool
    # register on a 1-vCPU host that is also paging XLA programs —
    # scheduler jitter dominates a sub-100ms wall time. The impact
    # ratio is two p99 TTFT tails of the same closed loop (the
    # requestlog overhead band's shape, plus sample capture), so it
    # inherits the same wide band.
    "flywheel_refresh_latency_s": 0.60,
    "flywheel_serving_p99_impact_ratio": 0.50,
    # Pod-real fleet keys (benchmarks/fleet_mesh.py subprocess, banked
    # from r19). Reshard-restore is host-array device_put over 8 fake
    # devices on 1 vCPU (scheduler-owned); the payload MB is pure
    # arithmetic (drift = the template changed); the 2-mesh routed
    # throughput rides emulated collectives + thread hand-offs, wider
    # than the 2rep thread-replica band; burn-cleared wall time is
    # dominated by the borrowed replica's serving-program compiles,
    # which vary with XLA's own scheduling on a loaded host.
    "fleet_reshard_restore_s": 0.60,
    "fleet_reshard_payload_mb": 0.05,
    "serve_tokens_per_sec_2mesh": 0.30,
    "chipmover_burn_cleared_s": 0.60,
}
DEFAULT_BAND_FLOOR = 0.08

#: Metrics where smaller is better (latency/stall/recovery); every
#: other numeric metric is treated as higher-is-better throughput/MFU.
LOWER_IS_BETTER = {
    "serve_p99_ttft_ms",
    "serve_tpot_int8_weights_ms",
    "checkpoint_step_stall_ms",
    "checkpoint_sync_save_ms",
    "recovery_time_sec",
    "step_dispatch_overhead_ms",
    "autoscale_recovery_s",
    "fleet_scrape_overhead_ms",
    "serve_ttft_shared_prefix_ms",
    "serve_steady_state_recompiles",
    "serve_drain_p99_ms",
    "failover_token_gap_ms",
    "serve_tenant_isolation_p99_ratio",
    "requestlog_overhead_p99_ttft_ratio",
    "requestlog_bytes_per_request",
    "flywheel_refresh_latency_s",
    "flywheel_serving_p99_impact_ratio",
    "fleet_reshard_restore_s",
    "chipmover_burn_cleared_s",
}

#: Lower-is-better metrics whose banked baseline is 0 and must STAY 0:
#: the ratio protocol divides by the median and goes silent on a zero
#: baseline, so these gate on the absolute value instead — any
#: positive draw is a regression regardless of bands.
ZERO_TOLERANCE = {
    "serve_steady_state_recompiles",
}

#: Non-measurement keys in a bench line: identifiers, config echoes,
#: and ratios whose baselines are already re-derived here.
_SKIP_KEYS = {"metric", "unit", "bert_batch"}


def normalize_round(obj: dict) -> Dict[str, float]:
    """One BENCH_r*.json (or a bench.py output line) -> canonical
    ``{metric: value}``. The headline ``value`` is keyed under the
    line's ``metric`` name; ``vs_*`` ratio fields are dropped (their
    denominators are exactly the protocol this gate replaces)."""
    parsed = obj.get("parsed", obj)
    out: Dict[str, float] = {}
    for key, value in parsed.items():
        if key in _SKIP_KEYS or "vs_" in key:
            continue
        if key == "value":
            key = parsed.get("metric", "value")
        if not isinstance(value, (int, float)) or isinstance(value, bool):
            continue
        out[ALIASES.get(key, key)] = float(value)
    return out


def load_round(path: str) -> Dict[str, float]:
    with open(path) as f:
        return normalize_round(json.load(f))


def _median(vals: List[float]) -> float:
    vals = sorted(vals)
    n = len(vals)
    mid = n // 2
    return vals[mid] if n % 2 else 0.5 * (vals[mid - 1] + vals[mid])


def noise_band(metric: str, history: List[float]) -> float:
    """The metric's tolerance: max(per-metric floor, half the relative
    spread of its own bank) — a bank that scattered 20% peak-to-peak
    testifies to >= 10% one-draw noise regardless of the floor."""
    floor = NOISE_BAND_FLOORS.get(metric, DEFAULT_BAND_FLOOR)
    med = _median(history)
    if med == 0:
        return floor
    spread = (max(history) - min(history)) / abs(med)
    return max(floor, spread / 2.0)


def evaluate_regressions(
    current: Dict[str, float],
    history_rounds: List[Dict[str, float]],
    min_history: int = 2,
) -> List[dict]:
    """Classify every current metric against the banked history.

    Returns one row per metric: ``status`` is ``regression`` /
    ``improved`` / ``ok`` / ``no-baseline`` (fewer than
    ``min_history`` banked draws — advisory only, never gating)."""
    rows: List[dict] = []
    for metric in sorted(current):
        value = current[metric]
        hist = [
            r[metric] for r in history_rounds
            if metric in r and r[metric] is not None
        ]
        if len(hist) < min_history:
            rows.append({
                "metric": metric, "value": value, "baseline": None,
                "band": None, "ratio": None, "status": "no-baseline",
                "n_history": len(hist),
            })
            continue
        baseline = _median(hist)
        band = noise_band(metric, hist)
        ratio = value / baseline if baseline else None
        lower_better = metric in LOWER_IS_BETTER
        status = "ok"
        if metric in ZERO_TOLERANCE and baseline == 0:
            # value/0 has no ratio: gate the count absolutely.
            status = "regression" if value > 0 else "ok"
        elif ratio is not None:
            if lower_better:
                if ratio > 1.0 + band:
                    status = "regression"
                elif ratio < 1.0 - band:
                    status = "improved"
            else:
                if ratio < 1.0 - band:
                    status = "regression"
                elif ratio > 1.0 + band:
                    status = "improved"
        rows.append({
            "metric": metric, "value": value, "baseline": baseline,
            "band": band, "ratio": ratio, "status": status,
            "n_history": len(hist),
        })
    return rows


def format_rows(rows: List[dict]) -> str:
    lines = [
        f"{'metric':44} {'value':>12} {'baseline':>12} {'band':>6} "
        f"{'ratio':>7}  status",
    ]
    for r in rows:
        base = f"{r['baseline']:12.2f}" if r["baseline"] is not None else (
            f"{'—':>12}"
        )
        band = f"{r['band']:6.2f}" if r["band"] is not None else f"{'—':>6}"
        ratio = f"{r['ratio']:7.3f}" if r["ratio"] is not None else (
            f"{'—':>7}"
        )
        flag = r["status"].upper() if r["status"] == "regression" else (
            r["status"]
        )
        lines.append(
            f"{r['metric']:44} {r['value']:12.2f} {base} {band} {ratio}"
            f"  {flag}"
        )
    return "\n".join(lines)


def default_history_paths(root: Optional[str] = None) -> List[str]:
    root = root or os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return sorted(glob.glob(os.path.join(root, "BENCH_r*.json")))


def gate(
    current: Dict[str, float],
    history_paths: List[str],
    min_history: int = 2,
) -> List[dict]:
    history = [load_round(p) for p in history_paths]
    return evaluate_regressions(current, history, min_history=min_history)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Noise-aware regression gate over the BENCH_r*.json "
        "bank (median-of-bank baselines, per-metric noise bands)"
    )
    ap.add_argument("current", nargs="?",
                    help="bench output JSON file to gate ('-' = stdin)")
    ap.add_argument("--current-json", help="inline JSON instead of a file")
    ap.add_argument("--history", nargs="*",
                    help="banked BENCH_r*.json files (default: the repo "
                    "root's)")
    ap.add_argument("--min-history", type=int, default=2,
                    help="banked draws required before a metric gates")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    if args.current_json:
        current_obj = json.loads(args.current_json)
    elif args.current == "-":
        current_obj = json.loads(sys.stdin.read())
    elif args.current:
        with open(args.current) as f:
            current_obj = json.load(f)
    else:
        ap.error("need a CURRENT json file, '-', or --current-json")
        return 2

    history_paths = (
        args.history if args.history else default_history_paths()
    )
    rows = gate(
        normalize_round(current_obj), history_paths,
        min_history=args.min_history,
    )
    print(json.dumps(rows) if args.json else format_rows(rows))
    regressions = [r for r in rows if r["status"] == "regression"]
    if regressions:
        print(
            f"REGRESSION: {', '.join(r['metric'] for r in regressions)}",
            file=sys.stderr,
        )
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
