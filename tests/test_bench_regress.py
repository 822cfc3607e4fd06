"""scripts/bench_regress.py: the noise-aware regression gate.

The protocol under test is the one BASELINE.md derived from the false
r05 ResNet-18 "0.923 regression": median-of-bank same-protocol
baselines, per-metric noise bands widened by the bank's own spread —
and the canonical acceptance case is that r05 itself classifies as
NO-regression while a genuinely halved draw still gates."""

import json

import pytest

from scripts.bench_regress import (
    ALIASES,
    evaluate_regressions,
    format_rows,
    main,
    noise_band,
    normalize_round,
)

#: The five round-1..5 chip records (2026-07, 1x TPU v5 lite) as
#: bench.py printed them — the values the r05 incident turns on. The
#: files themselves are gone; BASELINE.md keeps the full rows.
ROUNDS_R01_R05 = [
    {"metric": "resnet18_cifar10_train_throughput", "value": 30683.7},
    {"metric": "bert_base_sst2_train_throughput", "value": 1320.0,
     "mfu": 0.458, "resnet50_imagenet_images_per_sec_chip": 2605.4,
     "resnet18_images_per_sec_chip": 30873.0},
    {"metric": "bert_base_sst2_train_throughput", "value": 1530.0,
     "mfu": 0.5258, "resnet50_imagenet_images_per_sec_chip": 2638.4,
     "resnet18_images_per_sec_chip": 29644.1},
    {"metric": "bert_base_sst2_train_throughput", "value": 1533.5,
     "mfu": 0.527, "resnet50_imagenet_images_per_sec_chip": 2631.9,
     "resnet18_images_per_sec_chip": 35928.2,
     "bert_large_samples_per_sec_chip": 554.1},
    {"metric": "bert_base_sst2_train_throughput", "value": 1534.0,
     "mfu": 0.5272, "resnet50_imagenet_images_per_sec_chip": 2643.6,
     "resnet18_images_per_sec_chip_best_of_windows": 34065.5,
     "resnet18_vs_baseline_best_vs_best": 0.923,
     "bert_large_samples_per_sec_chip": 557.8},
]


def test_normalize_round_aliases_and_filters():
    row = normalize_round({
        "parsed": {
            "metric": "bert_base_sst2_train_throughput",
            "value": 1534.0,
            "unit": "samples/sec/chip",
            "vs_baseline": 1.162,
            "mfu": 0.527,
            "bert_batch": 256,
            "resnet18_images_per_sec_chip_best_of_windows": 34065.5,
            "resnet18_vs_baseline_best_vs_best": 0.923,
            "serve_tokens_per_sec": 900.0,
            "checkpoint_step_stall_ms": None,
        }
    })
    # Headline value keyed under its metric name, then canonicalized.
    assert row["bert_base_samples_per_sec_chip"] == 1534.0
    assert row["resnet18_images_per_sec_chip"] == 34065.5
    assert row["serve_tokens_per_sec"] == 900.0
    assert row["mfu"] == 0.527
    # Ratios against the broken protocol, units, config echoes, nulls:
    # all dropped.
    for absent in ("vs_baseline", "resnet18_vs_baseline_best_vs_best",
                   "unit", "metric", "bert_batch",
                   "checkpoint_step_stall_ms", "value"):
        assert absent not in row
    # Works on a bare bench.py line too (no "parsed" wrapper).
    bare = normalize_round({"metric": "x_throughput", "value": 5.0})
    assert bare == {"x_throughput": 5.0}


def test_noise_band_floor_and_spread():
    # Tight bank: the per-metric floor rules.
    assert noise_band("bert_base_samples_per_sec_chip",
                      [1000.0, 1010.0, 990.0]) == pytest.approx(0.08)
    # The resnet floor encodes the documented ±20% ambient drift.
    assert noise_band("resnet18_images_per_sec_chip",
                      [30000.0, 30100.0]) == pytest.approx(0.25)
    # A scattered bank widens the band past the floor: its own spread
    # is evidence of one-draw noise.
    band = noise_band("bert_base_samples_per_sec_chip",
                      [1000.0, 1400.0, 1200.0])
    assert band == pytest.approx((1400 - 1000) / 1200 / 2)


def test_gate_directions_and_no_baseline():
    hist = [
        {"tput": 100.0, "lat_ms": 10.0},
        {"tput": 104.0, "lat_ms": 11.0},
        {"tput": 96.0, "lat_ms": 9.0},
    ]
    hist = [dict(h, **{"serve_p99_ttft_ms": h.pop("lat_ms")}) for h in hist]
    rows = evaluate_regressions(
        {"tput": 80.0, "serve_p99_ttft_ms": 30.0, "brand_new": 1.0}, hist
    )
    by = {r["metric"]: r for r in rows}
    # Higher-is-better: 80 vs median 100 with band max(0.08, 0.04) ->
    # regression. Lower-is-better: 30 ms vs median 10 with band 0.5 ->
    # regression.
    assert by["tput"]["status"] == "regression"
    assert by["tput"]["baseline"] == 100.0
    assert by["serve_p99_ttft_ms"]["status"] == "regression"
    assert by["brand_new"]["status"] == "no-baseline"
    # Inside the band: ok; outside on the good side: improved.
    rows = evaluate_regressions(
        {"tput": 97.0, "serve_p99_ttft_ms": 4.0}, hist
    )
    by = {r["metric"]: r for r in rows}
    assert by["tput"]["status"] == "ok"
    assert by["serve_p99_ttft_ms"]["status"] == "improved"
    # min_history gates gating itself.
    rows = evaluate_regressions({"tput": 1.0}, hist[:1])
    assert rows[0]["status"] == "no-baseline"


def test_r05_incident_is_the_self_test():
    """The banked acceptance case: r05's ResNet-18 draw classifies as
    no-regression under the median-of-bank protocol (the max-of-bank
    ratio called it 0.923), and a halved draw still gates."""
    *history, r05 = [normalize_round(r) for r in ROUNDS_R01_R05]
    by_metric = {
        r["metric"]: r for r in evaluate_regressions(r05, history)
    }
    resnet = by_metric["resnet18_images_per_sec_chip"]
    assert resnet["value"] == 34065.5 and resnet["baseline"] < 34065.5
    assert resnet["status"] != "regression", resnet
    bert = by_metric["bert_base_samples_per_sec_chip"]
    assert bert["status"] != "regression", bert

    # The gate still has teeth: a genuinely halved ResNet draw is
    # outside ANY honest noise band.
    broken = dict(r05)
    broken["resnet18_images_per_sec_chip"] *= 0.5
    bad = {
        r["metric"]: r for r in evaluate_regressions(broken, history)
    }["resnet18_images_per_sec_chip"]
    assert bad["status"] == "regression", bad

    # Lower-is-better direction: a doubled latency regresses, a halved
    # one improves.
    lat_hist = [{"serve_p99_ttft_ms": v} for v in (100.0, 110.0, 105.0)]
    worse = evaluate_regressions({"serve_p99_ttft_ms": 220.0}, lat_hist)
    assert worse[0]["status"] == "regression", worse
    better = evaluate_regressions({"serve_p99_ttft_ms": 40.0}, lat_hist)
    assert better[0]["status"] == "improved", better


def test_no_bank_is_the_normal_case():
    """The repo root holds no BENCH_r*.json: the gate reports every
    metric as no-baseline and regresses nothing."""
    from scripts.bench_regress import default_history_paths, gate

    assert default_history_paths() == []
    rows = gate({"tput": 1.0}, default_history_paths())
    assert [r["status"] for r in rows] == ["no-baseline"]


def test_cli_gate_and_exit_codes(tmp_path, capsys):
    cur = tmp_path / "cur.json"
    hist_files = []
    for i, v in enumerate([100.0, 102.0, 98.0]):
        p = tmp_path / f"BENCH_r0{i + 1}.json"
        p.write_text(json.dumps(
            {"parsed": {"metric": "tput", "value": v}}
        ))
        hist_files.append(str(p))

    cur.write_text(json.dumps({"metric": "tput", "value": 99.0}))
    assert main([str(cur), "--history"] + hist_files) == 0
    out = capsys.readouterr().out
    assert "tput" in out and "ok" in out

    cur.write_text(json.dumps({"metric": "tput", "value": 50.0}))
    assert main([str(cur), "--history"] + hist_files) == 1
    captured = capsys.readouterr()
    assert "REGRESSION" in captured.err
    # --json emits machine-readable rows.
    assert main([str(cur), "--json", "--history"] + hist_files) == 1
    rows = json.loads(capsys.readouterr().out)
    assert rows[0]["status"] == "regression"


def test_format_rows_renders_every_status():
    rows = evaluate_regressions(
        {"a": 1.0},
        [{"a": 2.0}, {"a": 2.2}],
    ) + evaluate_regressions({"b": 1.0}, [])
    text = format_rows(rows)
    assert "REGRESSION" in text and "no-baseline" in text


def test_aliases_map_to_canonical_names():
    # Every alias target is itself stable (no chains).
    for target in ALIASES.values():
        assert target not in ALIASES


def test_zero_tolerance_metric_gates_on_absolute_value():
    """serve_steady_state_recompiles banks at 0, where the ratio
    protocol is blind (value/0 has no ratio): any positive draw must
    classify as regression, and staying at 0 as ok."""
    hist = [
        {"serve_steady_state_recompiles": 0.0},
        {"serve_steady_state_recompiles": 0.0},
    ]
    bad = evaluate_regressions({"serve_steady_state_recompiles": 3.0}, hist)
    assert bad[0]["status"] == "regression"
    good = evaluate_regressions({"serve_steady_state_recompiles": 0.0}, hist)
    assert good[0]["status"] == "ok"
