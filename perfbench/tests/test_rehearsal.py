"""The harness end to end at tiny widths on the CPU.

``run_cell`` is everything of a run after the look for a chip. Here it
runs the tiny configurations of ``rehearsal/`` on the CPU (four forced
host devices for the four-chip cell). What comes out names the CPU as
its device and carries no share of a chip's peak: a rehearsal shows that
the paths, the arguments and the arithmetic hold together, never a time.

The tests after them break the timed path underneath (a token altered,
a step that changes nothing, an update too large, an update uphill, a
mask drawn otherwise) and see ``correct`` come out false.
"""

import json
import os

import pytest

from perfbench import run
from perfbench.device import require_chips
from perfbench.manifest import Manifest

HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.path.join(HERE, "rehearsal")
SERVE_STEADY = "mistral-7b-l16.shortchat-steady"
SERVE_BACKLOG = "mistral-7b-l16.longprompt-backlog"
TRAIN = "bert-base-sst2.b256"
TRAIN_4 = "bert-large-sst2-4chip.dp4-b256"


def rehearse(cell_name, trace=False, seconds=2.0, seed=7):
    import time

    manifest = Manifest(REHEARSAL)
    cell = manifest.cell(cell_name)
    device = require_chips(cell["chips"], allow_cpu=True)
    result = run.run_cell(manifest, cell, device, seed, seconds, trace,
                          time.monotonic())
    # The line a run prints is JSON.
    return manifest, json.loads(json.dumps(result))


@pytest.mark.parametrize("cell", [SERVE_STEADY, SERVE_BACKLOG, TRAIN, TRAIN_4])
def test_untraced_run_reports_the_cells_end_to_end_metrics(cell):
    manifest, out = rehearse(cell, seed=2**31 + 11)
    assert set(out) == {"correct", "attempted", "failed", "metrics", "device"}
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["count"] == manifest.cell(cell)["chips"]
    want = {m["name"] for m in manifest.metrics(cell, "end_to_end")}
    assert set(out["metrics"]) == want and "setup_s" in want
    for m in out["metrics"].values():
        assert m["value"] > 0 and isinstance(m["unit"], str)


@pytest.mark.parametrize("cell", [SERVE_STEADY, SERVE_BACKLOG, TRAIN])
def test_traced_run_reports_the_layers_and_no_share_of_a_peak(cell):
    manifest, out = rehearse(cell, trace=True, seconds=6.0)
    assert out["correct"] is True
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    want = {m["name"] for m in manifest.metrics(cell, "per_layer")}
    peaks = {n for n in want if n.endswith("_roofline") or "mfu" in n}
    assert set(out["metrics"]) == want - peaks
    recompiles = [v["value"] for k, v in out["metrics"].items()
                  if "recompiles" in k]
    assert all(v == 0 for v in recompiles)


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from tpudl.serve import engine

    sound = engine._select_greedy

    def altered(logits):
        return (sound(logits) + 1) % logits.shape[-1]

    monkeypatch.setattr(engine, "_select_greedy", altered)
    _, out = rehearse(SERVE_STEADY)
    assert out["correct"] is False


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    import tpudl.train

    sound = tpudl.train.compile_step

    def unchanged(step_fn, mesh, state, rules, **kwargs):
        real = sound(step_fn, mesh, state, rules, donate_state=False, **kwargs)

        def step(state, batch, rng):
            _, metrics = real(state, batch, rng)
            return state, metrics

        return step

    monkeypatch.setattr(tpudl.train, "compile_step", unchanged)
    _, out = rehearse(TRAIN)
    assert out["correct"] is False


def _with_optimizer(monkeypatch, change):
    """The trainer's optimizer, built from a changed configuration."""
    import dataclasses

    from tpudl.train import optim

    sound = optim.make_optimizer
    monkeypatch.setattr(
        optim, "make_optimizer",
        lambda cfg: sound(dataclasses.replace(cfg, **change(cfg))),
    )


def _comparisons(capsys):
    out = capsys.readouterr().out
    return {line.split()[1].rstrip(":"): "NOT OK" not in line
            for line in out.splitlines() if line.startswith("check ")}


def test_an_update_half_as_large_again_is_not_correct(monkeypatch, capsys):
    _with_optimizer(
        monkeypatch, lambda cfg: {"learning_rate": 1.5 * cfg.learning_rate})
    _, out = rehearse(TRAIN)
    assert out["correct"] is False
    ok = _comparisons(capsys)
    # The gradient is sound; it is the change that is wrong.
    assert ok["first_grad_median_leaf_error"] and ok["loss_step1_gap"]
    assert not ok["param_change_median_leaf_error"]


def test_an_update_uphill_is_not_correct(monkeypatch, capsys):
    _with_optimizer(
        monkeypatch, lambda cfg: {"learning_rate": -cfg.learning_rate})
    _, out = rehearse(TRAIN)
    assert out["correct"] is False
    ok = _comparisons(capsys)
    # The norms of the change are as they should be: only its sign is not.
    assert ok["param_change_norm_worst_leaf_gap"]
    assert not ok["param_change_median_leaf_error"]


def test_masks_drawn_otherwise_are_not_correct(monkeypatch):
    from perfbench.reference import bert as ref

    monkeypatch.setattr(ref.DropoutRule, "step_key",
                        staticmethod(lambda key, step: key))
    _, out = rehearse(TRAIN)
    assert out["correct"] is False


def test_run_refuses_a_machine_without_an_accelerator(capsys):
    with pytest.raises(SystemExit) as stop:
        run.main(["--workload", TRAIN, "--seed", "1", "--seconds", "1",
                  "--trace", "0"])
    assert stop.value.code not in (0, None)
    assert "{" not in capsys.readouterr().out
