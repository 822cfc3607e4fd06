"""TpuDistributor launch-path tests (SURVEY.md §4.2: localhost multi-process
bring-up substitutes for the reference lineage's run-on-a-cluster testing)."""

import numpy as np
import pytest

from tests import dist_helpers
from tpudl.runtime.distributor import TpuDistributor


def test_in_process_mode():
    d = TpuDistributor(num_processes=1)
    results = d.run(lambda x: x + 1, 41)
    assert results == [42]


def test_unpicklable_fn_error():
    d = TpuDistributor(num_processes=2)
    with pytest.raises(ValueError, match="picklable"):
        d.run(lambda x: x, 1)


def test_local_spawn_refuses_tpu_platform():
    """A chip belongs to one process: N local workers on platform "tpu"
    would each claim every chip. Refused before anything is spawned."""
    d = TpuDistributor(num_processes=2, platform="tpu")
    with pytest.raises(ValueError, match="ONE process drives all local chips"):
        d.run(dist_helpers.report_topology)


def test_spawned_worker_platform_is_set_not_inherited(monkeypatch, tmp_path):
    """The worker's JAX_PLATFORMS is the distributor's platform even
    when the parent's environment names another (the worker would go
    after a chip its parent may hold)."""
    import subprocess

    seen = {}

    class _Done:
        returncode = 0

        def poll(self):
            return 0

    def fake_popen(cmd, env=None, **kwargs):
        seen.setdefault("platforms", []).append(env["JAX_PLATFORMS"])
        assert "TPUDL_PLATFORM" not in env
        return _Done()

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(subprocess, "Popen", fake_popen)
    d = TpuDistributor(num_processes=2, platform="cpu", timeout_s=5.0)
    with pytest.raises(RuntimeError):  # no worker ran: no results
        d._spawn_in(str(tmp_path), "localhost:1", b"", None)
    assert seen["platforms"] == ["cpu", "cpu"]


@pytest.mark.slow
def test_spawn_two_processes_topology():
    d = TpuDistributor(num_processes=2, platform="cpu", devices_per_process=2)
    results = d.run(dist_helpers.report_topology)
    assert [r["process_index"] for r in results] == [0, 1]
    for r in results:
        assert r["process_count"] == 2
        assert r["local_devices"] == 2
        assert r["global_devices"] == 4


@pytest.mark.slow
def test_spawn_global_collective():
    d = TpuDistributor(num_processes=2, platform="cpu", devices_per_process=2)
    results = d.run(dist_helpers.global_sum)
    # 2 devices * 1.0 (proc 0) + 2 devices * 2.0 (proc 1) = 6.0 on every rank
    assert results == [6.0, 6.0]


@pytest.mark.slow
def test_spawn_distributed_train_smoke():
    d = TpuDistributor(num_processes=2, platform="cpu", devices_per_process=2)
    results = d.run(dist_helpers.distributed_train_smoke)
    for losses in results:
        assert len(losses) == 3
        assert all(l == l for l in losses)  # no NaNs
    # Both ranks computed the same global losses.
    assert results[0] == pytest.approx(results[1])


@pytest.mark.slow
def test_worker_failure_propagates():
    d = TpuDistributor(num_processes=2, platform="cpu", devices_per_process=1)
    with pytest.raises(RuntimeError, match="intentional worker failure"):
        d.run(dist_helpers.failing_worker)


@pytest.mark.slow
def test_spawn_converter_fed_training(tmp_path):
    """BASELINE.json north_star composition, executed: a materialized
    Parquet dataset feeds a 2-process x 2-device fit() run through
    disjoint converter shards and prefetch_to_device's
    make_array_from_process_local_data path. Every rank sees identical
    global losses; the ranks together consume the whole dataset (minus
    per-shard batch truncation)."""
    from tpudl.data.datasets import materialize_cifar10_like

    data_dir = str(tmp_path / "cifar")
    # 250 rows / 2 shards / batch 16: each 125-row shard truncates its
    # last partial batch to 112 consumed rows — real truncation, so the
    # coverage arithmetic below actually verifies the shard contract.
    num_rows, local_batch = 250, 16
    conv = materialize_cifar10_like(
        data_dir, num_rows=num_rows, rows_per_file=64
    )
    assert len(conv) == num_rows

    d = TpuDistributor(num_processes=2, platform="cpu", devices_per_process=2)
    results = d.run(dist_helpers.converter_fed_train, data_dir, local_batch)

    (losses0, rows0), (losses1, rows1) = results
    assert losses0, "no training steps ran"
    # Identical global losses on every rank (the global-array contract).
    assert losses0 == pytest.approx(losses1)
    assert all(np.isfinite(losses0))
    # Disjoint shards cover the dataset minus drop_last truncation only.
    shard = num_rows // 2
    expected_per_rank = (shard // local_batch) * local_batch
    assert expected_per_rank < shard  # truncation genuinely exercised
    assert rows0 == rows1 == expected_per_rank
    assert len(losses0) == expected_per_rank // local_batch


@pytest.mark.slow
def test_spawn_prefetch_multicolumn_global():
    """Multi-column batches through the two-stage prefetch's multi-host
    make_array_from_process_local_data path: global shapes/dtypes, exact
    cross-process sums, and source ORDER (the assembly pool must not
    reorder) agree on every rank."""
    local_batch, num_batches = 8, 6
    d = TpuDistributor(num_processes=2, platform="cpu", devices_per_process=2)
    r0, r1 = d.run(
        dist_helpers.prefetch_multicolumn_global, local_batch, num_batches
    )
    assert len(r0) == len(r1) == num_batches
    for i, (a, b) in enumerate(zip(r0, r1)):
        # Both ranks observed the same GLOBAL batch, in source order.
        assert a == b
        assert a["order"] == i
        assert a["shapes"] == {
            "image": (16, 4, 4, 3),
            "label": (16,),
            "weight": (16,),
            "order": (16,),
        }
        assert a["dtypes"]["image"] == "uint8"
        assert a["dtypes"]["label"] == "int32"
        assert a["dtypes"]["weight"] == "float32"
        # label: rank 0 contributes 8*(i*1000), rank 1 adds 8*(i*1000+100).
        assert a["sums"]["label"] == 8 * (i * 1000) + 8 * (i * 1000 + 100)
        assert a["sums"]["image"] == 16 * 4 * 4 * 3 * (i + 1)
        assert a["sums"]["weight"] == 16.0 * i


@pytest.mark.slow
def test_spawn_checkpoint_save_resume(tmp_path):
    """Multi-process checkpoint/resume — the actual pod recovery story
    (SURVEY.md §5.3-5.4): 2 spawned JAX processes train and save through
    CheckpointManager (Orbax multi-process coordination over the shared
    filesystem), the processes EXIT (the kill), a fresh 2-process spawn
    restores on both ranks and continues — with post-resume losses
    exactly equal to an uninterrupted run's tail, identical on both
    ranks."""
    ckpt = str(tmp_path / "ckpt")
    d = TpuDistributor(num_processes=2, platform="cpu", devices_per_process=2)
    phase1 = d.run(dist_helpers.checkpoint_save_phase, ckpt, 3)
    (r0, losses0), (r1, losses1) = sorted(phase1)
    assert (r0, r1) == (0, 1)
    assert losses0 == pytest.approx(losses1)

    # Fresh distributor = fresh processes: nothing survives but the disk.
    d2 = TpuDistributor(num_processes=2, platform="cpu", devices_per_process=2)
    phase2 = d2.run(dist_helpers.checkpoint_resume_phase, ckpt, 5, 3)
    (_, step0, resumed0, control0), (_, step1, resumed1, control1) = sorted(
        phase2
    )
    assert step0 == step1 == 3  # both ranks restored the same checkpoint
    assert resumed0 == pytest.approx(resumed1)  # ranks agree post-resume
    # The restored trajectory IS the uninterrupted trajectory: the
    # control's first 3 steps reproduce phase 1, its tail equals the
    # post-resume losses (params, momentum, BN stats, and the step
    # counter all round-tripped).
    assert control0[:3] == pytest.approx(losses0)
    assert resumed0 == pytest.approx(control0[3:])
    assert all(np.isfinite(resumed0))
