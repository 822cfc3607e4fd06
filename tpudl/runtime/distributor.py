"""TpuDistributor: distributed process bring-up and launch.

The TPU-native replacement for the reference lineage's HorovodRunner /
pyspark TorchDistributor launch path ("NCCL allreduce on GPU workers",
BASELINE.json `north_star`; the reference tree has no launcher —
SURVEY.md §2.3). Structural differences from the Horovod design:

- Bring-up is `jax.distributed.initialize(coordinator, num_processes,
  process_id)` — one JAX process per host, not one per accelerator.
- There are no framework-level collectives to install: gradient sync is
  compiled into the step by GSPMD from sharding annotations and rides ICI
  (TPU pods) or the Gloo/TCP fallback (CPU testing).

Three modes:

1. **In-process** (default, num_processes=1): `run(fn)` calls fn directly —
   single-host single-process, the configs[0]/configs[1] shape.
2. **Local spawn** (num_processes>1): N subprocesses against a localhost
   coordinator, each with its own (CPU) device set — the cluster-free way
   to exercise the real multi-process code path (SURVEY.md §4.2).
3. **Pod** (`TpuDistributor.pod().ensure_initialized()`): on a real TPU pod
   slice each host runs the same program; initialize() auto-detects
   coordinator and process_id from the TPU metadata environment.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import pickle
import shutil
import socket
import subprocess
import sys
import tempfile
import time
from tpudl.analysis.registry import env_int
from typing import Any, Callable, List, Optional, Sequence

from tpudl.obs import exporter as obs_exporter
from tpudl.obs import spans as obs_spans


def _free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _update_rank_heartbeats(
    hearts: dict, pending_pids, obs_workers: Optional[str]
) -> None:
    """Refresh each rank's liveness from its span file's mtime (the
    progress proxy the parent can read without cooperation from a hung
    worker) and publish ``rank<N>_last_heartbeat_age_s`` gauges. A rank
    no longer pending is stopped — exited workers are classified by
    ``collect``, never reported hung. Without span recording (or
    before a worker's file appears) the beat degrades to process
    liveness — "alive" keeps the heartbeat fresh, so a healthy
    obs-less cohort never false-flips /healthz stale; only with span
    files does a hung-but-alive rank show as a growing age."""
    from tpudl.obs import counters as obs_counters

    reg = obs_counters.registry()
    for pid, hb in hearts.items():
        if pid not in pending_pids:
            hb.stop()
        else:
            t = None
            if obs_workers is not None and os.path.isdir(obs_workers):
                hits = glob.glob(
                    os.path.join(obs_workers, f"spans-*-p{pid}-*.jsonl")
                )
                if hits:
                    t = max(os.path.getmtime(h) for h in hits)
            hb.beat_at(time.time() if t is None else t)
        age = hb.age_s()
        if age is not None:
            # Gauges keep their final value after the cohort exits —
            # the last observation, like every other obs gauge.
            reg.gauge(f"rank{pid}_last_heartbeat_age_s").set(age)


@dataclasses.dataclass
class WorkerFailure:
    """One failed worker, classified: ``kind`` is "exception" (the
    payload raised in Python), "exit" (died without a result — killed,
    OOMed, segfaulted; ``signal`` carries the signal number when the
    exit code encodes one), "exit-after-result" (returned a value but
    exited nonzero), or "timeout"."""

    pid: int
    kind: str
    detail: str
    returncode: Optional[int] = None
    signal: Optional[int] = None

    def describe(self) -> str:
        head = f"[process {self.pid}] {self.kind}"
        if self.signal is not None:
            import signal as _signal

            try:
                name = _signal.Signals(self.signal).name
            except ValueError:
                name = str(self.signal)
            head += f" (signal {name})"
        elif self.returncode not in (None, 0):
            head += f" (exit code {self.returncode})"
        return f"{head}: {self.detail}"


class WorkerFailedError(RuntimeError):
    """Cohort launch failed. ``failures`` carries the classified root
    failures; ``survivor_logs`` the log tails of every OTHER worker
    (peer-terminated or completed), which is where the actual cause
    often surfaces — e.g. the rank that logged the poison value before
    a PEER crashed on it."""

    def __init__(
        self,
        num_processes: int,
        failures: List[WorkerFailure],
        survivor_logs: "dict[int, str]",
    ):
        self.failures = failures
        self.survivor_logs = survivor_logs
        detail = "\n---\n".join(f.describe() for f in failures)
        if survivor_logs:
            detail += "\n---\nsurviving-worker log tails:"
            for pid, tail in sorted(survivor_logs.items()):
                detail += f"\n[process {pid}] {tail}"
        super().__init__(
            f"TpuDistributor: {len(failures)}/{num_processes} "
            f"worker(s) failed:\n{detail}"
        )


@dataclasses.dataclass
class TpuDistributor:
    """Launches a callable across JAX processes.

    Args:
      num_processes: process count. 1 = run in-process.
      coordinator_address: "host:port" for `jax.distributed.initialize`;
        a free localhost port is picked when spawning locally.
      platform: "cpu" for local spawn (the only platform it accepts: a
        chip belongs to one process), "tpu" on pods. In-process and pod
        modes never override the platform.
      devices_per_process: fake host devices per spawned worker.
      timeout_s: cohort wall-clock limit for local spawn.
      peer_grace_s: after the FIRST worker failure, how long surviving
        workers get to finish before the launcher tears them down
        (peers blocked on a collective with the dead rank never will).
    """

    num_processes: int = 1
    coordinator_address: Optional[str] = None
    platform: str = "cpu"
    devices_per_process: int = 1
    timeout_s: float = 600.0
    peer_grace_s: float = 5.0

    @classmethod
    def pod(cls) -> "TpuDistributor":
        """Distributor for a real TPU pod slice (one process per host)."""
        d = cls(num_processes=-1, platform="tpu")
        return d

    def ensure_initialized(self) -> None:
        """Bring up jax.distributed on a pod (idempotent).

        Each host of the slice runs the same program and calls this once
        BEFORE any other JAX call (backend init must not have happened yet);
        coordinator/process_id auto-detect from the TPU environment.
        """
        import jax

        # Idempotence check without touching the backend:
        # jax.process_count() would itself initialize XLA and poison
        # initialize().
        if jax.distributed.is_initialized():
            return
        if self.coordinator_address:
            jax.distributed.initialize(
                self.coordinator_address,
                num_processes=self.num_processes,
                process_id=env_int("TPUDL_PROCESS_ID", 0),
            )
        else:
            jax.distributed.initialize()

    # ------------------------------------------------------------------
    # run()
    # ------------------------------------------------------------------

    def run(self, fn: Callable, *args: Any, **kwargs: Any) -> List[Any]:
        """Run `fn(*args, **kwargs)` on every process; returns rank-ordered
        results (the HorovodRunner(np=N).run(...) analog).

        For local spawn, `fn` must be picklable by reference (a module-level
        function) — the same constraint TorchDistributor places on its
        train_fn in practice.
        """
        if self.num_processes == -1:
            # Pod mode: every host runs this same program; bring up the
            # slice-wide runtime, then run fn in-process on this host.
            self.ensure_initialized()
            return [fn(*args, **kwargs)]
        if self.num_processes in (0, 1):
            return [fn(*args, **kwargs)]
        return self._spawn_local(fn, args, kwargs)

    # ------------------------------------------------------------------
    # observability plumbing: each spawned worker streams its own span
    # file (tagged host/process — tpudl.obs.spans picks the tags up from
    # the TPUDL_* env this launcher already sets) into a workers/ subdir
    # of the parent's obs directory; run() merges those records into the
    # parent's stream afterward, so one `python -m tpudl.obs.report`
    # over the parent file sees every rank and can attribute cross-host
    # stragglers. Merged even when workers FAIL — that is precisely when
    # the spans matter.
    # ------------------------------------------------------------------

    def _obs_workers_dir(self) -> Optional[str]:
        rec = obs_spans.active_recorder()
        if rec is None or not rec.path:
            return None
        return os.path.join(os.path.dirname(rec.path), "workers")

    def _merge_worker_spans(self, workers_dir: str) -> None:
        rec = obs_spans.active_recorder()
        if rec is None:
            return
        for path in sorted(glob.glob(os.path.join(workers_dir, "*.jsonl"))):
            for record in obs_spans.read_jsonl(path):
                rec.ingest(record)
            os.remove(path)  # merged: a dir-wide report must not double-count
        try:
            os.rmdir(workers_dir)
        except OSError:
            pass

    def _spawn_local(self, fn, args, kwargs) -> List[Any]:
        if self.platform != "cpu":
            # A chip belongs to one process: N workers on this host
            # would each claim every local chip (after a parent that may
            # hold them already) and fail or hang.
            raise ValueError(
                f"TpuDistributor(platform={self.platform!r}, "
                f"num_processes={self.num_processes}): local spawn is "
                f"the CPU test path. On one host, ONE process drives "
                f"all local chips (num_processes=1; make_mesh over "
                f"jax.devices()); on a pod slice use "
                f"TpuDistributor.pod(), one process per host"
            )
        try:
            payload = pickle.dumps((fn, args, kwargs))
        except Exception as e:
            raise ValueError(
                "TpuDistributor.run requires a module-level (picklable) "
                f"function for multi-process launch; got {fn!r}: {e}"
            ) from e

        coord = self.coordinator_address or f"localhost:{_free_port()}"
        workdir = tempfile.mkdtemp(prefix="tpudl_dist_")
        obs_workers = self._obs_workers_dir()
        try:
            return self._spawn_in(workdir, coord, payload, obs_workers)
        finally:
            shutil.rmtree(workdir, ignore_errors=True)
            if obs_workers is not None:
                self._merge_worker_spans(obs_workers)

    def _spawn_in(
        self,
        workdir: str,
        coord: str,
        payload: bytes,
        obs_workers: Optional[str] = None,
    ) -> List[Any]:
        payload_path = os.path.join(workdir, "payload.pkl")
        with open(payload_path, "wb") as f:
            f.write(payload)

        procs = []
        for pid in range(self.num_processes):
            env = dict(os.environ)
            env["TPUDL_COORDINATOR"] = coord
            env["TPUDL_NUM_PROCESSES"] = str(self.num_processes)
            env["TPUDL_PROCESS_ID"] = str(pid)
            # Set, not defaulted: a JAX_PLATFORMS inherited from outside
            # would send the worker after the chip its parent may hold.
            env["JAX_PLATFORMS"] = self.platform
            if obs_workers is not None:
                env["TPUDL_OBS_DIR"] = obs_workers
            else:
                # Parent has no active recorder: workers must not
                # auto-enable one from an inherited TPUDL_OBS_DIR and
                # write files run() would never merge.
                env.pop("TPUDL_OBS_DIR", None)
            flags = env.get("XLA_FLAGS", "")
            flags = " ".join(
                t
                for t in flags.split()
                if not t.startswith("--xla_force_host_platform_device_count")
            )
            env["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{self.devices_per_process}"
            ).strip()
            result_path = os.path.join(workdir, f"result_{pid}.pkl")
            log_path = os.path.join(workdir, f"log_{pid}.txt")
            # Logs go to files, not pipes: a worker blocked on a full pipe
            # buffer would stall collectives on every other worker.
            log_f = open(log_path, "w")
            p = subprocess.Popen(
                [
                    sys.executable,
                    "-m",
                    "tpudl.runtime._worker",
                    payload_path,
                    result_path,
                ],
                env=env,
                stdout=log_f,
                stderr=subprocess.STDOUT,
                cwd=os.path.dirname(os.path.dirname(os.path.dirname(__file__))),
            )
            log_f.close()
            procs.append((pid, p, result_path, log_path))

        def read_log(path: str) -> str:
            try:
                with open(path) as f:
                    return f.read()[-4000:]
            except OSError:
                return "<no log>"

        # Per-rank liveness: a worker proves progress by appending to
        # its span file, so the file's mtime IS the rank's last
        # heartbeat — the parent polls it every poll interval and
        # publishes `rank<N>_last_heartbeat_age_s` gauges plus
        # /healthz heartbeats. A rank hung in a collective (alive, not
        # progressing) shows up as a growing age within seconds, not
        # only in post-mortem straggler attribution. Without span
        # recording the beat degrades to process liveness (see
        # _update_rank_heartbeats).
        launch_t = time.time()
        hearts = {
            pid: obs_exporter.Heartbeat(f"rank{pid}", clock=time.time)
            for pid, *_ in procs
        }
        for hb in hearts.values():
            hb.beat_at(launch_t)

        def update_rank_heartbeats(pending_pids) -> None:
            _update_rank_heartbeats(hearts, pending_pids, obs_workers)

        results: List[Any] = [None] * self.num_processes
        completed: List[int] = []
        failures: List[WorkerFailure] = []
        peer_terminated: dict = {}

        def collect(pid: int, p, result_path: str, log_path: str) -> None:
            """Classify one finished worker: success, a Python
            exception in the payload, an exit WITHOUT a result (killed
            / OOM / segfault — the signal is decoded from the exit
            code), or a result followed by a nonzero exit."""
            try:
                with open(result_path, "rb") as f:
                    status, value = pickle.load(f)
            except (FileNotFoundError, EOFError, pickle.UnpicklingError):
                rc = p.returncode
                sig = -rc if (rc is not None and rc < 0) else None
                failures.append(
                    WorkerFailure(
                        pid, "exit",
                        f"no result file\n{read_log(log_path)}",
                        returncode=rc, signal=sig,
                    )
                )
                return
            if status == "ok" and p.returncode == 0:
                results[pid] = value
                completed.append(pid)
            elif status == "ok":
                failures.append(
                    WorkerFailure(
                        pid, "exit-after-result",
                        f"worker returned a result but exited with code "
                        f"{p.returncode}\n{read_log(log_path)}",
                        returncode=p.returncode,
                    )
                )
            else:
                failures.append(
                    WorkerFailure(
                        pid, "exception", f"worker exception: {value}",
                        returncode=p.returncode,
                    )
                )

        # Poll ALL workers instead of waiting rank-by-rank: a worker
        # SIGKILLed mid-collective is detected within a poll interval,
        # its peers (blocked on the dead rank forever) get a short
        # grace, then the cohort is torn down and reported — the
        # supervisor's restart latency is the poll interval, not the
        # full timeout budget.
        pending = {
            pid: (p, result_path, log_path)
            for pid, p, result_path, log_path in procs
        }
        deadline = time.monotonic() + self.timeout_s
        grace_deadline: Optional[float] = None
        timed_out = False
        while pending:
            for pid in sorted(pending):
                p, result_path, log_path = pending[pid]
                if p.poll() is not None:
                    del pending[pid]
                    collect(pid, p, result_path, log_path)
            update_rank_heartbeats(pending)
            if not pending:
                break
            now = time.monotonic()
            if grace_deadline is None and (failures or now >= deadline):
                # First failure OR the cohort budget spent: survivors
                # get peer_grace_s to finish naturally (a near-done
                # peer classifies by its real outcome, not as
                # collateral) before the launcher tears down.
                timed_out = not failures and now >= deadline
                grace_deadline = now + self.peer_grace_s
            if grace_deadline is not None and now >= grace_deadline:
                # Decide ONCE: either the teardown is a pure-timeout
                # one (every still-pending worker is a root timeout)
                # or a peer teardown after real failures.
                as_timeouts = timed_out and not failures
                for pid in sorted(pending):
                    p, result_path, log_path = pending.pop(pid)
                    p.kill()
                    p.wait()
                    if as_timeouts:
                        # Budget spent, nobody else failed: the still-
                        # running workers ARE the root cause.
                        failures.append(
                            WorkerFailure(
                                pid, "timeout",
                                f"timeout after {self.timeout_s}s\n"
                                f"{read_log(log_path)}",
                            )
                        )
                    else:
                        # Peers of a dead worker: terminated by the
                        # launcher, NOT root failures — but their logs
                        # often hold the real story, so keep the tails
                        # for the error detail.
                        peer_terminated[pid] = read_log(log_path)
                break
            time.sleep(0.05)
        # Every exit path (drained, timeout teardown, peer teardown)
        # leaves no rank marked running — a torn-down worker must not
        # read as "hung" on /healthz forever after.
        update_rank_heartbeats(pending)

        if failures:
            survivor_logs = dict(peer_terminated)
            for pid, _, _, log_path in procs:
                if pid in completed:
                    survivor_logs[pid] = read_log(log_path)
            raise WorkerFailedError(
                self.num_processes, failures, survivor_logs
            )
        return results
