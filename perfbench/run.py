"""One run of one cell.

    python3 -m perfbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run is one new process: it requires the cell's chips (and fails, never
falling back to the CPU), builds the system from the seed, warms exactly
the cell's shapes, measures for ``--seconds``, checks what the window
produced against the plain reference, and prints one JSON object as the
last line of its standard output.
"""

from __future__ import annotations

import time

_T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORK_DIR = os.path.join(ROOT, "perfbench", ".work")
TRACE_SECONDS = 4.0


def place_compile_cache() -> None:
    """The compile cache sits at a fixed path inside the checkout, unless
    the machine has placed it already. Before the first import of JAX."""
    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.join(ROOT, "perfbench", ".cache", "jax"),
    )


def say(*parts) -> None:
    print(*parts, flush=True)


class Context:
    """What the readers read: the window's record, the program's spans,
    the set-up's numbers and, lazily, the reduced trace."""

    def __init__(self, cell, device, record, setup, spans, tracer):
        self.config = cell["config"]
        self.device = device
        self.record = record
        self.setup = setup
        self.spans = spans
        self.tracer = tracer
        self._trace = None

    def window_spans(self, name: str) -> list:
        """The program's spans of that name that began in the window."""
        t0 = self.record["t0_monotonic"]
        t1 = t0 + self.record["window_s"]
        return [s for s in self.spans if s.get("kind") == "span"
                and s.get("name") == name and t0 <= s["ts"] < t1]

    @property
    def trace(self):
        """{"raw", "offset_ns", "reduced"} of a traced run, else None."""
        if self.tracer is None or not self.tracer.done:
            return None
        if self._trace is None:
            from perfbench import trace as tr

            raw = tr.load(tr.find_xplane(self.tracer.out_dir),
                          host_prefix=tr.HOST_PREFIX)
            offset = tr.clock_offset_ns(raw, self.tracer.sync.marks_s)
            host = []
            if offset is not None:
                host = tr.spans_on_trace_clock(
                    self.spans, offset,
                    ("prefill", "decode_step", "train_step", "data_wait"),
                )
                for plane in raw["planes"]:
                    if not plane["name"].startswith("/host"):
                        continue
                    for line in plane["lines"]:
                        host += [(e[0], e[1], e[1] + e[2])
                                 for e in line["events"]
                                 if e[0].startswith(tr.HOST_PREFIX)
                                 and e[0] != tr.SYNC_NAME]
            self._trace = {
                "raw": raw, "offset_ns": offset,
                "reduced": tr.reduce(raw, host),
            }
        return self._trace


def read_metrics(manifest, cell_name: str, group: str, ctx: Context) -> dict:
    out = {}
    for m in manifest.metrics(cell_name, group):
        reader = importlib.import_module(f"perfbench.readers.{m['reader']}")
        value = reader.read(ctx, **m["args"])
        # A reader that finds nothing to read returns nothing, and the
        # metric is left out of the line.
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(manifest, cell: dict, device: dict, seed: int, seconds: float,
             trace: bool, t_start: float) -> dict:
    """Everything of a run after the look for a chip. ``t_start``: when
    the device was ready, from which ``setup_s`` counts."""
    from perfbench.device import memory_peak_bytes
    from perfbench.tracing import Tracer
    from tpudl.analysis.dispatch import compile_seconds
    from tpudl.obs import spans as obs_spans

    family = importlib.import_module(
        f"perfbench.families.{cell['config']['family']}"
    )
    compile_0 = compile_seconds()
    t_build = time.monotonic()
    system = family.build(cell["config"], device, seed)
    t_warm = time.monotonic()
    system.warm_up(cell["traffic"], seconds)
    say(f"set-up: {t_build - t_start:.2f} s importing, "
        f"{t_warm - t_build:.2f} s building, "
        f"{time.monotonic() - t_warm:.2f} s warming up")
    compile_s = compile_seconds() - compile_0 - getattr(
        system, "reference_compile_seconds", 0.0
    )
    tracer = recorder = None
    if trace:
        os.makedirs(WORK_DIR, exist_ok=True)
        span_file = os.path.join(WORK_DIR, "spans.jsonl")
        if os.path.exists(span_file):
            os.remove(span_file)
        recorder = obs_spans.enable(span_file)
        tracer = Tracer(
            os.path.join(WORK_DIR, "trace"),
            start_at_s=max(0.0, seconds - TRACE_SECONDS),
        )
    # What set-up built lives as long as the run: keep the collector
    # from walking it again and again inside the window.
    gc.collect()
    gc.freeze()
    reference_s = getattr(system, "reference_seconds", 0.0)
    setup = {
        "setup_s": time.monotonic() - t_start - reference_s,
        "compile_s": compile_s,
        "reference_s": reference_s,
    }
    say(f"set-up {setup['setup_s']:.2f} s (compile {compile_s:.2f} s; "
        f"reference {reference_s:.2f} s, not counted)")
    record = system.run_window(cell["traffic"], seconds, tracer)
    gc.unfreeze()
    memory = memory_peak_bytes(cell["chips"])
    spans = []
    if recorder is not None:
        spans = recorder.records
        obs_spans.disable()
    system.release()
    t = time.monotonic()
    check = system.check(record)
    say(f"check took {time.monotonic() - t:.2f} s")
    correct = True
    for c in check["comparisons"]:
        ok = c["value"] <= c["limit"]
        correct = correct and ok
        where = f" ({c['leaf']})" if "leaf" in c else ""
        say(f"check {c['name']}: value={c['value']!r} limit={c['limit']!r} "
            f"{'ok' if ok else 'NOT OK'}{where}")
    attempted, failed = family.attempted_failed(record)
    ctx = Context(cell, device, record, setup, spans, tracer)
    group = "per_layer" if trace else "end_to_end"
    result = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": read_metrics(manifest, cell["name"], group, ctx),
        "device": {**device, **memory},
    }
    if not trace:
        # What the layers' readers can read without spans or a trace, for
        # the log (the result line carries the end-to-end metrics only).
        also = read_metrics(manifest, cell["name"], "per_layer", ctx)
        say("layers, untraced: " + json.dumps(
            {k: v["value"] for k, v in also.items()}))
    if trace and ctx.trace is not None:
        reduced = ctx.trace["reduced"]
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {
            "device_ops": reduced["device_ops"],
            "idle_gaps": reduced["idle_gaps"],
        }
        say(f"trace: start stalled {tracer.start_stall_s:.3f} s, stop "
            f"{tracer.stop_stall_s:.3f} s, {len(tracer.sync.marks_s)} marks")
    say("checked: " + json.dumps({k: v for k, v in check.items()
                                  if k != "comparisons"}))
    # For the log only: the window's own timeline, from which another
    # statistic than the judged one can be worked out afterwards.
    say("timeline: " + json.dumps(family.timeline(record)))
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    place_compile_cache()
    from perfbench.device import require_chips
    from perfbench.manifest import Manifest

    manifest = Manifest(ROOT)
    cell = manifest.cell(args.workload)
    device = require_chips(cell["chips"])
    # ``setup_s`` counts from here. What comes before is the interpreter,
    # JAX's import and the TPU runtime's own start: 9-19 s that swing
    # from run to run and that no code of the repo touches (PERF.md 2).
    t_ready = time.monotonic()
    say(f"device: {device}, ready {t_ready - _T_START:.2f} s after the "
        f"process began (not in setup_s)")
    result = run_cell(manifest, cell, device, args.seed, args.seconds,
                      bool(args.trace), t_ready)
    say(f"run took {time.monotonic() - _T_START:.1f} s")
    say(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
