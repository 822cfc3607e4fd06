"""The traced part of a traced run: a profiler trace of a few seconds
inside the window, and the marks that tie its clock to the host's."""

from __future__ import annotations

import os
import shutil
import time

from perfbench.trace import ClockSync


class Tracer:
    """Starts the profiler once the window has run ``start_at_s``
    seconds, and stops it when the window's driver calls ``finish``."""

    def __init__(self, out_dir: str, start_at_s: float):
        self.out_dir = out_dir
        self.start_at_s = start_at_s
        self.sync = ClockSync()
        self.tracing = False
        self.done = False
        self.start_stall_s = 0.0
        self.stop_stall_s = 0.0
        shutil.rmtree(out_dir, ignore_errors=True)
        os.makedirs(out_dir, exist_ok=True)

    def poll(self, now_s: float) -> None:
        if self.tracing or self.done or now_s < self.start_at_s:
            return
        import jax

        options = jax.profiler.ProfileOptions()
        # Python's own tracer would slow the host loop it watches.
        options.python_tracer_level = 0
        t = time.monotonic()
        jax.profiler.start_trace(self.out_dir, profiler_options=options)
        self.start_stall_s = time.monotonic() - t
        self.tracing = True

    def finish(self) -> None:
        if not self.tracing:
            return
        import jax

        t = time.monotonic()
        jax.profiler.stop_trace()
        self.stop_stall_s = time.monotonic() - t
        self.tracing = False
        self.done = True
