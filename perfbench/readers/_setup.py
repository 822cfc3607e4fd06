"""Set-up as the program itself recorded it.

Since PR 49 the program records its start-up whether or not a span
recorder is on (``tpudl.obs.spans.startup_recorder``): ``startup.*``
phases, ``kernel.trace`` around a Pallas kernel's trace, and one
``program.trace`` / ``program.lower`` / ``program.compile`` record a
program that JAX built. The recorder that a traced run turns on after
set-up is handed them, so ``ctx.spans`` holds set-up's timeline before
the window's.
"""

PHASE = "startup."
PROGRAM = "program."


def records(ctx):
    """The program's span records that ENDED before the window began.
    None where there is no start-up timeline to read: an untraced run,
    or a program from before it recorded one (the readers then report
    nothing, never a zero that was not measured)."""
    t0 = ctx.record["t0_monotonic"]
    setup = [s for s in ctx.spans if s.get("kind") == "span"
             and s["ts"] + s["dur"] <= t0]
    if not any(s["name"].startswith((PHASE, PROGRAM)) for s in setup):
        return None
    return setup


def programs(setup: list) -> list:
    """The ``program.*`` records of the PROGRAM's programs: those named
    ``tpudl_...`` and those built while one of its start-up phases ran
    (an initialiser's ``jit(broadcast_in_dim)``), by the clock: a phase
    recorded after the fact (``startup.first_requests``) is no parent
    of what was built inside it. The reference's and the harness's own
    are neither and are left out, as ``compile_s`` leaves them out."""
    phases = [(s["ts"], s["ts"] + s["dur"]) for s in setup
              if s["name"].startswith(PHASE)]

    def inside_a_phase(s) -> bool:
        return any(lo <= s["ts"] and s["ts"] + s["dur"] <= hi
                   for lo, hi in phases)

    return [s for s in setup if s["name"].startswith(PROGRAM)
            and (str(s.get("program", "")).startswith("tpudl_")
                 or inside_a_phase(s))]


def union_seconds(spans: list) -> float:
    """The length of the union of the spans' intervals."""
    total, end = 0.0, float("-inf")
    for ts, dur in sorted((s["ts"], s["dur"]) for s in spans):
        if ts + dur > end:
            total += ts + dur - max(ts, end)
            end = ts + dur
    return total
