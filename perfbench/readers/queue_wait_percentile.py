from perfbench.readers._serve import measured
from perfbench.stats import percentile


def read(ctx, p):
    """Wait from due time to seating: the generator's lateness plus the
    engine's own queue wait, ms."""
    waits = [r["submit_s"] - r["due_s"] + r["queue_wait_s"]
             for r in measured(ctx) if r["queue_wait_s"] is not None]
    if not waits:
        return None
    return 1e3 * percentile(waits, p)
