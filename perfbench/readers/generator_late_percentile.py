from perfbench.readers._serve import measured
from perfbench.stats import percentile


def read(ctx, p):
    """How late the generator submitted: submit time - due time, ms."""
    reqs = measured(ctx)
    if not reqs:
        return None
    return 1e3 * percentile([r["submit_s"] - r["due_s"] for r in reqs], p)
