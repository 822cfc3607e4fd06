from perfbench.readers._serve import finished
from perfbench.stats import percentile


def read(ctx, p):
    """Time from the moment a request was due to its first token, ms."""
    reqs = finished(ctx)
    if not reqs:
        return None
    return 1e3 * percentile([r["token_s"][0] - r["due_s"] for r in reqs], p)
