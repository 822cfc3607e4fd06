from perfbench.readers._serve import finished
from perfbench.stats import percentile, tpot_s


def read(ctx, p):
    """Per request, (last token - first token)/(tokens - 1), ms."""
    gaps = [tpot_s(r["token_s"]) for r in finished(ctx)]
    gaps = [g for g in gaps if g is not None]
    if not gaps:
        return None
    return 1e3 * percentile(gaps, p)
