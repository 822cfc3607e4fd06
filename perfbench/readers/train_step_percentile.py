from perfbench.stats import percentile


def read(ctx, p):
    """Percentile of the time from one step's end to the next's, ms."""
    rec = ctx.record
    if rec.get("kind") != "train" or len(rec["steps"]) < 2:
        return None
    ends = [t for t, _ in rec["steps"]]
    return 1e3 * percentile([b - a for a, b in zip(ends, ends[1:])], p)
