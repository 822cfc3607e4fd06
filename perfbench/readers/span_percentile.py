from perfbench.stats import percentile


def read(ctx, name, p):
    """Percentile of the durations of the program's spans ``name``, ms."""
    spans = ctx.window_spans(name)
    if not spans:
        return None
    return 1e3 * percentile([s["dur"] for s in spans], p)
