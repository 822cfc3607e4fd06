from perfbench.readers import _program_trace as pt
from perfbench.stats import percentile


def read(ctx, name, p):
    """Over the occurrences of the program's annotation ``tpudl.<name>``
    that lie wholly inside the traced window: the time inside each in
    which no device operation ran (its length less the device's busy
    time there), percentile ``p``, ms. For leaf spans: a span with
    children counts their idle too."""
    trace = pt.of_run(ctx)
    if trace is None:
        return None
    spans = pt.occurrences(trace, name)
    if not spans:
        return None
    merged = pt.busy(trace)
    return 1e-6 * percentile(
        [pt.idle_inside(merged, s, e) for s, e, _ in spans], p
    )
