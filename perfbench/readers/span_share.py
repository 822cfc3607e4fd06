def read(ctx, name):
    """Time in the program's spans called ``name`` over the window, %."""
    spans = ctx.window_spans(name)
    if not ctx.spans:
        return None
    return 100.0 * sum(s["dur"] for s in spans) / ctx.record["window_s"]
