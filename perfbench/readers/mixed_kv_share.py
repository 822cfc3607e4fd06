from perfbench import flops_window_moe as fl


def read(ctx, what):
    """Mean over the window's ``decode_step`` spans of a share of the
    two-group page cache, %, from the spans' counters by group:

    ``what="live_over_uniform"``: the live keys and values the step
    reads (full-context layers their whole live context,
    ``tokens_live``; window layers at most their window a sequence,
    ``tokens_live_window``) over what one table for every layer would
    read (every layer ``tokens_live``).

    ``what="ring_pages"``: the pages reserved in the window layers'
    rings (``pages_reserved_window`` in each window layer) over all
    pages reserved (those, and ``pages_reserved`` in each full-context
    layer).

    Nothing where the spans lack the window group's counters."""
    spans = [s for s in ctx.window_spans("decode_step")
             if "tokens_live_window" in s and "pages_reserved_window" in s]
    full, window = fl.layer_counts(ctx.config)
    shares = []
    for s in spans:
        if what == "live_over_uniform":
            part = fl.live_kv_bytes(
                ctx.config, s["tokens_live"], s["tokens_live_window"])
            whole = fl.uniform_kv_bytes(ctx.config, s["tokens_live"])
        elif what == "ring_pages":
            part = window * s["pages_reserved_window"]
            whole = part + full * s["pages_reserved"]
        else:
            raise ValueError(
                f"what must be live_over_uniform or ring_pages: {what!r}")
        if whole > 0:
            shares.append(part / whole)
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
