from perfbench import flops_sink_window_moe as fl


def read(ctx, what):
    """Mean over the window's ``decode_step`` spans of a share of the
    two-group page cache in BYTES, %, each group weighted by its own
    row bytes (the groups' rows differ: positions and pages do not say
    bytes):

    ``what="live_over_uniform"``: the bytes of cache rows the step's
    attention read over both groups, the program's own count
    (``kv_bytes_live``), over what one table for every layer would read
    (every layer ``tokens_live`` positions at its own row bytes).

    ``what="ring_bytes"``: the bytes of the pages reserved in the window
    layers' rings (``pages_reserved_window`` in each) over all reserved
    bytes (those, and ``pages_reserved`` in each full-context layer).

    Nothing where the spans lack the counters."""
    spans = [s for s in ctx.window_spans("decode_step")
             if "kv_bytes_live" in s and "pages_reserved_window" in s]
    page = int(ctx.config["session"]["page_size"])
    shares = []
    for s in spans:
        if what == "live_over_uniform":
            part = s["kv_bytes_live"]
            whole = fl.uniform_kv_bytes(ctx.config, s["tokens_live"])
        elif what == "ring_bytes":
            rest, part = fl.reserved_kv_bytes(
                ctx.config, page, s["pages_reserved"],
                s["pages_reserved_window"])
            whole = part + rest
        else:
            raise ValueError(
                f"what must be live_over_uniform or ring_bytes: {what!r}")
        if whole > 0:
            shares.append(part / whole)
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
