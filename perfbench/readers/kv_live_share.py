def read(ctx, over):
    """Mean over the window's ``decode_step`` spans of the positions
    the seated slots hold live (``tokens_live``) over what is kept for
    them, %: ``over="reserved"``, the pages they reserve x the page
    size; ``over="gathered"``, slots x positions a slot, which the
    decode program gathers whatever the live lengths."""
    spans = [s for s in ctx.window_spans("decode_step")
             if "tokens_live" in s and "pages_reserved" in s]
    if not spans:
        return None
    session = ctx.config["session"]
    shares = []
    for s in spans:
        if over == "reserved":
            kept = s["pages_reserved"] * int(session["page_size"])
        elif over == "gathered":
            kept = ctx.record["slots"] * int(session["max_seq_len"])
        else:
            raise ValueError(f"over must be reserved or gathered: {over!r}")
        if kept > 0:
            shares.append(s["tokens_live"] / kept)
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
