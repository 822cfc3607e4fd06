def read(ctx):
    """Slots in use per decode step over the slots there are, %."""
    spans = ctx.window_spans("decode_step")
    if not spans:
        return None
    busy = sum(s["busy"] for s in spans) / len(spans)
    return 100.0 * busy / ctx.record["slots"]
