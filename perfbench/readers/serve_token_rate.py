def read(ctx):
    """Generated tokens of the requests completed in the window over
    its seconds."""
    rec = ctx.record
    if rec.get("kind") != "serve":
        return None
    tokens = sum(
        len(r["tokens"]) for r in rec["requests"]
        if r["finish_reason"] in ("length", "eos")
        and r["token_s"] and r["token_s"][-1] <= rec["window_s"]
    )
    return tokens / rec["window_s"]
