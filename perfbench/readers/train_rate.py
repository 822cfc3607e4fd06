def read(ctx):
    """Samples of the steps completed in the window over its seconds."""
    rec = ctx.record
    if rec.get("kind") != "train" or not rec["steps"]:
        return None
    return len(rec["steps"]) * rec["batch"] / rec["window_s"]
