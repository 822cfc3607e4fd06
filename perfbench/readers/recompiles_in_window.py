def read(ctx):
    """Backend compilations inside the measured window."""
    return ctx.record.get("compiles_in_window")
