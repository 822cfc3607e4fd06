def read(ctx):
    """Seconds the backend compiled (or read its cache) during set-up,
    the reference's programs left out."""
    return ctx.setup["compile_s"]
