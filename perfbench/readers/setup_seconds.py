def read(ctx):
    """Process start to the first measured step or request."""
    return ctx.setup["setup_s"]
