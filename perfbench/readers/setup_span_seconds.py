from perfbench.readers import _setup


def read(ctx, names):
    """Seconds of set-up inside the program's spans called one of
    ``names`` (phases that do not nest in one another)."""
    setup = _setup.records(ctx)
    if setup is None:
        return None
    return sum(s["dur"] for s in setup if s["name"] in names)
