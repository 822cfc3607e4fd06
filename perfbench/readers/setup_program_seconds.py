from perfbench.readers import _setup


def read(ctx, stage):
    """Seconds of set-up in one stage of building the program's
    programs: ``trace`` (their Python, a Pallas kernel's body
    included), ``lower`` (to MLIR modules, a kernel's Mosaic lowering
    included) or ``compile`` (the backend's compile or cache read)."""
    setup = _setup.records(ctx)
    if setup is None:
        return None
    return sum(s["dur"] for s in _setup.programs(setup)
               if s["name"] == _setup.PROGRAM + stage)
