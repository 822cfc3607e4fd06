from perfbench.readers import _setup


def read(ctx):
    """Seconds of ``setup_s`` outside the program: what is left of it
    beside the union of the program's start-up phases and of its
    programs built outside them. The harness's own (weights from the
    seed, the traffic's generator, hand-overs between the two), which
    no change to the program can shorten."""
    setup = _setup.records(ctx)
    if setup is None:
        return None
    own = [s for s in setup if s["name"].startswith(_setup.PHASE)]
    own += _setup.programs(setup)
    return ctx.setup["setup_s"] - _setup.union_seconds(own)
