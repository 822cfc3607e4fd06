from perfbench.readers import _setup


def read(ctx, cache_hit=None):
    """Executables set-up compiled or read from the compile cache for
    the program; with ``cache_hit`` 0 those the cache did not hold."""
    setup = _setup.records(ctx)
    if setup is None:
        return None
    built = [s for s in _setup.programs(setup)
             if s["name"] == _setup.PROGRAM + "compile"]
    if cache_hit is not None:
        built = [s for s in built if s.get("cache_hit") == cache_hit]
    return len(built)
