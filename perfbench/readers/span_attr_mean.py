def read(ctx, name, attr):
    """Mean of the attribute ``attr`` over the window's spans ``name``
    that carry it."""
    values = [s[attr] for s in ctx.window_spans(name) if attr in s]
    if not values:
        return None
    return sum(values) / len(values)
