def read(ctx, name, part, whole):
    """The attribute ``part`` summed over the window's spans ``name``,
    over the sum of the attribute ``whole`` there. Nothing where no
    span carries both (a program from before it counted them)."""
    spans = [s for s in ctx.window_spans(name) if part in s and whole in s]
    total = sum(s[whole] for s in spans)
    if total <= 0:
        return None
    return sum(s[part] for s in spans) / total
