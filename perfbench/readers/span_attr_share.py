def read(ctx, name, part, whole):
    """The attribute ``part`` summed over the window's spans ``name``,
    over the sum of the attributes ``whole`` there, %. Nothing where no
    span carries them (a program from before it counted them)."""
    spans = [s for s in ctx.window_spans(name)
             if part in s and all(w in s for w in whole)]
    total = sum(s[w] for s in spans for w in whole)
    if total <= 0:
        return None
    return 100.0 * sum(s[part] for s in spans) / total
