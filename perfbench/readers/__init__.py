"""Readers: one small module per way of taking a metric from a run.
``read(ctx, **args)`` returns a number, or None when it finds nothing
to read (the metric is then left out of the line)."""
