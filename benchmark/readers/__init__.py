"""Per-layer readers: ``read(ctx, **args)`` returns the metric's value, or
None when the run left it nothing to read (the metric is then left out)."""
