"""One reader per metric: ``read(ctx: run.RunContext)`` returns the
metric's value, or None where the run has nothing to read it from."""
