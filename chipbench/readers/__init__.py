"""One module per way of reading a per-layer metric from a traced run,
found by the ``reader`` key of ``metrics/<name>.json``. Each has
``read(ctx, **params)`` and returns a number, or ``None`` where it finds
nothing to read. See README.md for what ``ctx`` holds."""
