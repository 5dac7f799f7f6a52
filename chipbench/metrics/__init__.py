"""One reader per metric: ``read(run) -> value or None``, found by name."""
