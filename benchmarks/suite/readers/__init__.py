"""One module per per-layer metric reader, found by the ``reader`` a metric's
file names.  ``read(context, **args)`` returns the number, or None where it
finds nothing to read (the metric is then left out of the line)."""
