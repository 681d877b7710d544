"""One module per way of reading a per-layer metric. ``read(sample,
args)`` gets the run's sample (counters at the window's start and end,
the harness's own records, the reduced trace) and the ``args`` of the
metric's file, and returns a number, or None where it finds nothing to
read."""
