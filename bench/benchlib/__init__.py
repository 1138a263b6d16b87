"""The benchmark's own library: specs, traffic, data, reference, checks and
the reduction of traces to metrics.  Nothing here is imported by the
program under test, and nothing here imports the program except
:mod:`benchlib.serve`, which drives it."""
