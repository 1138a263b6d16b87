"""Seconds from process start to the first timed request: data made on
the device, the index built, the mesh laid out, every batch shape warmed
up (compiling, or loading from the compile cache), the server and the
load generator's connections up."""


def read(run):
    return float(run.setup_s)
