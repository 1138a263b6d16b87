"""Admission: mean time from a request's admission to the start of the
tick that executes it, the program's ``serve.admit_wait_ms`` histogram,
over the window's ticks (``benchlib.program``).  A client's back-off after
a refusal comes before admission and is not in it.  None where the
program has no such histogram."""
from benchlib import program


def read(run):
    return program.mean(run, "serve.admit_wait_ms")
