"""Routing: mean host time of the router's call, the program's
``fleet.route`` span, over the window's ticks (``benchlib.program``).
None where the program has no such span."""
from benchlib import program


def read(run):
    return program.mean(run, "span.fleet.route")
