"""Fleet tick: mean host time of the program's ``serve.tick`` span over
the window's ticks (``benchlib.program``): the engine's execution of one
batch, its fleet query included; completing the tickets' answers comes
after the span."""
from benchlib import program


def read(run):
    return program.mean(run, "span.serve.tick")
