"""Device, host side: mean host time per tick of the mesh call's argument
put and launch (``fleet.mesh.put``) and its copy of the outputs to numpy
(``fleet.mesh.fetch``), the program's spans, over the window's ticks
(``benchlib.program``).  The wait for the device (``fleet.mesh.wait``)
is left out.  None where the program has no such spans."""
from benchlib import program


def read(run):
    return program.mean(run, "span.fleet.mesh.put", "span.fleet.mesh.fetch",
                        per="span.serve.tick")
