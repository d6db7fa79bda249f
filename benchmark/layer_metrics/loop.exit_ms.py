"""Model step: milliseconds of a step under `ouro.exit`, every pass: the
final norm at the end of each of a looped model's walks, the exit gate of
each, the exit distribution and its entropy (`models/ouro.py`).  From the
program's map of its step (`benchmark/reduce/scopes.py`).  Source: program
span."""

from benchmark.reduce import scopes


def read(ctx):
    return scopes.scope_ms(ctx, r"^ouro\.exit$")
