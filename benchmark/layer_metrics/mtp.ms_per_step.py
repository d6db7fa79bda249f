"""Model step: milliseconds of a step under `joyai.mtp`, every pass and
every child: the prediction module's two norms and its projection, its
layer (latent attention, router, shared and held experts: the scopes the
main stack's layers open, here under this one), its final norm and the
SECOND streamed cross-entropy over the held head slice.  From the
program's map of its step (`benchmark/reduce/scopes.py`).  Source: program
span."""

from benchmark.reduce import scopes


def read(ctx):
    return scopes.scope_ms(ctx, r"^joyai\.mtp$")
