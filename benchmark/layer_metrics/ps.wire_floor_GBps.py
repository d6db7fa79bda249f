"""PS wire: gigabytes a second this host moves between the worker and
another process over the session's kind and number of lanes, frames of
its partition size, both ways at once and with no protocol: `duplex` of
the `wire_floor.json` the worker leaves at shutdown.  A program that
leaves none reads nothing.  Source: host clock, in the program."""

from benchmark.reduce import wire_counts


def read(ctx):
    floor = wire_counts.floor(ctx.dir)
    return floor and floor["duplex"]["GB_per_s"]
