"""Expert layer: the least time the chip could take for the grouped
products of the window over the time they (and their metadata kernels)
took, in percent.  The rows are those the routing NEEDS under an even
deployment, tokens * k * held / experts, and never the padded buffer's
(`benchmark/reduce/afmoe_cost.py`).  Source: device trace."""

from benchmark.reduce import afmoe_cost, flash_cost


def read(ctx):
    cfg = ctx.family.cfg
    tokens = ctx.samples_per_step * ctx.family.seq_len // ctx.n_chips
    rows = tokens * cfg.num_experts_per_tok * len(cfg.held) / cfg.num_experts
    least = took = 0.0
    for name, start, end in ctx.ops(0):
        if not afmoe_cost.is_grouped(name):
            continue
        took += (end - start) / 1e9
        call = afmoe_cost.grouped_call(name)
        if call is not None:
            least += flash_cost.least_seconds(
                *afmoe_cost.grouped_cost(rows, *call), ctx.peaks)[0]
    return 100.0 * least / took if took and least else None
