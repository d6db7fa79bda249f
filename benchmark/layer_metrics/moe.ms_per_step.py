"""Expert layer: milliseconds of a step in the instructions of the expert
layers that can be told from the trace: scores, top-k and sort, the gather
into the buffer, the grouped products, the weighting and the scatter back,
the shared expert's up-projections (`benchmark/reduce/afmoe_cost.py` says
how each is found, and that the shared expert's down-projection is not).
Source: device trace."""

from benchmark.reduce import afmoe_cost, xplane


def read(ctx):
    if not any(afmoe_cost.is_grouped(n) for n, _, _ in ctx.ops(0)):
        return None         # no expert layer ran: shapes alone prove nothing
    cfg = ctx.family.cfg
    tokens = ctx.samples_per_step * ctx.family.seq_len // ctx.n_chips
    spans = [e - s for n, s, e in xplane.leaves(ctx.ops(0))
             if afmoe_cost.is_expert_layer(
                 n, tokens, cfg.num_experts_per_tok, cfg.num_experts,
                 (cfg.moe.buffer_rows(tokens), cfg.moe.past_rows(tokens)),
                 cfg.moe_intermediate_size)]
    if not spans:
        return None
    return sum(spans) / ctx.n_steps / 1e6
