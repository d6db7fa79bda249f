"""Sparse attention: the least time the chip could take to score every
causal pair ONCE a call (`benchmark/reduce/sparse_cost.py` `index_cost`:
2 x pairs x 16 heads x 64) over the time the selection kernel
`index_topk` took, in percent: how near finding the selection is to
computing the scores it is found from.  Source: device trace."""

from benchmark.reduce import flash_cost, sparse_cost


def read(ctx):
    spans = sparse_cost.kernel_spans(ctx.ops(0)).get("select")
    if not spans:
        return None
    cfg = ctx.family.cfg
    least = flash_cost.least_seconds(
        *sparse_cost.index_cost(ctx.family.seq_len, cfg.index_heads,
                                cfg.index_head_dim), ctx.peaks)[0]
    sequences = ctx.samples_per_step // ctx.n_chips
    return 100.0 * least * sequences * len(spans) / (sum(spans) / 1e9)
