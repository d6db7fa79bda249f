"""Sparse attention: the least time the chip could take for the attention
calls of the window over the time they took, in percent.  A call's work
is that of the pairs its rows SELECT (`benchmark/reduce/sparse_cost.py`
`attention_cost`), whatever the kernel walks to reach them: a mask over
dense tiles reads low, a gather can raise it.  Source: device trace."""

from benchmark.reduce import flash_cost, sparse_cost


def read(ctx):
    spans = sparse_cost.kernel_spans(ctx.ops(0))
    cfg = ctx.family.cfg
    sequences = ctx.samples_per_step // ctx.n_chips
    least = took = 0.0
    for kind in ("forward", "dq", "dkv"):
        calls = spans.get(kind, ())
        if not calls:
            continue
        one = flash_cost.least_seconds(
            *sparse_cost.attention_cost(
                kind, ctx.family.seq_len, cfg.index_topk, cfg.num_heads,
                cfg.num_kv_heads, cfg.head_dim), ctx.peaks)[0]
        least += one * sequences * len(calls)
        took += sum(calls) / 1e9
    return 100.0 * least / took if took else None
