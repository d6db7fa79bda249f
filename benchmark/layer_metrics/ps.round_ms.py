"""PS staging: milliseconds of one `bps.push_pull_tree` round, by the
job's own host timer around the call (gradients ready before, pulled tree
ready after), over the traced steps.  Source: host clock."""


def read(ctx):
    rounds = ctx.extras.get("ps_round_s", [])[
        ctx.first_step:ctx.first_step + ctx.n_steps]
    if not rounds:
        return None
    return 1e3 * sum(rounds) / len(rounds)
