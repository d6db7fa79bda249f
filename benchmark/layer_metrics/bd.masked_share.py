"""Model step: of the tokens of the step's batch, the percentage the noise
masked (a block's t uniform on [eps, 1): 50% in expectation): the
program's own gauge `bps_bd_masked_share`, set from the batch of the run's
reference check, which is the step's (`models/sdar.py` `record_batch`).
A program without the gauge reads nothing.  Source: program counter."""


def read(ctx):
    import byteps_tpu as bps
    share = bps.get_metrics().get("bps_bd_masked_share")
    return 100.0 * share if share else None
