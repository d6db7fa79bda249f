"""Model step: the entropy of a looped model's exit distribution in nats,
the mean over the tokens of the step's batch (at most ln of the walks):
the program's own gauge `bps_exit_entropy`, set from the batch of the
run's reference check, which is the step's (`models/ouro.py`
`record_exit`).  A program without the gauge reads nothing.  Source:
program counter."""


def read(ctx):
    import byteps_tpu as bps
    return bps.get_metrics().get("bps_exit_entropy") or None
