"""Model step: the walk a token leaves a looped model at, sum t p_t, the
mean over the tokens of the step's batch: the program's own gauge
`bps_exit_expected_steps`, set from the batch of the run's reference
check, which is the step's (`models/ouro.py` `record_exit`).  1.875 where
every gate of four walks reads 0.5; 1.0 where the gate has collapsed onto
the first walk and the others train nothing.  A program without the gauge
reads nothing.  Source: program counter."""


def read(ctx):
    import byteps_tpu as bps
    return bps.get_metrics().get("bps_exit_expected_steps") or None
