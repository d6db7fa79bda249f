"""Model step: megabytes of layer inputs that a looped model's backward
pass keeps from its forward pass under whole-layer remat, applications x
rows x hidden x the activations' item size: the program's own gauge
`bps_loop_kept_bytes`, set when the step is traced (`models/ouro.py`
`loop_counters`).  A program without the gauge reads nothing.  Source:
program counter."""


def read(ctx):
    import byteps_tpu as bps
    kept = bps.get_metrics().get("bps_loop_kept_bytes")
    return kept / 1e6 if kept else None
