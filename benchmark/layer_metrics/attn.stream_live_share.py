"""Kernels: of the grid steps the STREAMING flash-attention calls of a
step walk, the percentage that compute a tile: 100 means every grid is
the band its mask leaves.  Summed over the step's layers, each layer's
calls counted by one head's forward grid: the program's own gauges
`bps_flash_stream_live` / `bps_flash_stream_steps`
(`ops/flash_attention.py` `stream_schedule`, from the bounds its kernels
use), one set a window (label `window`, "none" without one), and the
family's `layer_types` say which set a layer's calls are.  The mellum
cell (S = 32,768 in tiles of 512): a sliding layer walks 192 steps and
computes in 189, the full layer walks 4,096 (its grid is still the
square) and computes in 2,080, so three sliding layers and one full give
(3 x 189 + 2,080) / (3 x 192 + 4,096) = 56.7%; a grid over the triangle
alone would read 99.7%.  It does not depend on the order the layers are
traced in.  A program without the labelled gauges, a family without
`layer_types`, or a cell whose calls are all resident, reads nothing.
Source: program counter."""


def read(ctx):
    import byteps_tpu as bps
    cfg = getattr(ctx.family, "cfg", None)
    kinds = getattr(cfg, "layer_types", None)
    if not kinds:
        return None
    metrics = bps.get_metrics()
    steps = live = 0
    for kind in kinds:
        window = cfg.sliding_window if kind == "sliding_attention" else None
        if window is not None and window >= ctx.family.seq_len:
            window = None           # the program's own rule (`_attn_fn`)
        label = '{window="%s"}' % ("none" if window is None else window)
        walked = metrics.get("bps_flash_stream_steps" + label)
        if not walked:
            return None
        steps += walked
        live += metrics.get("bps_flash_stream_live" + label, 0)
    return 100.0 * live / steps
