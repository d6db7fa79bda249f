"""Kernels: of the (row, key) pairs in the tiles that a flash-attention
call under a block-diffusion mask computes, the percentage the mask needs,
L^2 + L beta a head: the program's own gauge
`bps_flash_bd_pairs_needed_share`, written when the call is traced
(`ops/flash_attention.py` `stream_schedule`, from the table its kernels
walk; 94.1% at 2 L = 32,768 in tiles of 512).  A program without the
gauge reads nothing.  Source: program counter."""


def read(ctx):
    import byteps_tpu as bps
    share = bps.get_metrics().get("bps_flash_bd_pairs_needed_share")
    return 100.0 * share if share else None
