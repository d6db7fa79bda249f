"""The real cells cut to sizes a CPU can run: the same files, read the
same way, with widths and batch overridden in memory."""

import copy
import dataclasses

from benchmark.harness import manifest

_TINY = {
    "gpt2": {
        "published": dict(n_layer=2, n_embd=64, n_head=4, n_inner=256,
                          vocab_size=512, n_positions=128),
        "job": dict(per_chip_batch=2, seq_len=128),
        "pinned": dict(ce_chunk_rows=64),
        "tolerances": dict(grad_rel_tol=0.03, grad_norm_tol=0.02),
    },
    "vgg": {
        "published": dict(image_size=32, num_classes=10),
        "job": dict(per_chip_batch=4),
        "pinned": {},
        "tolerances": {},
    },
}


def tiny_config(config: dict) -> dict:
    config = copy.deepcopy(config)
    cut = _TINY[config["family"]]
    config["published"].update(cut["published"])
    config["job"].update(cut["job"])
    config["program_options"].get("pinned", {}).update(cut["pinned"])
    # Few elements average less: at these widths bfloat16 moves the loss
    # by 1.2e-4 and a leaf by up to 1.7% (gpt2) or 35% (vgg).
    config["reference_check"].update(samples=2, loss_rel_tol=1e-3,
                                     **cut["tolerances"])
    return config


def tiny_cell(name: str) -> manifest.Cell:
    cell = manifest.load_cell(name)
    config = tiny_config(cell.config)
    return dataclasses.replace(cell, config=config,
                               job={**cell.job, **config["job"]})
