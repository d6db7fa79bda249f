"""The gpt2 family: a GPT-2 `config.json` run through the program's
transformer (`byteps_tpu.models.transformer`), with the plain reference
of `benchmark/reference/gpt2.py` beside it.

A family is what a job needs to know of a model: how to make weights and
a batch on the device from a key, the loss the program trains, the
optimizer of the configuration's job, what one sample counts as, the
model FLOPs of one sample, and the reference loss.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

from benchmark.reference import gpt2 as reference
from byteps_tpu.models import transformer as tfm


def model_flops_per_token(n_layer: int, n_embd: int, n_inner: int,
                          vocab_size: int, seq_len: int) -> float:
    """Model FLOPs to train on one token, forward and backward, recompute
    not counted (PaLM's convention, arXiv:2204.02311 appendix B): 6 per
    matmul parameter (qkv, attention output, the two MLP matrices, the tied
    head) plus 12 * n_layer * seq_len * n_embd for the score and value
    matmuls of attention over the full S x S square, causal or not.

    Copied from `transformer.flops_per_token`, with the sequence length of
    the cell in place of the model's `max_seq_len`."""
    matmul_params = (n_layer * (4 * n_embd * n_embd + 2 * n_embd * n_inner)
                     + vocab_size * n_embd)
    return 6.0 * matmul_params + 12.0 * n_layer * seq_len * n_embd


class Family:
    unit = "tokens"
    causal_attention = True
    def __init__(self, config: dict, job: dict):
        pub = config["published"]
        options = {**config["program_options"]["pinned"],
                   **config["program_options"]["left_at_rule"]}
        self.seq_len = int(job["seq_len"])
        if self.seq_len > pub["n_positions"]:
            raise ValueError(f"seq_len {self.seq_len} is beyond the model's "
                             f"{pub['n_positions']} positions")
        self.pub = pub
        self.cfg = tfm.TransformerConfig(
            vocab_size=pub["vocab_size"], num_layers=pub["n_layer"],
            d_model=pub["n_embd"], num_heads=pub["n_head"],
            d_ff=pub["n_inner"], max_seq_len=pub["n_positions"],
            causal=True, norm="layernorm", act="gelu", pos="learned",
            use_bias=True, **options)
        self.units_per_sample = self.seq_len
        # how many samples the reference check takes and how far the
        # program may be from the reference, with the reason, are the
        # configuration's own
        self.reference_check = config["reference_check"]
        opt = job["optimizer"]
        if opt["name"] != "adamw":
            raise ValueError(f"gpt2 family: no optimizer {opt['name']!r}")
        self._learning_rate = float(opt["learning_rate"])

    def optimizer(self) -> optax.GradientTransformation:
        return optax.adamw(self._learning_rate)

    def init(self, key):
        return tfm.init_params(key, self.cfg)

    def make_batch(self, key, n_samples: int):
        return tfm.synthetic_batch(key, n_samples, self.seq_len, self.cfg)

    def loss(self, params, batch):
        return tfm.loss_fn(params, batch, self.cfg)

    def reference_loss(self, params, batch):
        return reference.loss(params, batch, self.pub["n_head"])

    def model_flops_per_sample(self) -> float:
        p = self.pub
        return self.seq_len * model_flops_per_token(
            p["n_layer"], p["n_embd"], p["n_inner"], p["vocab_size"],
            self.seq_len)
