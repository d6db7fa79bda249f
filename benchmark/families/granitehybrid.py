"""The granitehybrid family: a `granitemoehybrid` `config.json` (IBM's
Granite 4.0-H models, the members without routed experts) run through the
program's `byteps_tpu.models.granite_hybrid` as ONE PIPELINE STAGE of a
deployment, with the plain reference of
`benchmark/reference/granitehybrid.py` beside it, told the same stage.
See `benchmark/families/gpt2.py` for what a family is.

The configuration's `published` group holds the model's numbers as
published and `held` what this chip holds of them: which of the model's
layers and which slice of the tied vocabulary.  The model is built from
the first with the second on top.

A fourth number in `correct`.  The cell's three limits (loss, worst leaf,
norm ratio) cannot tell a scan whose carried state is bfloat16 from the
program, whose products are bfloat16 already (3.03% against 2.98% on the
chip).  So `reference_loss` also feeds the program's scan, alone, float32
operands and compares what the CARRIED STATE gives with the reference's
recurrence (`scan_disagreement`): the operands are the first mamba
layer's on the cell's own sequence, as the reference computes them, with x
set to zero after the first chunk, so that every later position's result
is state handed from chunk to chunk and nothing else.  Head by head, the
norm of the difference over the norm of the recurrence's result; the
number is the MEDIAN over the heads whose state lives that long.  On the
chip the sound program reads 1e-5 there and 1e-4 to 4e-4 in its worst
head (the chip's float32 `exp`, in every form of the scan and in the
reference alike; 1e-6 on the CPU), and a state rounded to bfloat16 once
a chunk reads 1e-3 in EVERY head: the median tells them a hundred times
apart where the whole result's norm tells them by six (PERF.md, Findings,
PR 34).  The harness's comparison has three numbers and is not this PR's
to edit, so the fourth reaches `correct` the way the afmoe family's
unexplained choices do: a reading over `reference_check.scan_rel_tol`
adds 1 to the reference's loss, which then fails `loss_rel_tol`.
Nothing is recorded on the way: a host callback in the reference's
program would keep it out of the compile cache and cost every run a
minute of set-up (it did: 113 s against 51, chip, PR 34).
`tools/reference_check.py` prints the number by calling
`scan_disagreement` itself.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax import lax

from benchmark.reduce import ssd_cost
from benchmark.reference import granitehybrid as reference
from byteps_tpu.models import granite_hybrid
from byteps_tpu.ops import ssd


def matmul_params_per_token(n: dict, layer_types, held_vocab: int) -> float:
    """Parameters of the matrices a token is multiplied by, on this chip:
    the shared MLP's two in every layer, a mamba layer's `in_proj` and
    `out_proj`, an attention layer's four projections, and the held rows
    of the tied head.  The embedding is a lookup; the convolution, the
    norms and the scan's own leaves are no matrices."""
    D = n["hidden_size"]
    mlp = 3 * D * n["shared_intermediate_size"]
    inner = n["mamba_n_heads"] * n["mamba_d_head"]
    conv_dim = inner + 2 * n["mamba_n_groups"] * n["mamba_d_state"]
    mamba = D * (inner + conv_dim + n["mamba_n_heads"]) + inner * D
    size = D // n["num_attention_heads"]
    attn = (D * (n["num_attention_heads"] + 2 * n["num_key_value_heads"])
            * size + n["num_attention_heads"] * size * D)
    n_mamba = sum(t == granite_hybrid.MAMBA for t in layer_types)
    return (len(layer_types) * mlp + n_mamba * mamba
            + (len(layer_types) - n_mamba) * attn + held_vocab * D)


class Family:
    unit = "tokens"

    def __init__(self, config: dict, job: dict):
        n = {**config["published"], **config["held"]}
        self.numbers = n
        options = config["program_options"]["pinned"]
        self.seq_len = int(job["seq_len"])
        if self.seq_len > n["max_position_embeddings"]:
            raise ValueError(f"seq_len {self.seq_len} is beyond the model's "
                             f"{n['max_position_embeddings']} positions")
        if n["num_local_experts"] or n["num_experts_per_tok"]:
            raise ValueError("granitehybrid family: routed experts are not "
                             "written here")
        self.layer_types = tuple(
            config["published"]["layer_types"][i] for i in n["layers"])
        if len(n["layers"]) != n["num_hidden_layers"]:
            raise ValueError("the configuration's `held` count disagrees "
                             "with its list of layers")
        size = n["hidden_size"] // n["num_attention_heads"]
        self.cfg = granite_hybrid.GraniteHybridConfig(
            vocab_size=n["vocab_size"], vocab_start=n["vocab_start"],
            hidden_size=n["hidden_size"], layer_types=self.layer_types,
            intermediate_size=n["shared_intermediate_size"],
            num_heads=n["num_attention_heads"],
            num_kv_heads=n["num_key_value_heads"], head_dim=size,
            mamba_n_heads=n["mamba_n_heads"], mamba_d_head=n["mamba_d_head"],
            mamba_d_state=n["mamba_d_state"],
            mamba_n_groups=n["mamba_n_groups"],
            mamba_d_conv=n["mamba_d_conv"],
            mamba_chunk_size=n["mamba_chunk_size"],
            embedding_multiplier=n["embedding_multiplier"],
            residual_multiplier=n["residual_multiplier"],
            attention_multiplier=n["attention_multiplier"],
            logits_scaling=n["logits_scaling"],
            rms_norm_eps=n["rms_norm_eps"], **options)
        self.reference_check = config["reference_check"]
        self.spec = {
            "layer_types": self.layer_types,
            "heads": n["num_attention_heads"],
            "kv_heads": n["num_key_value_heads"], "head_dim": size,
            "mamba_heads": n["mamba_n_heads"],
            "mamba_head_dim": n["mamba_d_head"],
            "mamba_state": n["mamba_d_state"],
            "mamba_groups": n["mamba_n_groups"],
            "embedding_multiplier": n["embedding_multiplier"],
            "residual_multiplier": n["residual_multiplier"],
            "attention_multiplier": n["attention_multiplier"],
            "logits_scaling": n["logits_scaling"],
            "eps": n["rms_norm_eps"], "vocab_start": n["vocab_start"],
            **self.reference_check["reference_blocks"]}
        self.units_per_sample = self.seq_len
        opt = job["optimizer"]
        if opt["name"] != "adamw":
            raise ValueError(f"granitehybrid family: no optimizer "
                             f"{opt['name']!r}")
        self._learning_rate = float(opt["learning_rate"])

    def optimizer(self) -> optax.GradientTransformation:
        return optax.adamw(self._learning_rate)

    def init(self, key):
        return granite_hybrid.init_params(key, self.cfg)

    def make_batch(self, key, n_samples: int):
        return granite_hybrid.synthetic_batch(key, n_samples, self.seq_len,
                                              self.cfg)

    def loss(self, params, batch):
        return granite_hybrid.loss_fn(params, batch, self.cfg)

    def scan_disagreement(self, params, tokens):
        """The program's scan on float32 operands against the reference's
        recurrence, in what the carried state alone gives (the module's
        docstring): the median over the heads of a head's difference over
        its size, at the positions after the first chunk, x being zero
        there.  Heads whose state is gone by then (under a thousandth of
        the largest) have nothing to compare and are left out; a sequence
        of one chunk carries nothing and reads 0."""
        x, dt, a, bm, cm, d = reference.first_scan_operands(
            params, tokens, self.spec)
        chunk = self.scan_shape()["chunk"]
        if x.shape[1] <= chunk:
            return jnp.zeros((), jnp.float32)
        x = x.at[:, chunk:].set(0.0)
        no_d = jnp.zeros_like(d)
        # `highest` for the reference and for the `jnp` form's products;
        # the kernels' float32 products read the same on the chip
        with jax.default_matmul_precision("highest"):
            want = reference.recurrence(
                x, dt, a, bm, cm, no_d, self.spec["scan_segment"])[:, chunk:]
            got = ssd.ssd_scan(x, dt, a, bm, cm, no_d, chunk=chunk)[:, chunk:]

        def per_head(t):                      # [B, S, H, P] -> [H]
            return jnp.sqrt((t * t).sum((0, 1, 3)))
        size = per_head(want)
        live = size >= 1e-3 * size.max()
        return jnp.nanmedian(jnp.where(live, per_head(got - want) / size,
                                       jnp.nan))

    def reference_loss(self, params, batch):
        """The reference's loss, plus 1 where the program's scan alone
        disagrees with the recurrence in float32 about what the carried
        state gives (the module's docstring)."""
        value = reference.loss(params, batch, self.spec)
        if not self.cfg.count(granite_hybrid.MAMBA):
            return value
        scan = self.scan_disagreement(lax.stop_gradient(params), batch[0])
        sound = scan <= self.reference_check["scan_rel_tol"]
        return value + lax.stop_gradient(
            jnp.where(sound, 0.0, 1.0).astype(value.dtype))

    def scan_shape(self) -> dict:
        """What `benchmark/reduce/ssd_cost.py` needs of one sequence's
        scan."""
        c = self.cfg
        return dict(tokens=self.seq_len, heads=c.mamba_n_heads,
                    head_dim=c.mamba_d_head, state=c.mamba_d_state,
                    groups=c.mamba_n_groups,
                    chunk=min(c.mamba_chunk_size, self.seq_len))

    def model_flops_per_sample(self) -> float:
        """Model FLOPs to train on one sequence, forward and backward, no
        recompute: 6 per matmul parameter a token meets on this chip
        (`matmul_params_per_token`); the scan's products at what the
        chunked form needs, forward and backward without the recomputed
        part (`ssd_cost.model_flops`), a mamba layer; attention's two
        matmuls over the causal triangle, 2 FLOPs a multiply-add, three
        passes, an attention layer."""
        n = self.numbers
        params = matmul_params_per_token(n, self.layer_types,
                                         n["vocab_size"])
        n_mamba = self.cfg.count(granite_hybrid.MAMBA)
        pairs = self.seq_len * (self.seq_len + 1) // 2
        return (6.0 * params * self.seq_len
                + n_mamba * ssd_cost.model_flops(**self.scan_shape())
                + (len(self.layer_types) - n_mamba) * 12.0 * pairs
                * n["hidden_size"])
