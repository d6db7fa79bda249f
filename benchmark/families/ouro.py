"""The ouro family: an `ouro` `config.json` (ByteDance's Ouro, a looped
decoder) run through the program's `byteps_tpu.models.ouro` as ONE
PIPELINE STAGE's layers with the embedding, the final norm, the exit gate
and the head beside them, walked `total_ut_steps` times, with the plain
reference of `benchmark/reference/ouro.py` beside it.  See
`benchmark/families/gpt2.py` for what a family is.

A dense model has no discontinuous choice, so `correct` is the loss and
every gradient leaf of the whole step against the reference, and two
parts compared ALONE, which make the limits mean something where the
whole step's bfloat16 hides a lower precision in a small float32 part:

  - `exit_abs_diff`: the program's exit distribution (`ouro.exit_gate`,
    `ouro.exit_distribution`) against the reference's on the SAME normed
    states `h_t`, the program's own: float32 against float32, the
    largest difference of a `p_t` over walks and tokens.
  - `nll_rms_diff`: the program's head (the streamed cross-entropy as
    the step runs it) against the reference's float32 head on the same
    `h_t`: the root mean square, over the walks' rows, of the difference
    of a row's NLL.

Each part over its limit ADDS 1 to the reference's loss, as the expert
families count a choice that rounding does not explain.  The same pass
sets the exit gauges (`ouro.record_exit`) from the batch of the check,
which is the step's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax import lax

from benchmark.reference import ouro as reference
from byteps_tpu.models import ouro


class Family:
    unit = "tokens"
    causal_attention = True

    def __init__(self, config: dict, job: dict):
        published = config["published"]
        n = {**published, **config["held"]}
        self.numbers = n
        assumed = config["assumed"]["numbers"]
        options = {**config["program_options"]["pinned"],
                   **config["program_options"]["left_at_rule"]}
        self.seq_len = int(job["seq_len"])
        if self.seq_len > n["max_position_embeddings"]:
            raise ValueError(f"seq_len {self.seq_len} is beyond the model's "
                             f"{n['max_position_embeddings']} positions")
        if (len(n["layers"]) != n["num_hidden_layers"]
                or any(t != "full_attention" for t in n["layer_types"])
                or n["rope_scaling"] is not None
                or n["tie_word_embeddings"] or n["hidden_act"] != "silu"):
            raise ValueError("the configuration's `held` count disagrees "
                             "with its list, or the model is not full "
                             "attention, plain rotary, SwiGLU and an "
                             "untied head")
        self.cfg = ouro.OuroConfig(
            vocab_size=n["vocab_size"], hidden_size=n["hidden_size"],
            num_heads=n["num_attention_heads"],
            num_kv_heads=n["num_key_value_heads"], head_dim=n["head_dim"],
            intermediate_size=n["intermediate_size"],
            num_layers=len(n["layers"]),
            total_ut_steps=n["total_ut_steps"],
            exit_entropy_beta=float(assumed["exit_entropy_beta"]),
            rms_norm_eps=n["rms_norm_eps"],
            rope_theta=float(n["rope_theta"]), **options)
        self.spec = {
            "heads": n["num_attention_heads"],
            "kv_heads": n["num_key_value_heads"], "head_dim": n["head_dim"],
            "eps": n["rms_norm_eps"], "theta": float(n["rope_theta"]),
            "walks": n["total_ut_steps"],
            "beta": float(assumed["exit_entropy_beta"]),
            "q_block": 512, "ce_block": 2048}
        self.units_per_sample = self.seq_len
        self.reference_check = config["reference_check"]
        self.exit_abs_tol = float(config["reference_check"]["exit_abs_tol"])
        self.nll_rms_tol = float(config["reference_check"]["nll_rms_tol"])
        # what the parts read, a dict a reference check (the name the
        # families' cases read: `tests/family_cases.py`)
        self.selection = []
        opt = job["optimizer"]
        if opt["name"] != "adamw":
            raise ValueError(f"ouro family: no optimizer {opt['name']!r}")
        self._learning_rate = float(opt["learning_rate"])

    def optimizer(self) -> optax.GradientTransformation:
        return optax.adamw(self._learning_rate)

    def init(self, key):
        return ouro.init_params(key, self.cfg)

    def make_batch(self, key, n_samples: int):
        return ouro.synthetic_batch(key, n_samples, self.seq_len, self.cfg)

    def loss(self, params, batch):
        return ouro.loss_fn(params, batch, self.cfg)

    def _record(self, parts, counters):
        self.selection.append(jax.tree.map(float, parts))
        ouro.record_exit(counters)

    def parts_disagreement(self, params, batch):
        """`(parts, counters)`: the two parts of the program ALONE, each
        against the reference's float32 on the program's own normed states
        of `batch` (the module's docstring says which and why), and the
        batch's exit statistics (`ouro.exit_counters`)."""
        tokens, targets = batch
        h, lam = ouro.walks(params, tokens, self.cfg)
        p = ouro.exit_distribution(lam)
        nll = ouro.nll_rows(params, h, targets, self.cfg)
        with jax.default_matmul_precision("highest"):
            plain = jax.tree.map(lambda a: a.astype(jnp.float32), params)
            want_p, want_nll = reference.exit_and_nll(
                plain, list(h.astype(jnp.float32)), targets, self.spec)
        # the variants may run another number of walks than the reference
        # would: both sides here read the walks the program made
        parts = {
            "exit_abs_diff": jnp.abs(p - jnp.stack(want_p)).max(),
            "nll_rms_diff": jnp.sqrt(jnp.mean(
                (nll - jnp.stack(want_nll)) ** 2))}
        return parts, ouro.exit_counters(p, nll)

    def reference_loss(self, params, batch):
        """The reference's loss, plus 1 for each part of the program that
        alone is further from float32 than its limit
        (`parts_disagreement`: the exit distribution `exit_abs_tol`, the
        head's rows `nll_rms_tol`)."""
        parts, counters = self.parts_disagreement(lax.stop_gradient(params),
                                                  batch)
        jax.debug.callback(self._record, parts, counters)
        off = ((parts["exit_abs_diff"] > self.exit_abs_tol).astype(
            jnp.float32) + (parts["nll_rms_diff"] > self.nll_rms_tol))
        return (reference.loss(params, batch, self.spec)
                + lax.stop_gradient(off))

    def model_flops_per_sample(self) -> float:
        """Model FLOPs to train on one sequence, forward and backward, no
        recompute (the mellum family's convention): 6 a matmul parameter
        a token MEETS, a layer's once a walk and the head's and the
        gate's once a walk too (the objective reads every walk's logits),
        plus attention's two matmuls over the (query, key) pairs the
        causal mask NEEDS, in every layer application: 2 FLOPs a
        multiply-add, two matmuls, three passes."""
        n, cfg, S = self.numbers, self.cfg, self.seq_len
        D, width = n["hidden_size"], cfg.num_heads * cfg.head_dim
        layer = (D * (cfg.num_heads + 2 * cfg.num_kv_heads) * cfg.head_dim
                 + width * D + 3 * D * n["intermediate_size"])
        walk = cfg.num_layers * layer + n["vocab_size"] * D + D
        applications = cfg.total_ut_steps * cfg.num_layers
        return (6.0 * cfg.total_ut_steps * walk * S
                + 12.0 * applications * (S * (S + 1) // 2) * width)
