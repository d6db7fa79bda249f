"""The afmoe family: an `afmoe` `config.json` (Arcee's Trinity models) run
through the program's `byteps_tpu.models.afmoe` as ONE CHIP'S SHARE of an
expert-parallel deployment, with the plain reference of
`benchmark/reference/afmoe.py` beside it, told the same share.  See
`benchmark/families/gpt2.py` for what a family is.

The configuration's `published` group holds the model's numbers as
published and `held` what this chip holds of them: which of the model's
layers, which experts of each layer, which slice of the vocabulary.  The
model is built from the first with the second on top.

How `correct` is decided (`benchmark/harness/correct.py` compares
`loss` with `reference_loss`, unedited).  Top-k is discontinuous: the
program's bfloat16 activations move a router's scores a little, some
tokens then choose one expert differently than the float32 reference
would, and each such row moves an expert's gradient far more than rounding
does.  So `reference_loss` separates the two:

  - the arithmetic: the reference computes its own scores and weights, in
    float32, but for the experts THE PROGRAM chose (the program's own
    routing code run on the sample, `models.afmoe.routing`).  Loss and
    every gradient leaf are then held to the configuration's tolerances,
    which are of bfloat16's size;
  - the choice: the reference also takes its own top-k, and for every
    token whose set differs, the gap between the best score the program
    left out and the worst it took instead.  A gap of `selection_eps` or
    more is a choice that rounding does not explain; the number of such
    tokens is ADDED to the reference's loss, which so leaves the loss
    tolerance by orders of magnitude and fails the check.

`selection` keeps what the reference saw, a record a sample, and
`routing_counters` the program's own counters: `tools/afmoe_check.py`
prints both, and the `route.*` readers in `benchmark/layer_metrics/`
report them from a run's own reference check.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax import lax

from benchmark.reduce.afmoe_cost import window_pairs
from benchmark.reference import afmoe as reference
from byteps_tpu.models import afmoe
from byteps_tpu.parallel import dropless_moe


def matmul_params_per_token(n: dict, layer_types, dense_layers: int,
                            held_experts: int, held_vocab: int) -> float:
    """Parameters of the matrices a token is multiplied by, on this chip:
    attention's four projections and the output's in every layer, the
    dense SwiGLU or the router, the shared expert and the routed experts a
    token meets HERE (its `num_experts_per_tok` choices fall on the held
    experts in proportion, one expert's worth in an even deployment), and
    the held rows of the head.  The embedding is a lookup."""
    D, size = n["hidden_size"], n["head_dim"]
    H, Hkv = n["num_attention_heads"], n["num_key_value_heads"]
    attn = D * (2 * H + 2 * Hkv) * size + H * size * D
    expert = 3 * D * n["moe_intermediate_size"]
    routed = n["num_experts_per_tok"] * held_experts / n["num_experts"]
    moe = D * n["num_experts"] + expert * (n["num_shared_experts"] + routed)
    dense = 3 * D * n["intermediate_size"]
    layers = len(layer_types)
    return (layers * attn + dense_layers * dense
            + (layers - dense_layers) * moe + held_vocab * D)


class Family:
    unit = "tokens"

    def __init__(self, config: dict, job: dict):
        n = {**config["published"], **config["held"]}
        self.numbers = n
        options = {**config["program_options"]["pinned"],
                   **config["program_options"]["left_at_rule"]}
        self.seq_len = int(job["seq_len"])
        if self.seq_len > n["max_position_embeddings"]:
            raise ValueError(f"seq_len {self.seq_len} is beyond the model's "
                             f"{n['max_position_embeddings']} positions")
        published_types = config["published"]["layer_types"]
        self.layer_types = tuple(published_types[i] for i in n["layers"])
        dense = sum(i < config["published"]["num_dense_layers"]
                    for i in n["layers"])
        if (dense != n["num_dense_layers"]
                or len(n["layers"]) != n["num_hidden_layers"]
                or len(n["experts"]) != n["num_experts"]):
            raise ValueError("the configuration's `held` counts disagree "
                             "with its lists")
        self.cfg = afmoe.AfmoeConfig(
            vocab_size=n["vocab_size"], vocab_start=n["vocab_start"],
            hidden_size=n["hidden_size"],
            num_heads=n["num_attention_heads"],
            num_kv_heads=n["num_key_value_heads"], head_dim=n["head_dim"],
            intermediate_size=n["intermediate_size"],
            moe_intermediate_size=n["moe_intermediate_size"],
            num_experts=config["published"]["num_experts"],
            num_experts_per_tok=n["num_experts_per_tok"],
            held_experts=tuple(n["experts"]),
            layer_types=self.layer_types, num_dense_layers=dense,
            sliding_window=n["sliding_window"],
            num_shared_experts=n["num_shared_experts"],
            route_scale=n["route_scale"], route_norm=n["route_norm"],
            score_func=n["score_func"], rms_norm_eps=n["rms_norm_eps"],
            rope_theta=float(n["rope_theta"]), mup_enabled=n["mup_enabled"],
            **options)
        self.spec = {
            "heads": n["num_attention_heads"],
            "kv_heads": n["num_key_value_heads"], "head_dim": n["head_dim"],
            "window": n["sliding_window"], "layer_types": self.layer_types,
            "dense_layers": dense, "top_k": n["num_experts_per_tok"],
            "held": tuple(n["experts"]), "route_scale": n["route_scale"],
            "eps": n["rms_norm_eps"], "theta": float(n["rope_theta"]),
            "vocab_start": n["vocab_start"], "q_block": 512,
            "ce_block": 2048}
        self.units_per_sample = self.seq_len
        self.reference_check = config["reference_check"]
        self.selection_eps = float(config["reference_check"]["selection_eps"])
        self.selection, self.routing_counters = [], []
        opt = job["optimizer"]
        if opt["name"] != "adamw":
            raise ValueError(f"afmoe family: no optimizer {opt['name']!r}")
        self._learning_rate = float(opt["learning_rate"])

    def optimizer(self) -> optax.GradientTransformation:
        return optax.adamw(self._learning_rate)

    def init(self, key):
        return afmoe.init_params(key, self.cfg)

    def make_batch(self, key, n_samples: int):
        return afmoe.synthetic_batch(key, n_samples, self.seq_len, self.cfg)

    def loss(self, params, batch):
        return afmoe.loss_fn(params, batch, self.cfg)

    def _record(self, selection, counters):
        self.selection.append(jax.tree.map(float, selection))
        self.routing_counters.append(
            jax.tree.map(lambda a: [float(x) for x in a], counters))

    def reference_loss(self, params, batch):
        """The reference's loss at the program's choice of experts, plus
        the number of tokens whose choice rounding does not explain (see
        the module's docstring)."""
        tokens = batch[0]
        if self.cfg.num_dense_layers == len(self.layer_types):
            return reference.loss(params, batch, self.spec)
        routing = afmoe.routing(lax.stop_gradient(params), tokens, self.cfg)
        value, stats = reference.loss(params, batch, self.spec,
                                      sel=routing.sel, with_stats=True)
        gaps = jnp.stack([s["gaps"] for s in stats])      # [layers, T]
        unexplained = (gaps >= self.selection_eps).sum()
        selection = {
            "tokens": tokens.size * len(stats),
            "swapped_share": jnp.stack(
                [s["swapped_tokens"] for s in stats]).sum()
            / (tokens.size * len(stats)),
            "max_gap": gaps.max(), "unexplained_tokens": unexplained}
        counters = jax.vmap(
            lambda r: dropless_moe.counters(r, tokens.size))(routing)
        jax.debug.callback(self._record, selection, counters)
        return value + lax.stop_gradient(unexplained.astype(jnp.float32))

    def model_flops_per_sample(self) -> float:
        """Model FLOPs to train on one sequence, forward and backward, no
        recompute: 6 per matmul parameter a token meets on this chip
        (`matmul_params_per_token`), plus attention's two matmuls over the
        (query, key) pairs each kind of layer NEEDS, the causal triangle
        in a full layer and the window's band in a sliding one: 2 FLOPs a
        multiply-add, two matmuls, three passes."""
        n = self.numbers
        params = matmul_params_per_token(
            n | {"num_experts": self.cfg.num_experts}, self.layer_types,
            self.cfg.num_dense_layers, len(self.cfg.held), n["vocab_size"])
        width = n["num_attention_heads"] * n["head_dim"]
        pairs = sum(window_pairs(
            self.seq_len, n["sliding_window"] if t == afmoe.SLIDING else None)
            for t in self.layer_types)
        return 6.0 * params * self.seq_len + 12.0 * pairs * width
