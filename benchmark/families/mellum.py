"""The mellum family: a `mellum` `config.json` (JetBrains' Mellum 2) run
through the program's `byteps_tpu.models.mellum` as ONE CHIP'S SHARE of an
expert-parallel deployment, with the plain reference of
`benchmark/reference/mellum.py` beside it, told the same share.  See
`benchmark/families/gpt2.py` for what a family is and
`benchmark/families/afmoe.py` for how a share is written down (`published`
and `held`) and how `correct` is decided where top-k is discontinuous:
the reference computes its own scores and weights in float32 at the
experts THE PROGRAM chose, and every token whose choice differs from the
reference's own top-k by a gap of `selection_eps` or more ADDS 1 to the
reference's loss.  Here the gap is read in the router's logits (the
softmax's normaliser cancels within a token).

What the existing readers ask of a family is here under the names they
use: `cfg` (with `.moe`, `.held`, `.num_experts`, `.num_experts_per_tok`,
`.moe_intermediate_size`), `seq_len`, `routing_counters`, `selection`, and
the model FLOPs of a sample with attention counted at the pairs each
layer's mask leaves.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax import lax

from benchmark.reduce.afmoe_cost import window_pairs
from benchmark.reference import mellum as reference
from byteps_tpu.models import afmoe, mellum
from byteps_tpu.parallel import dropless_moe


def matmul_params_per_token(n: dict, layers: int, held_experts: int,
                            held_vocab: int) -> float:
    """Parameters of the matrices a token is multiplied by, on this chip:
    attention's three projections and the output's, the router, the routed
    experts a token meets HERE (its `num_experts_per_tok` choices fall on
    the held experts in proportion: two experts' worth where a quarter of
    them is held and a token takes 8), in every layer, and the held rows
    of the head.  The embedding is a lookup."""
    D, size = n["hidden_size"], n["head_dim"]
    H, Hkv = n["num_attention_heads"], n["num_key_value_heads"]
    attn = D * (H + 2 * Hkv) * size + H * size * D
    routed = n["num_experts_per_tok"] * held_experts / n["num_experts"]
    moe = D * n["num_experts"] + 3 * D * n["moe_intermediate_size"] * routed
    return layers * (attn + moe) + held_vocab * D


class Family:
    unit = "tokens"

    def __init__(self, config: dict, job: dict):
        published = config["published"]
        n = {**published, **config["held"]}
        self.numbers = n
        options = {**config["program_options"]["pinned"],
                   **config["program_options"]["left_at_rule"]}
        self.seq_len = int(job["seq_len"])
        if self.seq_len > n["max_position_embeddings"]:
            raise ValueError(f"seq_len {self.seq_len} is beyond the model's "
                             f"{n['max_position_embeddings']} positions")
        if (len(n["layers"]) != n["num_hidden_layers"]
                or len(n["experts"]) != n["num_experts"]
                or any(t != "sparse" for t in published["mlp_layer_types"])):
            raise ValueError("the configuration's `held` counts disagree "
                             "with its lists, or a layer is not sparse")
        self.layer_types = tuple(published["layer_types"][i]
                                 for i in n["layers"])
        rope = n["rope_parameters"]
        full, sliding = rope["full_attention"], rope["sliding_attention"]
        if (full["rope_type"], sliding["rope_type"]) != ("yarn", "default"):
            raise ValueError(f"rope_parameters {rope}: full layers under "
                             f"yarn and sliding ones plain is what is "
                             f"written here")
        theta = float(sliding["rope_theta"])
        if float(full["rope_theta"]) != theta:
            raise ValueError("one rope_theta for both kinds of layer")
        self.cfg = mellum.MellumConfig(
            vocab_size=n["vocab_size"], vocab_start=n["vocab_start"],
            hidden_size=n["hidden_size"],
            num_heads=n["num_attention_heads"],
            num_kv_heads=n["num_key_value_heads"], head_dim=n["head_dim"],
            moe_intermediate_size=n["moe_intermediate_size"],
            num_experts=published["num_experts"],
            num_experts_per_tok=n["num_experts_per_tok"],
            held_experts=tuple(n["experts"]), layer_types=self.layer_types,
            sliding_window=n["sliding_window"],
            norm_topk_prob=n["norm_topk_prob"],
            rms_norm_eps=n["rms_norm_eps"], rope_theta=theta,
            yarn=mellum.Yarn(
                factor=float(full["factor"]),
                original_positions=full["original_max_position_embeddings"],
                beta_fast=float(full["beta_fast"]),
                beta_slow=float(full["beta_slow"]),
                attention_factor=float(full["attention_factor"])),
            **options)
        self.spec = {
            "heads": n["num_attention_heads"],
            "kv_heads": n["num_key_value_heads"], "head_dim": n["head_dim"],
            "window": n["sliding_window"], "layer_types": self.layer_types,
            "top_k": n["num_experts_per_tok"], "held": tuple(n["experts"]),
            "norm_topk_prob": n["norm_topk_prob"], "eps": n["rms_norm_eps"],
            "theta": theta, "yarn": dict(full),
            "vocab_start": n["vocab_start"], "q_block": 64,
            "ce_block": 2048}
        self.units_per_sample = self.seq_len
        self.reference_check = config["reference_check"]
        self.selection_eps = float(config["reference_check"]["selection_eps"])
        self.router_rel_tol = float(
            config["reference_check"]["router_rel_tol"])
        self.experts_rel_tol = float(
            config["reference_check"]["experts_rel_tol"])
        self.attn_row_tol = float(config["reference_check"]["attn_row_tol"])
        self.selection, self.routing_counters = [], []
        opt = job["optimizer"]
        if opt["name"] != "adamw":
            raise ValueError(f"mellum family: no optimizer {opt['name']!r}")
        self._learning_rate = float(opt["learning_rate"])
        self._embed_rows_times = float(
            config["initial_weights"]["embed_rows_times"])

    def optimizer(self) -> optax.GradientTransformation:
        return optax.adamw(self._learning_rate)

    def init(self, key):
        """The program's own initial weights, the embedding's rows times
        the cell's `initial_weights.embed_rows_times` (the configuration
        says why: a random model's flat attention makes neighbouring
        tokens alike, and the routers then send stretches of the sequence
        to the same few experts, another few for every seed)."""
        params = mellum.init_params(key, self.cfg)
        params["embed"] = params["embed"] * self._embed_rows_times
        return params

    def make_batch(self, key, n_samples: int):
        return mellum.synthetic_batch(key, n_samples, self.seq_len, self.cfg)

    def loss(self, params, batch):
        return mellum.loss_fn(params, batch, self.cfg)

    def _record(self, selection, counters):
        self.selection.append(jax.tree.map(float, selection))
        self.routing_counters.append(
            jax.tree.map(lambda a: [float(x) for x in a], counters))

    def _attention_alone(self, q, k, v, kind):
        """The program's attention call of a layer of `kind` (at the
        cell's length the STREAMING kernels, windowed in a sliding layer)
        against the reference's float32 attention on the SAME operands: q
        [group, S, size], k and v [1, S, size], one key-value head's group
        as the layer's own step computes them.  Two numbers, each the
        worst over the result and the gradients of q, k and v (the
        cotangent random normal from the first token): the relative norm
        of the difference, and how far the ROWS are scaled, the norm of
        `<got, want> - <want, want>` over the rows against that of
        `<want, want>`.  Rounding of the operands and products scatters a
        row's error over its 128 numbers and hardly moves its length; a
        softmax normaliser or log-sum-exp that has lost bits scales the
        whole row, which is all the second number sees."""
        cfg, (group, S, size) = self.cfg, q.shape
        g = jax.random.normal(
            jax.random.fold_in(jax.random.key(0), size * S + group),
            q.shape, jnp.float32).astype(q.dtype)
        attend = afmoe._attn_fn(cfg, kind)

        def program(q, k, v):
            k, v = (jnp.repeat(t, group, axis=0) for t in (k, v))
            return attend(q[None], k[None], v[None])[0]

        block = min(self.spec["q_block"], S)
        window = self.spec["window"] if kind == mellum.SLIDING else S

        def plain(q, k, v):
            @jax.checkpoint
            def rows(start):
                qb = lax.dynamic_slice_in_dim(q, start, block, axis=1)
                return reference.attention(qb[None], k, v, start, window)[0]
            out = lax.map(rows, jnp.arange(0, S, block))
            return out.transpose(1, 0, 2, 3).reshape(q.shape)

        out, vjp = jax.vjp(program, q, k, v)
        got = (out, *vjp(g))
        with jax.default_matmul_precision("highest"):
            out, vjp = jax.vjp(plain,
                               *(t.astype(jnp.float32) for t in (q, k, v)))
            want = (out, *vjp(g.astype(jnp.float32)))

        def rel(a, b):
            return jnp.linalg.norm(a.astype(jnp.float32) - b) / (
                jnp.linalg.norm(b))

        def row_scale(a, b):
            ab = (a.astype(jnp.float32) * b).sum(-1)
            bb = (b * b).sum(-1)
            return jnp.linalg.norm(ab - bb) / jnp.linalg.norm(bb)
        return (jnp.stack([rel(a, b) for a, b in zip(got, want)]).max(),
                jnp.stack([row_scale(a, b) for a, b in zip(got, want)]).max())

    def parts_disagreement(self, params, tokens):
        """Three parts of the program ALONE, each against the reference's
        float32 on operands that are the same on both sides and are THE
        STEP'S OWN: the first sequence of `tokens` walked through the
        program's layers as the timed step walks them.  In the whole step
        a lower precision in any of the three hides under what bfloat16
        activations do to the experts' gradients (3-5% a leaf).

          - `router`: `dropless_moe.route` on the float32 of each layer's
            normed input against the reference's weights at the same
            choice; the relative norm of the [T, k] weights, worst layer.
          - `experts`: `dropless_moe.held_experts` on each layer's normed
            input (bfloat16 in the step) against the reference's held
            experts on the float32 of the same numbers, at the same
            choice; the relative norm of the [T, D] result, worst layer.
          - `attention`, `attention_rows`: `_attention_alone` on the first
            key-value head's group of the first sliding and the first full
            layer; the worst of the two layers."""
        cfg, spec = self.cfg, self.spec
        group = cfg.num_heads // cfg.num_kv_heads
        x = mellum._embed(params, tokens[:1], cfg)
        router, experts, attention = [], [], {}

        def rel(a, b):
            return jnp.linalg.norm(a.astype(jnp.float32) - b) / (
                jnp.linalg.norm(b))
        for i, kind in enumerate(self.layer_types):
            lp = jax.tree.map(lambda a: a[i], params["moe"])
            plain = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
            if kind not in attention:
                q, k, v = mellum._qkv(x, lp, cfg, kind)
                attention[kind] = self._attention_alone(
                    q[0, :group], k[0, :1], v[0, :1], kind)
            x = mellum._attention(x, lp, cfg, kind)
            m = mellum._experts_input(x, lp, cfg)
            m32 = m.astype(jnp.float32)
            sel, weights = dropless_moe.route(m32, lp["router_w"], cfg.moe)
            routed, _ = dropless_moe.held_experts(
                m, lp["router_w"],
                {n: lp["expert_" + n] for n in ("gate_w", "up_w", "down_w")},
                cfg.moe, sel=sel)
            with jax.default_matmul_precision("highest"):
                want_weights = reference.chosen_weights(
                    jax.nn.softmax(m32 @ plain["router_w"], -1), sel,
                    spec["norm_topk_prob"])
                want_routed, _ = reference.experts_layer(m32, plain, spec,
                                                         sel)
            router.append(rel(weights, want_weights))
            experts.append(rel(routed, want_routed))
            x, _ = mellum._experts(x, lp, None, cfg)
        whole, rows = (jnp.stack(t).max() for t in zip(*attention.values()))
        return {"router_rel_diff": jnp.stack(router).max(),
                "experts_rel_diff": jnp.stack(experts).max(),
                "attn_rel_diff": whole, "attn_row_diff": rows}

    def reference_loss(self, params, batch):
        """The reference's loss at the program's choice of experts, plus
        the number of tokens whose choice rounding does not explain, plus
        1 for each part of the program that alone is further from float32
        than its limit (`parts_disagreement`: the router `router_rel_tol`,
        the expert products `experts_rel_tol`, the attention kernels'
        rows `attn_row_tol`)."""
        tokens = batch[0]
        routing = mellum.routing(lax.stop_gradient(params), tokens, self.cfg)
        value, stats = reference.loss(params, batch, self.spec,
                                      sel=routing.sel, with_stats=True)
        gaps = stats["gaps"]                               # [layers, T]
        unexplained = (gaps >= self.selection_eps).sum()
        parts = self.parts_disagreement(lax.stop_gradient(params), tokens)
        selection = {
            "tokens": gaps.size,
            "swapped_share": stats["swapped_tokens"].sum() / gaps.size,
            "max_gap": gaps.max(), "unexplained_tokens": unexplained,
            **parts}
        counters = jax.vmap(
            lambda r: dropless_moe.counters(r, tokens.size))(routing)
        jax.debug.callback(self._record, selection, counters)
        off = (unexplained
               + (parts["router_rel_diff"] > self.router_rel_tol)
               + (parts["experts_rel_diff"] > self.experts_rel_tol)
               + (parts["attn_row_diff"] > self.attn_row_tol))
        return value + lax.stop_gradient(off.astype(jnp.float32))

    def model_flops_per_sample(self) -> float:
        """Model FLOPs to train on one sequence, forward and backward, no
        recompute: 6 per matmul parameter a token meets on this chip
        (`matmul_params_per_token`), plus attention's two matmuls over the
        (query, key) pairs each kind of layer NEEDS, the causal triangle
        in a full layer and the window's band in a sliding one: 2 FLOPs a
        multiply-add, two matmuls, three passes."""
        n = self.numbers
        params = matmul_params_per_token(
            n | {"num_experts": self.cfg.num_experts}, len(self.layer_types),
            len(self.cfg.held), n["vocab_size"])
        width = n["num_attention_heads"] * n["head_dim"]
        pairs = sum(window_pairs(
            self.seq_len,
            n["sliding_window"] if t == mellum.SLIDING else None)
            for t in self.layer_types)
        return 6.0 * params * self.seq_len + 12.0 * pairs * width
