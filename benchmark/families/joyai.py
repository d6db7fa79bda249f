"""The joyai family: a `joyai_llm_flash` `config.json` (JD's
JoyAI-LLM-Flash: DeepSeek-V3's keys) run through the program's
`byteps_tpu.models.joyai` as ONE CHIP'S SHARE of an expert-parallel
deployment and one pipeline stage of it, with the plain reference of
`benchmark/reference/joyai.py` beside it, told the same share.  See
`benchmark/families/gpt2.py` for what a family is and
`benchmark/families/afmoe.py` for how a share is written down
(`published` and `held`) and how `correct` is decided where top-k is
discontinuous.

`correct`'s three numbers (loss, worst leaf, norm ratio) are the
harness's; what they cannot tell is ADDED to the reference's loss, 1 a
count, which then fails `loss_rel_tol`:

  - every token whose choice of experts differs from the reference's own
    top-8 by a gap of `selection_eps` or more in the scores;
  - `router_rel_tol`, `experts_rel_tol`, `attn_rel_tol`: the router, the
    held experts' three products and one latent-attention call (queries
    and keys 192 wide, values 128: the STREAMING kernels at the cell's
    length), each alone on the step's own operands
    (`parts_disagreement`);
  - `mtp_state_tol`: what the prediction module's hidden states read of
    the main stack's FINAL NORM, which is nothing: on seeded weights every
    norm's scale is 1, a hidden state normed twice is the one normed
    once, and a module fed the normed state computes the same values and
    all but one of the same gradients.

What the existing readers ask of a family is here under the names they
use: `cfg` (with `.moe`, `.held`, `.num_experts`, `.num_experts_per_tok`,
`.moe_intermediate_size`), `seq_len`, `routing_counters`, `selection`,
and the model FLOPs of a sample.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax import lax

from benchmark.reference import joyai as reference
from byteps_tpu.models import afmoe, joyai
from byteps_tpu.parallel import dropless_moe


def matmul_params_per_token(n: dict, layers: int, dense_layers: int,
                            modules: int, held_experts: int,
                            held_vocab: int) -> float:
    """Parameters of the matrices a token is multiplied by, on this chip:
    latent attention's five (both chains down in one, each up, the
    output) in every layer; the dense SwiGLU or the router, the shared
    expert and the routed experts a token meets HERE (its
    `num_experts_per_tok` choices fall on the held experts in proportion:
    half an expert's worth where a sixteenth is held and a token takes
    8); a prediction module's layer and its [2 hidden, hidden] projection;
    the held rows of the head, once for every head product a step makes
    (the main one and each module's).  The embedding is a lookup."""
    D, H = n["hidden_size"], n["num_attention_heads"]
    rq, rkv = n["q_lora_rank"], n["kv_lora_rank"]
    attn = (D * (rq + rkv + n["qk_rope_head_dim"])
            + rq * H * (n["qk_nope_head_dim"] + n["qk_rope_head_dim"])
            + rkv * H * (n["qk_nope_head_dim"] + n["v_head_dim"])
            + H * n["v_head_dim"] * D)
    expert = 3 * D * n["moe_intermediate_size"]
    routed = n["num_experts_per_tok"] * held_experts / n["n_routed_experts"]
    moe = attn + D * n["n_routed_experts"] + expert * (
        n["n_shared_experts"] + routed)
    dense = attn + 3 * D * n["intermediate_size"]
    return (dense_layers * dense + (layers - dense_layers + modules) * moe
            + modules * 2 * D * D + (1 + modules) * held_vocab * D)


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
                or len(n["experts"]) != n["n_routed_experts"]):
            raise ValueError("the configuration's `held` counts disagree "
                             "with its lists")
        if (n["scoring_func"], n["topk_method"], n["n_shared_experts"],
                n["n_group"], n["topk_group"], n["norm_topk_prob"],
                n["rope_scaling"], n["moe_layer_freq"], n["hidden_act"]) != (
                    "sigmoid", "noaux_tc", 1, 1, 1, True, None, 1, "silu"):
            raise ValueError("joyai family: sigmoid scores with a bias for "
                             "the choice alone, one shared expert, no group "
                             "limit, normed weights, unscaled rotary "
                             "positions and SwiGLU in every layer are what "
                             "is written here")
        if n["qk_head_dim"] != n["qk_nope_head_dim"] + n["qk_rope_head_dim"]:
            raise ValueError("qk_head_dim is not its two parts' sum")
        dense = sum(i < published["first_k_dense_replace"]
                    for i in n["layers"])
        self.cfg = joyai.JoyaiConfig(
            vocab_size=n["vocab_size"], vocab_start=n["vocab_start"],
            hidden_size=n["hidden_size"],
            num_heads=n["num_attention_heads"],
            q_lora_rank=n["q_lora_rank"], kv_lora_rank=n["kv_lora_rank"],
            qk_nope_head_dim=n["qk_nope_head_dim"],
            qk_rope_head_dim=n["qk_rope_head_dim"],
            v_head_dim=n["v_head_dim"],
            intermediate_size=n["intermediate_size"],
            moe_intermediate_size=n["moe_intermediate_size"],
            num_experts=published["n_routed_experts"],
            num_experts_per_tok=n["num_experts_per_tok"],
            num_layers=len(n["layers"]), num_dense_layers=dense,
            num_mtp_modules=n["num_nextn_predict_layers"],
            mtp_loss_weight=float(config["assumed"]["mtp_loss_weight"]),
            held_experts=tuple(n["experts"]),
            route_scale=n["routed_scaling_factor"],
            route_norm=n["norm_topk_prob"], rms_norm_eps=n["rms_norm_eps"],
            rope_theta=float(n["rope_theta"]), **options)
        self.reference_check = config["reference_check"]
        self.spec = {
            "heads": n["num_attention_heads"], "nope": n["qk_nope_head_dim"],
            "rope": n["qk_rope_head_dim"], "q_lora": n["q_lora_rank"],
            "kv_lora": n["kv_lora_rank"], "eps": n["rms_norm_eps"],
            "theta": float(n["rope_theta"]),
            "top_k": n["num_experts_per_tok"], "held": tuple(n["experts"]),
            "route_scale": n["routed_scaling_factor"],
            "vocab_start": n["vocab_start"],
            "mtp_weight": self.cfg.mtp_loss_weight,
            **self.reference_check["reference_blocks"]}
        self.units_per_sample = self.seq_len
        for name in ("selection_eps", "router_rel_tol", "experts_rel_tol",
                     "attn_rel_tol", "mtp_state_tol"):
            setattr(self, name, float(self.reference_check[name]))
        self.selection, self.routing_counters = [], []
        opt = job["optimizer"]
        if opt["name"] != "adamw":
            raise ValueError(f"joyai family: no optimizer {opt['name']!r}")
        self._learning_rate = float(opt["learning_rate"])
        self._embed_rows_times = float(
            config["initial_weights"]["embed_rows_times"])

    def optimizer(self) -> optax.GradientTransformation:
        return optax.adamw(self._learning_rate)

    def init(self, key):
        """The program's own initial weights, the embedding's rows times
        the cell's `initial_weights.embed_rows_times` (the configuration
        says why)."""
        params = joyai.init_params(key, self.cfg)
        params["embed"] = params["embed"] * self._embed_rows_times
        return params

    def make_batch(self, key, n_samples: int):
        return joyai.synthetic_batch(key, n_samples, self.seq_len, self.cfg)

    def loss(self, params, batch):
        return joyai.loss_fn(params, batch, self.cfg)

    def losses(self, params, batch):
        """`(main, mtp)` of the program and of the reference, each the
        mean over its own positions: what the two heads read apart."""
        return (joyai.losses(params, batch, self.cfg),
                reference.losses(params, batch, self.spec)[:2])

    def _record(self, selection, counters):
        self.selection.append(jax.tree.map(float, selection))
        self.routing_counters.append(
            jax.tree.map(lambda a: [float(x) for x in a], counters))

    # -- the parts alone ---------------------------------------------------
    def _attention_alone(self, q, k, v):
        """The program's attention call (at the cell's length the
        STREAMING kernels, queries and keys `nope + rope` wide, values
        `v`) against the reference's float32 latent attention on the SAME
        operands: q, k [heads, S, nope + rope], v [heads, S, v] of a few
        heads, as the layer's own step computes them; the reference is
        handed the two parts of a query and a key apart, a head
        at a time.  Two numbers as
        `benchmark/families/mellum.py` reads them: the relative norm of
        the difference, and how far the ROWS are scaled, each the worst
        over the result and the gradients of q, k and v.  The first is
        the one held to a limit here: over four seeds on the chip it
        read 3.0e-3 to 3.3e-3 where the rows read 1.2e-3 to 2.5e-3."""
        cfg, nope = self.cfg, self.cfg.qk_nope_head_dim
        g = jax.random.normal(
            jax.random.fold_in(jax.random.key(0), v.size), v.shape,
            jnp.float32).astype(v.dtype)

        def program(q, k, v):
            return afmoe._attn_fn(cfg, afmoe.FULL)(q[None], k[None],
                                                   v[None])[0]

        block = min(self.spec["q_block"], q.shape[1])

        def plain(q, k, v):
            def head(q, k, v):                  # [S, .] each
                @jax.checkpoint
                def rows(start):
                    qb = lax.dynamic_slice_in_dim(q, start, block)[None]
                    return reference.attention(
                        qb[..., :nope], qb[..., nope:], k[None, :, :nope],
                        k[:, nope:], v[None], start)[0]
                out = lax.map(rows, jnp.arange(0, q.shape[0], block))
                return out.reshape(v.shape)
            return jnp.stack([head(q[h], k[h], v[h])
                              for h in range(q.shape[0])])

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

    def parts_disagreement(self, params, batch, module_state=None):
        """Three parts of the program ALONE, each against the reference's
        float32 on operands that are the same on both sides and are THE
        STEP'S OWN: the first sequence of the batch walked through the
        program's layers as the timed step walks them, the prediction
        module's layer last.

          - `router`: `dropless_moe.route` on the float32 of each expert
            layer's normed input against the reference's weights at the
            same choice; the relative norm of the [T, k] weights, worst
            layer.
          - `experts`: `dropless_moe.held_experts` on each expert layer's
            normed input (bfloat16 in the step) against the reference's
            held experts on the float32 of the same numbers, at the same
            choice; the relative norm of the [T, D] result, worst layer.
          - `attention`, `attention_rows`: `_attention_alone` on the first
            two heads of the first layer.

        And `mtp_state_diff`: how far the prediction module's hidden
        states (`module_state`, the program's own on `batch`) move when
        the main stack's `final_ln` is laid out unevenly (a ramp from a
        half to one and a half over its lanes): the module reads the
        stack's output BEFORE that norm, so not at all but for bfloat16's
        last bit (two instances of the same layers, fused differently)."""
        cfg, spec = self.cfg, self.spec
        tokens, targets = (t[:1] for t in batch)
        nd = cfg.num_dense_layers
        router, experts = [], []

        def rel(a, b):
            return jnp.linalg.norm(a.astype(jnp.float32) - b) / (
                jnp.linalg.norm(b))

        def expert_parts(x, lp):
            m = joyai._experts_input(x, lp, cfg).reshape(-1, x.shape[-1])
            m32 = m.astype(jnp.float32)
            plain = {k: lp[k].astype(jnp.float32) for k in (
                "router_w", "expert_gate_w", "expert_up_w", "expert_down_w")}
            sel, weights = dropless_moe.route(m32, lp["router_w"], cfg.moe)
            routed, _ = dropless_moe.held_experts(
                m, lp["router_w"],
                {n: lp["expert_" + n] for n in ("gate_w", "up_w", "down_w")},
                cfg.moe, sel=sel)
            with jax.default_matmul_precision("highest"):
                want_weights = reference.chosen_weights(
                    jax.nn.sigmoid(m32 @ plain["router_w"]), sel,
                    spec["route_scale"])
                want_routed, _ = reference.routed_experts(m32, plain, spec,
                                                          sel)
            router.append(rel(weights, want_weights))
            experts.append(rel(routed, want_routed))

        x = joyai._embed(params, tokens, cfg)
        attention = None
        for i in range(cfg.num_layers):
            group, j = ("dense", i) if i < nd else ("moe", i - nd)
            lp = jax.tree.map(lambda a: a[j], params[group])
            if attention is None:
                q, k, v = joyai._qkv(x, lp, cfg)
                attention = self._attention_alone(q[0, :2], k[0, :2],
                                                  v[0, :2])
            x = joyai._attention(x, lp, cfg)
            if i >= nd:
                expert_parts(x, lp)
            x, _ = joyai._feed_forward(x, lp, None, cfg, i >= nd)
        if cfg.num_mtp_modules:
            x = joyai._mtp_input(params, x, targets, cfg)
            expert_parts(joyai._attention(x, params["mtp"], cfg),
                         params["mtp"])
        zero = jnp.zeros((), jnp.float32)
        moved = zero
        if module_state is not None:
            ramp = jnp.linspace(0.5, 1.5, cfg.hidden_size)
            again = joyai.forward_hidden(
                {**params, "final_ln": params["final_ln"] * ramp}, batch[0],
                cfg, next_tokens=batch[1])[1]
            moved = rel(again, module_state.astype(jnp.float32))
        return {"mtp_state_diff": moved,
                "router_rel_diff": jnp.stack(router or [zero]).max(),
                "experts_rel_diff": jnp.stack(experts or [zero]).max(),
                "attn_rel_diff": attention[0], "attn_row_diff": attention[1]}

    def reference_loss(self, params, batch):
        """The reference's loss (main + weight x MTP) at the program's
        choice of experts, plus the number of tokens whose choice rounding
        does not explain, plus 1 for each part of the program that alone
        is further from float32 than its limit (`parts_disagreement`)."""
        tokens, targets = batch
        frozen = lax.stop_gradient(params)
        hidden, routing = joyai.forward_hidden(
            frozen, tokens, self.cfg, with_routing=True, next_tokens=targets)
        if routing is None:                     # dense layers alone
            return reference.loss(params, batch, self.spec)
        value, stats = reference.loss(params, batch, self.spec,
                                      sel=routing.sel, with_stats=True)
        parts = self.parts_disagreement(
            frozen, batch, hidden[1] if self.cfg.num_mtp_modules else None)
        off = ((parts["router_rel_diff"] > self.router_rel_tol).astype(
            jnp.int32)
            + (parts["experts_rel_diff"] > self.experts_rel_tol)
            + (parts["attn_rel_diff"] > self.attn_rel_tol)
            + (parts["mtp_state_diff"] > self.mtp_state_tol))
        gaps = stats["gaps"]                              # [layers, T]
        unexplained = (gaps >= self.selection_eps).sum()
        selection = {
            "tokens": gaps.size,
            "swapped_share": stats["swapped_tokens"].sum() / gaps.size,
            "max_gap": gaps.max(), "unexplained_tokens": unexplained,
            **parts}
        counters = jax.vmap(
            lambda r: dropless_moe.counters(r, tokens.size))(routing)
        jax.debug.callback(self._record, selection, counters)
        return value + lax.stop_gradient(
            (off + unexplained).astype(jnp.float32))

    def model_flops_per_sample(self) -> float:
        """Model FLOPs to train on one sequence, forward and backward, no
        recompute: 6 per matmul parameter a token meets on this chip
        (`matmul_params_per_token`), plus latent attention's two matmuls
        over the causal triangle, the first `nope + rope` deep and the
        second `v` wide: 2 FLOPs a multiply-add, three passes, every
        layer and the prediction module's."""
        n, cfg = self.numbers, self.cfg
        params = matmul_params_per_token(
            n | {"n_routed_experts": cfg.num_experts}, cfg.num_layers,
            cfg.num_dense_layers, cfg.num_mtp_modules, len(cfg.held),
            n["vocab_size"])
        pairs = self.seq_len * (self.seq_len + 1) // 2
        width = cfg.num_heads * (cfg.qk_head_dim + cfg.v_head_dim)
        return (6.0 * params * self.seq_len
                + (cfg.num_layers + cfg.num_mtp_modules) * 6.0 * pairs * width)
