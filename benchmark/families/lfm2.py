"""The lfm2 family: an `lfm2_moe` `config.json` (LiquidAI's LFM2
mixture-of-experts decoders) run through the program's
`byteps_tpu.models.lfm2` as ONE CHIP'S SHARE of an expert-parallel
deployment and one pipeline stage of it, with the plain reference of
`benchmark/reference/lfm2.py` beside it, told the same share.  See
`benchmark/families/gpt2.py` for what a family is and
`benchmark/families/afmoe.py` for how a share is written down
(`published` and `held`) and how `correct` is decided where top-k is
discontinuous.

`correct`'s three numbers (loss, worst leaf, norm ratio) are the
harness's; what they cannot tell is ADDED to the reference's loss, 1 a
count, which then fails `loss_rel_tol`:

  - every token whose choice of experts differs from the reference's own
    top-4 by a gap of `selection_eps` or more in the scores;
  - `qk_rel_tol`: the queries and keys an attention layer makes when its
    q / k norms' scales are laid out unevenly (a ramp from a half to one
    and a half over a head's lanes).  On seeded weights every scale is 1,
    a norm of all ones commutes with the rotary turn, and a program that
    turned BEFORE it normed would compute the same values and all the
    same gradients but the two scales' own; under a ramp it does not.
  - `router_rel_tol`, `experts_rel_tol`, `attn_rel_tol`, `conv_rel_tol`:
    the router, the held experts' three products, one attention call (the
    resident flash kernels at head size 64) and the gated convolution
    (`ops/short_conv.py`'s kernels: forward result and the gradients of
    `bcx` and of the taps, the sequence laid out as TWO of half the
    length, so that a tap that reaches across a sequence's start shows),
    each alone on the step's own operands (`parts_disagreement`).

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

from benchmark.reference import lfm2 as reference
from byteps_tpu.models import afmoe, lfm2
from byteps_tpu.parallel import dropless_moe


def matmul_params_per_token(n: dict, layer_types, dense_layers: int,
                            held_experts: int, held_vocab: int) -> float:
    """Parameters of the matrices a token is multiplied by, on this chip:
    a convolution mixer's two projections or an attention mixer's four;
    the dense SwiGLU, or the router and the routed experts a token meets
    HERE (its `num_experts_per_tok` choices fall on the held experts in
    proportion: half an expert's worth where an eighth is held and a token
    takes 4); the held rows of the tied head.  The embedding is a lookup,
    the convolution's taps no matrix."""
    D = n["hidden_size"]
    H, G, size = (n["num_attention_heads"], n["num_key_value_heads"],
                  n["head_dim"])
    conv = 3 * D * D + D * D
    attn = D * (H + 2 * G) * size + H * size * D
    dense = 3 * D * n["intermediate_size"]
    routed = n["num_experts_per_tok"] * held_experts / n["num_experts"]
    moe = D * n["num_experts"] + 3 * D * n["moe_intermediate_size"] * routed
    n_conv = sum(t == lfm2.CONV for t in layer_types)
    return (n_conv * conv + (len(layer_types) - n_conv) * attn
            + dense_layers * dense + (len(layer_types) - dense_layers) * moe
            + held_vocab * D)


class Family:
    unit = "tokens"

    def __init__(self, config: dict, job: dict):
        published = config["published"]
        n = {**published, **config["held"]}
        n["head_dim"] = int(config["assumed"]["head_dim"])
        self.numbers = n
        options = config["program_options"]["pinned"]
        self.seq_len = int(job["seq_len"])
        if self.seq_len > n["max_position_embeddings"]:
            raise ValueError(f"seq_len {self.seq_len} is beyond the model's "
                             f"{n['max_position_embeddings']} positions")
        self.layer_types = tuple(published["layer_types"][i]
                                 for i in n["layers"])
        dense = sum(i < published["num_dense_layers"] for i in n["layers"])
        if (dense != n["num_dense_layers"]
                or len(n["layers"]) != n["num_hidden_layers"]
                or len(n["experts"]) != n["num_experts"]):
            raise ValueError("the configuration's `held` counts disagree "
                             "with its lists")
        if (n["conv_bias"], n["norm_topk_prob"], n["use_expert_bias"],
                n["rope_parameters"]["rope_type"]) != (False, True, True,
                                                       "default"):
            raise ValueError("lfm2 family: a convolution without bias, "
                             "normed weights, a bias for the choice alone "
                             "and unscaled rotary positions are what is "
                             "written here")
        norm_eps = float(config["assumed"]["route_norm_eps"])
        self.cfg = lfm2.Lfm2Config(
            vocab_size=n["vocab_size"], vocab_start=n["vocab_start"],
            hidden_size=n["hidden_size"], layer_types=self.layer_types,
            num_dense_layers=dense,
            intermediate_size=n["intermediate_size"],
            moe_intermediate_size=n["moe_intermediate_size"],
            num_experts=published["num_experts"],
            num_experts_per_tok=n["num_experts_per_tok"],
            num_heads=n["num_attention_heads"],
            num_kv_heads=n["num_key_value_heads"], head_dim=n["head_dim"],
            conv_kernel=n["conv_L_cache"], held_experts=tuple(n["experts"]),
            route_scale=float(n["routed_scaling_factor"]),
            route_norm=n["norm_topk_prob"], route_norm_eps=norm_eps,
            rms_norm_eps=n["norm_eps"],
            rope_theta=float(n["rope_parameters"]["rope_theta"]), **options)
        self.reference_check = config["reference_check"]
        self.spec = {
            "layer_types": self.layer_types, "dense_layers": dense,
            "heads": n["num_attention_heads"],
            "kv_heads": n["num_key_value_heads"], "head_dim": n["head_dim"],
            "eps": n["norm_eps"],
            "theta": float(n["rope_parameters"]["rope_theta"]),
            "top_k": n["num_experts_per_tok"], "held": tuple(n["experts"]),
            "route_scale": float(n["routed_scaling_factor"]),
            "norm_eps": norm_eps, "vocab_start": n["vocab_start"],
            **self.reference_check["reference_blocks"]}
        self.units_per_sample = self.seq_len
        for name in ("selection_eps", "router_rel_tol", "experts_rel_tol",
                     "attn_rel_tol", "conv_rel_tol", "qk_rel_tol"):
            setattr(self, name, float(self.reference_check[name]))
        self.selection, self.routing_counters = [], []
        opt = job["optimizer"]
        if opt["name"] != "adamw":
            raise ValueError(f"lfm2 family: no optimizer {opt['name']!r}")
        self._learning_rate = float(opt["learning_rate"])
        self._embed_rows_times = float(
            config["initial_weights"]["embed_rows_times"])

    def optimizer(self) -> optax.GradientTransformation:
        return optax.adamw(self._learning_rate)

    def init(self, key):
        """The program's own initial weights, the embedding's rows times
        the cell's `initial_weights.embed_rows_times` (the configuration
        says why)."""
        params = lfm2.init_params(key, self.cfg)
        params["embed"] = params["embed"] * self._embed_rows_times
        return params

    def make_batch(self, key, n_samples: int):
        return lfm2.synthetic_batch(key, n_samples, self.seq_len, self.cfg)

    def loss(self, params, batch):
        return lfm2.loss_fn(params, batch, self.cfg)

    def _record(self, selection, counters):
        self.selection.append(jax.tree.map(float, selection))
        self.routing_counters.append(
            jax.tree.map(lambda a: [float(x) for x in a], counters))

    # -- the parts alone ---------------------------------------------------
    def _attention_alone(self, q, k, v):
        """The program's attention call against the reference's float32
        attention on the SAME operands: q, k, v [heads, S, size] of a few
        query heads as the layer's own step computes them (keys and values
        already repeated for their query heads).  The relative norm of the
        difference and how far the ROWS are scaled
        (`benchmark/families/mellum.py`), each the worst over the result
        and the gradients of q, k and v."""
        cfg = self.cfg
        g = jax.random.normal(
            jax.random.fold_in(jax.random.key(0), v.size), v.shape,
            jnp.float32).astype(v.dtype)

        def program(q, k, v):
            return afmoe._attn_fn(cfg, afmoe.FULL)(q[None], k[None],
                                                   v[None])[0]

        block = min(self.spec["q_block"], q.shape[1])

        def plain(q, k, v):
            @jax.checkpoint
            def rows(start):
                qb = lax.dynamic_slice_in_dim(q, start, block, axis=1)
                return reference.attention(qb, k, v, start)
            out = lax.map(rows, jnp.arange(0, q.shape[1], block))
            return out.transpose(1, 0, 2, 3).reshape(v.shape)

        out, vjp = jax.vjp(program, q, k, v)
        got = (out, *vjp(g))
        with jax.default_matmul_precision("highest"):
            out, vjp = jax.vjp(plain,
                               *(t.astype(jnp.float32) for t in (q, k, v)))
            want = (out, *vjp(g.astype(jnp.float32)))

        def row_scale(a, b):
            ab = (a.astype(jnp.float32) * b).sum(-1)
            bb = (b * b).sum(-1)
            return jnp.linalg.norm(ab - bb) / jnp.linalg.norm(bb)
        return (jnp.stack([_rel(a, b) for a, b in zip(got, want)]).max(),
                jnp.stack([row_scale(a, b) for a, b in zip(got, want)]).max())

    def _conv_alone(self, bcx, taps):
        """The program's gated convolution (`lfm2._gated_conv`: the Pallas
        kernels) against the reference's float32 shifted sums on the SAME
        numbers: bcx [sequences, S, 3 hidden] as the first convolution
        layer's own step computes it, the layer's taps.  The relative norm
        of the difference, the worst of the result and the gradients of
        `bcx` and the taps under a fixed random cotangent, the reference
        given the operands as the kernel was (`_as_given`).  The harness
        hands the reference one sequence at a time, so the sequence is
        laid out as TWO of half the length: a tap that reaches across a
        sequence's start reads the first half's last rows there."""
        bcx = bcx.reshape(2 * bcx.shape[0], bcx.shape[1] // 2, -1)
        g = jax.random.normal(
            jax.random.fold_in(jax.random.key(0), bcx.size),
            (*bcx.shape[:-1], taps.shape[-1]), jnp.float32).astype(bcx.dtype)
        out, vjp = jax.vjp(lfm2._gated_conv, bcx, taps)
        got = (out, *vjp(g))
        out, vjp = jax.vjp(
            lambda b, w: jax.vmap(reference.gated_conv, (0, None))(b, w),
            _as_given(bcx), taps.astype(jnp.float32))
        want = (out, *vjp(_as_given(g)))
        return jnp.stack([_rel(a, b) for a, b in zip(got, want)]).max()

    def _queries_and_keys_alone(self, x, lp):
        """The program's queries and keys (`lfm2._qkv` on x [1, S, D], the
        layer's input as the step has it) against the reference's on the
        float32 of the same normed input, BOTH under q / k norm scales
        that are a ramp from 0.5 to 1.5 over a head's lanes: the relative
        norm of the difference, the worse of the two."""
        cfg, spec = self.cfg, self.spec
        H, G, size = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        ramp = jnp.linspace(0.5, 1.5, size)
        q, k, _ = lfm2._qkv(x, {**lp, "q_norm": ramp, "k_norm": ramp}, cfg)
        a = lfm2._norm(x, lp["operator_norm"], cfg)[0].astype(jnp.float32)
        w = lp["qkv_w"].astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            want_q = reference.normed_and_turned(a, w[:, :H * size], ramp,
                                                 spec)
            want_k = reference.normed_and_turned(
                a, w[:, H * size:(H + G) * size], ramp, spec)
        return jnp.maximum(_rel(q[0], want_q), _rel(k[0], want_k))

    def parts_disagreement(self, params, batch):
        """Five parts of the program ALONE, each against the reference's
        float32 on operands that are the same on both sides and are THE
        STEP'S OWN: the first sequence of the batch walked through the
        program's layers as the timed step walks them.

          - `router`: `dropless_moe.route` on the float32 of each expert
            layer's normed input against the reference's weights at the
            same choice; the relative norm of the [T, k] weights, worst
            layer.
          - `experts`: `dropless_moe.held_experts` on each expert layer's
            normed input (bfloat16 in the step) against the reference's
            held experts on the float32 of the same numbers, at the same
            choice; the relative norm of the [T, D] result, worst layer.
          - `attention`, `attention_rows`: `_attention_alone` on the first
            two query heads of the first attention layer.
          - `conv`: `_conv_alone` on the first convolution layer's
            `in_proj` result.
          - `qk`: `_queries_and_keys_alone` on the first attention layer's
            input."""
        cfg, spec = self.cfg, self.spec
        tokens = batch[0][:1]
        router, experts = [], []
        attention = conv = qk = None

        def expert_parts(x, lp):
            m = lfm2._ffn_input(x, lp, cfg).reshape(-1, x.shape[-1])
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
                    jax.nn.sigmoid(m32 @ plain["router_w"]), sel, spec)
                want_routed, _ = reference.routed_experts(m32, plain, spec,
                                                          sel)
            router.append(_rel(weights, want_weights))
            experts.append(_rel(routed, want_routed))

        x = lfm2._embed(params, tokens, cfg)
        for (mixer, ffn, n), group in zip(lfm2.stack_plan(cfg),
                                          params["layers"]):
            for j in range(n):
                lp = jax.tree.map(lambda a: a[j], group)
                if mixer == lfm2.CONV and conv is None:
                    u = lfm2._norm(x, lp["operator_norm"], cfg)
                    bcx = jnp.einsum("bsd,de->bse", u,
                                     lp["in_proj_w"].astype(cfg.dtype))
                    conv = self._conv_alone(bcx, lp["conv_w"])
                if mixer == lfm2.ATTENTION and attention is None:
                    qk = self._queries_and_keys_alone(x, lp)
                    q, k, v = lfm2._qkv(x, lp, cfg)
                    attention = self._attention_alone(
                        q[0, :2], jnp.repeat(k[0, :1], 2, axis=0),
                        jnp.repeat(v[0, :1], 2, axis=0))
                x = x + lfm2._MIXERS[mixer](x, lp, cfg)
                if ffn == lfm2.MOE:
                    expert_parts(x, lp)
                x = x + lfm2._feed_forward(x, lp, None, cfg, ffn)[0]
        zero = jnp.zeros((), jnp.float32)
        attention = attention or (zero, zero)
        return {"router_rel_diff": jnp.stack(router or [zero]).max(),
                "experts_rel_diff": jnp.stack(experts or [zero]).max(),
                "attn_rel_diff": attention[0], "attn_row_diff": attention[1],
                "conv_rel_diff": zero if conv is None else conv,
                "qk_rel_diff": zero if qk is None else qk}

    def reference_loss(self, params, batch):
        """The reference's loss at the program's choice of experts, plus
        the number of tokens whose choice rounding does not explain, plus
        1 for each part of the program that alone is further from float32
        than its limit (`parts_disagreement`)."""
        tokens = batch[0]
        frozen = lax.stop_gradient(params)
        parts = self.parts_disagreement(frozen, batch)
        off = ((parts["router_rel_diff"] > self.router_rel_tol).astype(
            jnp.int32)
            + (parts["experts_rel_diff"] > self.experts_rel_tol)
            + (parts["attn_rel_diff"] > self.attn_rel_tol)
            + (parts["conv_rel_diff"] > self.conv_rel_tol)
            + (parts["qk_rel_diff"] > self.qk_rel_tol))
        routing = lfm2.routing(frozen, tokens, self.cfg)
        if routing is None:                     # dense layers alone
            return reference.loss(params, batch, self.spec) + (
                lax.stop_gradient(off.astype(jnp.float32)))
        value, stats = reference.loss(params, batch, self.spec,
                                      sel=routing.sel, with_stats=True)
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
        (`matmul_params_per_token`), plus an attention layer's two matmuls
        over the causal triangle, 2 FLOPs a multiply-add, three passes.
        The convolution's own multiply-adds (a dozen a channel and token)
        are not matrix work and are left out."""
        n, cfg = self.numbers, self.cfg
        params = matmul_params_per_token(
            n | {"num_experts": cfg.num_experts}, self.layer_types,
            cfg.num_dense_layers, len(cfg.held), n["vocab_size"])
        pairs = self.seq_len * (self.seq_len + 1) // 2
        layers = sum(t == lfm2.ATTENTION for t in self.layer_types)
        return (6.0 * params * self.seq_len
                + layers * 12.0 * pairs * cfg.num_heads * cfg.head_dim)


def _rel(a, b):
    return jnp.linalg.norm(a.astype(jnp.float32) - b) / jnp.linalg.norm(b)


def _as_given(t):
    """bfloat16 `t` in float32 AS THE KERNEL WAS GIVEN IT.  `t` is a
    product's result rounded to bfloat16; asked for its float32, the
    chip's compiler drops the rounding with the cast back and hands on
    the product's own float32 (excess precision, its default), and the
    comparison then reads three operands' roundings that the program
    never made beside the one it did (3.3e-3 for 1.66e-3, my chip runs,
    PR 55).  `reduce_precision` is the rounding the compiler keeps; a
    float32 `t` (the tests' program) is its own float32."""
    wide = t.astype(jnp.float32)
    if t.dtype != jnp.bfloat16:
        return wide
    return lax.reduce_precision(wide, 8, 7)
