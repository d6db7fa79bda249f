"""The kimilinear family: a `kimi_linear` `config.json` (Moonshot's Kimi
Linear models) run through the program's `byteps_tpu.models.kimi_linear`
as ONE CHIP'S SHARE of an expert-parallel deployment and one pipeline
stage of it, with the plain reference of
`benchmark/reference/kimilinear.py` beside it, told the same share.  See
`benchmark/families/gpt2.py` for what a family is and
`benchmark/families/afmoe.py` for how a share is written down
(`published` and `held`) and how `correct` is decided where top-k is
discontinuous.

`correct`'s three numbers (loss, worst leaf, norm ratio) are the
harness's; what they cannot tell is ADDED to the reference's loss, 1 a
count, which then fails `loss_rel_tol`:

  - every token whose choice of experts differs from the reference's own
    top-8 by a gap of `selection_eps` or more in the scores;
  - `router_rel_tol`, `experts_rel_tol`, `attn_rel_tol`, `conv_rel_tol`:
    the router, the held experts' three products, one latent-attention
    call (queries and keys 192 wide, values 128) and the 4-tap
    convolution with its silu (`short_conv.mamba_conv` without a bias:
    result and the gradients of its input and its taps, the sequence laid
    out as TWO of half the length, so that a tap that reaches across a
    sequence's start shows), each alone on the step's own operands;
  - `kda_rel_tol`: the program's scan (`kimi_linear._scan`) on the first
    KDA layer's FLOAT32 q, k, v, g and beta, a few heads of them, against
    the reference's recurrence, a position at a time: the result and all
    five gradients.  What tells a state carried in bfloat16, pairwise
    decays that overflow, a decay on the wrong side of the correction:
    inside a whole step bfloat16 activations hide them
    (`parts_disagreement`).

What the existing readers ask of a family is here under the names they
use: `cfg` (with `.moe`, `.held`, `.num_experts`, `.num_experts_per_tok`,
`.moe_intermediate_size`), `seq_len`, `routing_counters`, `selection`,
the model FLOPs of a sample, and `kda_shape` for
`benchmark/reduce/kda_cost.py`.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import optax
from jax import lax

from benchmark.families.lfm2 import _as_given, _rel
from benchmark.reduce import kda_cost
from benchmark.reference import kimilinear as reference
from byteps_tpu.models import afmoe, kimi_linear
from byteps_tpu.ops import kda
from byteps_tpu.parallel import dropless_moe

# Heads of the first KDA layer the scan is held to the recurrence on.
SCAN_HEADS = 4


def matmul_params_per_token(n: dict, layer_types, dense_layers: int,
                            held_experts: int, held_vocab: int) -> float:
    """Parameters of the matrices a token is multiplied by, on this chip:
    a KDA mixer's projections (q, k, v, both low-rank pairs, beta, the
    output) or latent attention's four; the dense SwiGLU, or the router,
    the shared expert and the routed experts a token meets HERE (its
    `num_experts_per_token` choices fall on the held experts in
    proportion: a quarter of an expert's worth where 8 of 256 are held
    and a token takes 8); the held rows of the head.  The embedding is a
    lookup, the convolution's taps no matrix."""
    D, H = n["hidden_size"], n["num_attention_heads"]
    lin = n["linear_attn_config"]
    W, R = lin["num_heads"] * lin["head_dim"], lin["head_dim"]
    mixer = {
        kimi_linear.KDA: (3 * D * W + 2 * (D * R + R * W)
                          + D * lin["num_heads"] + W * D),
        kimi_linear.MLA: (
            D * H * (n["qk_nope_head_dim"] + n["qk_rope_head_dim"])
            + D * (n["kv_lora_rank"] + n["qk_rope_head_dim"])
            + n["kv_lora_rank"] * H * (n["qk_nope_head_dim"]
                                       + n["v_head_dim"])
            + H * n["v_head_dim"] * D)}
    expert = 3 * D * n["moe_intermediate_size"]
    routed = n["num_experts_per_token"] * held_experts / n["num_experts"]
    moe = D * n["num_experts"] + expert * (n["num_shared_experts"] + routed)
    dense = 3 * D * n["intermediate_size"]
    return (sum(mixer[t] for t in layer_types) + dense_layers * dense
            + (len(layer_types) - dense_layers) * moe + held_vocab * D)


class Family:
    unit = "tokens"

    def __init__(self, config: dict, job: dict):
        published = config["published"]
        n = {**published, **config["held"]}
        self.numbers = n
        options = config["program_options"]["pinned"]
        self.seq_len = int(job["seq_len"])
        if self.seq_len > n["model_max_length"]:
            raise ValueError(f"seq_len {self.seq_len} is beyond the model's "
                             f"{n['model_max_length']} positions")
        lin = published["linear_attn_config"]
        # `linear_attn_config` numbers the layers from 1
        kinds = {**{i: kimi_linear.KDA for i in lin["kda_layers"]},
                 **{i: kimi_linear.MLA for i in lin["full_attn_layers"]}}
        self.layer_types = tuple(kinds[i] for i in n["layers"])
        dense = sum(i <= published["first_k_dense_replace"]
                    for i in n["layers"])
        if (len(n["layers"]) != n["num_hidden_layers"]
                or len(n["experts"]) != n["num_experts"]
                or list(n["layer_kinds"]) != list(self.layer_types)):
            raise ValueError("the configuration's `held` counts and kinds "
                             "disagree with its lists")
        if (n["moe_router_activation_func"], n["num_shared_experts"],
                n["num_expert_group"], n["topk_group"], n["moe_renormalize"],
                n["mla_use_nope"], n["q_lora_rank"], n["moe_layer_freq"],
                n["hidden_act"], n["num_nextn_predict_layers"],
                n["tie_word_embeddings"]) != (
                    "sigmoid", 1, 1, 1, True, True, None, 1, "silu", 0,
                    False):
            raise ValueError("kimilinear family: sigmoid scores with a bias "
                             "for the choice alone, one shared expert, no "
                             "group limit, normed weights, latent attention "
                             "without positions and without a query chain, "
                             "SwiGLU in every layer, no prediction module "
                             "and an untied head are what is written here")
        self.cfg = kimi_linear.KimiLinearConfig(
            vocab_size=n["vocab_size"], vocab_start=n["vocab_start"],
            hidden_size=n["hidden_size"], layer_types=self.layer_types,
            num_dense_layers=dense,
            intermediate_size=n["intermediate_size"],
            moe_intermediate_size=n["moe_intermediate_size"],
            num_experts=published["num_experts"],
            num_experts_per_tok=n["num_experts_per_token"],
            num_heads=n["num_attention_heads"],
            kv_lora_rank=n["kv_lora_rank"],
            qk_nope_head_dim=n["qk_nope_head_dim"],
            qk_rope_head_dim=n["qk_rope_head_dim"],
            v_head_dim=n["v_head_dim"], kda_heads=lin["num_heads"],
            kda_head_dim=lin["head_dim"],
            conv_kernel=lin["short_conv_kernel_size"],
            held_experts=tuple(n["experts"]),
            route_scale=float(n["routed_scaling_factor"]),
            route_norm=n["moe_renormalize"], rms_norm_eps=n["rms_norm_eps"],
            **options)
        self.reference_check = config["reference_check"]
        self.spec = {
            "layer_types": self.layer_types, "dense_layers": dense,
            "heads": n["num_attention_heads"],
            "nope": n["qk_nope_head_dim"], "kv_lora": n["kv_lora_rank"],
            "eps": n["rms_norm_eps"], "top_k": n["num_experts_per_token"],
            "held": tuple(n["experts"]),
            "route_scale": float(n["routed_scaling_factor"]),
            "vocab_start": n["vocab_start"],
            **self.reference_check["reference_blocks"]}
        self.units_per_sample = self.seq_len
        for name in ("selection_eps", "router_rel_tol", "experts_rel_tol",
                     "attn_rel_tol", "conv_rel_tol", "kda_rel_tol"):
            setattr(self, name, float(self.reference_check[name]))
        self.selection, self.routing_counters = [], []
        opt = job["optimizer"]
        if opt["name"] != "adamw":
            raise ValueError(f"kimilinear family: no optimizer "
                             f"{opt['name']!r}")
        self._learning_rate = float(opt["learning_rate"])
        self._embed_rows_times = float(
            config["initial_weights"]["embed_rows_times"])

    def optimizer(self) -> optax.GradientTransformation:
        return optax.adamw(self._learning_rate)

    def init(self, key):
        """The program's own initial weights, the embedding's rows times
        the cell's `initial_weights.embed_rows_times` (the configuration
        says why)."""
        params = kimi_linear.init_params(key, self.cfg)
        params["embed"] = params["embed"] * self._embed_rows_times
        return params

    def make_batch(self, key, n_samples: int):
        return kimi_linear.synthetic_batch(key, n_samples, self.seq_len,
                                           self.cfg)

    def loss(self, params, batch):
        return kimi_linear.loss_fn(params, batch, self.cfg)

    def _record(self, selection, counters):
        self.selection.append(jax.tree.map(float, selection))
        self.routing_counters.append(
            jax.tree.map(lambda a: [float(x) for x in a], counters))

    # -- the parts alone ---------------------------------------------------
    def _attention_alone(self, q, k, v):
        """The program's attention call (the flash kernels, queries and
        keys `nope + rope` wide, values `v`) against the reference's
        float32 latent attention on the SAME operands: q, k [heads, S,
        nope + rope], v [heads, S, v] of a few heads as the layer's own
        step computes them; the reference is handed the two parts of a
        query and a key apart, a head at a time.  The relative norm of the
        difference and how far the ROWS are scaled
        (`benchmark/families/mellum.py`), each the worst over the result
        and the gradients of q, k and v."""
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

        def row_scale(a, b):
            ab = (a.astype(jnp.float32) * b).sum(-1)
            bb = (b * b).sum(-1)
            return jnp.linalg.norm(ab - bb) / jnp.linalg.norm(bb)
        return (jnp.stack([_rel(a, b) for a, b in zip(got, want)]).max(),
                jnp.stack([row_scale(a, b) for a, b in zip(got, want)]).max())

    def _conv_alone(self, qkv, taps):
        """The program's convolution and silu (`kimi_linear._conv`: the
        Pallas kernels, no bias) against the reference's float32 shifted
        sums on the SAME numbers: the first `SCAN_HEADS` heads' columns
        of q, of k and of v in qkv [1, S, 3 W] as the first KDA layer's
        own step computes it (the whole width would hold 8 GB of float32
        copies at 32,768 positions), and their taps.  The relative norm
        of the difference, the worst of the result and the gradients of
        `qkv` and the taps under a fixed random cotangent, the reference
        given the operands as the kernel was (`lfm2._as_given`).  The
        sequence is laid out as TWO of half the length: a tap that reaches
        across a sequence's start reads the first half's last rows
        there."""
        cfg = dataclasses.replace(
            self.cfg, kda_heads=min(SCAN_HEADS, self.cfg.kda_heads))

        def some(t):                    # [..., 3 W] -> [..., 3 W']
            t = t.reshape(*t.shape[:-1], 3, self.cfg.kda_heads, -1)
            return t[..., :cfg.kda_heads, :].reshape(*t.shape[:-3], -1)
        qkv, taps = some(qkv), some(taps)
        qkv = qkv.reshape(2 * qkv.shape[0], qkv.shape[1] // 2, -1)
        g = jax.random.normal(jax.random.fold_in(jax.random.key(0), qkv.size),
                              qkv.shape, jnp.float32).astype(qkv.dtype)

        def program(qkv, taps):
            return jnp.concatenate(kimi_linear._conv(qkv, taps, cfg), -1)
        out, vjp = jax.vjp(program, qkv, taps)
        got = (out, *vjp(g))
        out, vjp = jax.vjp(
            lambda x, w: jax.vmap(reference.conv_silu, (0, None))(x, w),
            _as_given(qkv), taps.astype(jnp.float32))
        want = (out, *vjp(_as_given(g)))
        return jnp.stack([_rel(a, b) for a, b in zip(got, want)]).max()

    def _scan_alone(self, u, lp):
        """The program's scan against the reference's recurrence on the
        first `SCAN_HEADS` heads of a KDA layer, in float32: the operands
        are the reference's own of `u` [S, hidden] (the layer's normed
        input in float32) under the layer's leaves `lp`.  The relative
        norm of the difference, the worst of the result and the gradients
        of q, k, v, g and beta under a fixed random cotangent."""
        mine = reference.heads_of(
            {k: v.astype(jnp.float32) for k, v in lp.items()}, 0,
            min(SCAN_HEADS, self.cfg.kda_heads))
        with jax.default_matmul_precision("highest"):
            operands = reference.kda_operands(u, mine)
            ct = jax.random.normal(jax.random.key(57), operands[2].shape,
                                   jnp.float32)
            out, vjp = jax.vjp(
                lambda *a: reference.recurrence(*a, self.spec["scan_block"]),
                *operands)
            want = (out, *vjp(ct))

            def program(q, k, v, g, beta):
                flat = [t.reshape(1, t.shape[0], -1) for t in (q, k, v, g)]
                return kimi_linear._scan(*flat, beta[None]).reshape(v.shape)
            out, vjp = jax.vjp(program, *operands)
            got = (out, *vjp(ct))
        return jnp.stack([_rel(a, b) for a, b in zip(got, want)]).max()

    def parts_disagreement(self, params, batch):
        """Five parts of the program ALONE, each against the reference's
        float32 on operands that are the same on both sides and are THE
        STEP'S OWN: the first sequence of the batch walked through the
        program's layers as the timed step walks them.

          - `router`, `experts`: as `benchmark/families/lfm2.py` has them,
            the worst expert layer.
          - `attention`, `attention_rows`: `_attention_alone` on the first
            two heads of the first latent-attention layer.
          - `conv`: `_conv_alone` on the first KDA layer's projection.
          - `kda`: `_scan_alone` on the first KDA layer's normed input."""
        cfg, spec = self.cfg, self.spec
        tokens = batch[0][:1]
        router, experts = [], []
        attention = conv = scan = None

        def expert_parts(x, lp):
            m = kimi_linear._ffn_input(x, lp, cfg).reshape(-1, x.shape[-1])
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
            router.append(_rel(weights, want_weights))
            experts.append(_rel(routed, want_routed))

        x = kimi_linear._embed(params, tokens, cfg)
        for (mixer, ffn, n), group in zip(kimi_linear.stack_plan(cfg),
                                          params["layers"]):
            for j in range(n):
                lp = jax.tree.map(lambda a: a[j], group)
                if mixer == kimi_linear.KDA and conv is None:
                    u = kimi_linear._norm(x, lp["input_ln"], cfg)
                    qkv = jnp.einsum("bsd,de->bse", u,
                                     lp["qkv_w"].astype(cfg.dtype))
                    conv = self._conv_alone(qkv, lp["conv_w"])
                    scan = self._scan_alone(u[0].astype(jnp.float32), lp)
                if mixer == kimi_linear.MLA and attention is None:
                    q, k, v = kimi_linear._qkv(x, lp, cfg)
                    attention = self._attention_alone(q[0, :2], k[0, :2],
                                                      v[0, :2])
                x = x + kimi_linear._MIXERS[mixer](x, lp, cfg)
                if ffn == kimi_linear.MOE:
                    expert_parts(x, lp)
                x = x + kimi_linear._feed_forward(x, lp, None, cfg, ffn)[0]
        zero = jnp.zeros((), jnp.float32)
        attention = attention or (zero, zero)
        return {"router_rel_diff": jnp.stack(router or [zero]).max(),
                "experts_rel_diff": jnp.stack(experts or [zero]).max(),
                "attn_rel_diff": attention[0], "attn_row_diff": attention[1],
                "conv_rel_diff": zero if conv is None else conv,
                "kda_rel_diff": zero if scan is None else scan}

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
            + ~(parts["kda_rel_diff"] <= self.kda_rel_tol))
        routing = kimi_linear.routing(frozen, tokens, self.cfg)
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

    def kda_shape(self) -> dict:
        """What `benchmark/reduce/kda_cost.py` needs of one sequence's
        call."""
        return {"tokens": self.seq_len, "heads": self.cfg.kda_heads,
                "key_dim": self.cfg.kda_head_dim,
                "value_dim": self.cfg.kda_head_dim}

    def model_flops_per_sample(self) -> float:
        """Model FLOPs to train on one sequence, forward and backward, no
        recompute: 6 per matmul parameter a token meets on this chip
        (`matmul_params_per_token`), plus latent attention's two matmuls
        over the causal triangle (the first `nope + rope` deep, the second
        `v` wide; 2 FLOPs a multiply-add, three passes), plus the scan's
        chunked products, three times its forward call's
        (`kda_cost.cost`), a KDA layer."""
        n, cfg = self.numbers, self.cfg
        params = matmul_params_per_token(
            n | {"num_experts": cfg.num_experts}, self.layer_types,
            cfg.num_dense_layers, len(cfg.held), n["vocab_size"])
        pairs = self.seq_len * (self.seq_len + 1) // 2
        width = cfg.num_heads * (cfg.qk_head_dim + cfg.v_head_dim)
        layers = {t: self.layer_types.count(t)
                  for t in (kimi_linear.KDA, kimi_linear.MLA)}
        scan = kda_cost.cost("fwd", **self.kda_shape(), chunk=kda.CHUNK)[0]
        return (6.0 * params * self.seq_len
                + layers[kimi_linear.MLA] * 6.0 * pairs * width
                + layers[kimi_linear.KDA] * 3.0 * scan)
