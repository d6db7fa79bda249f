"""The nemotronh family: a `nemotron_h` `config.json` (the causal context
tower of NVIDIA's Nemotron-Labs-TwoTower-30B-A3B-Base) run through the
program's `byteps_tpu.models.nemotron_h` as ONE CHIP'S SHARE of an
expert-parallel deployment and one pipeline stage of it, with the plain
reference of `benchmark/reference/nemotronh.py` beside it, told the same
share.  See `benchmark/families/gpt2.py` for what a family is,
`benchmark/families/afmoe.py` for how a share is written down
(`published` and `held`) and how `correct` is decided where top-k is
discontinuous, and `benchmark/families/granitehybrid.py` for the scan's
own number.

`correct`'s three numbers (loss, worst leaf, norm ratio) are the
harness's; what they cannot tell is ADDED to the reference's loss, 1 a
count, which then fails `loss_rel_tol`:

  - every token whose choice of experts differs from the reference's own
    top-6 by a gap of `selection_eps` or more in the scores;
  - `scan_rel_tol`: the program's scan ALONE on float32 operands against
    the reference's recurrence in what the CARRIED STATE gives, as
    granite's, now with 8 groups of B and C (`scan_disagreement`);
  - `router_rel_tol`, `experts_rel_tol`, `attn_row_tol`: the router, the
    held experts' two products at width 1856 and one attention call,
    each alone on the step's own operands (`parts_disagreement`).

What the existing readers ask of a family is here under the names they
use: `cfg` (with `.moe`, `.held`, `.num_experts`, `.num_experts_per_tok`,
`.moe_intermediate_size`), `seq_len`, `routing_counters`, `selection`,
`scan_shape`, and the model FLOPs of a sample.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax import lax

from benchmark.reduce import ssd_cost
from benchmark.reference import nemotronh as reference
from byteps_tpu.models import granite_hybrid, nemotron_h
from byteps_tpu.ops import ssd
from byteps_tpu.parallel import dropless_moe

MAMBA, MOE, ATTENTION = nemotron_h.MAMBA, nemotron_h.MOE, nemotron_h.ATTENTION


def matmul_params_per_token(n: dict, kinds, held_experts: int,
                            held_vocab: int) -> float:
    """Parameters of the matrices a token is multiplied by, on this chip:
    a mixer's `in_proj` and `out_proj`; an expert layer's router, its
    shared expert's two and the routed experts a token meets HERE (its
    `num_experts_per_tok` choices fall on the held experts in
    proportion: 0.375 experts' worth where a sixteenth is held and a
    token takes 6); an attention layer's four projections; the held rows
    of the head.  The embedding is a lookup; the convolution, the norms
    and the scan's own leaves are no matrices."""
    D = n["hidden_size"]
    inner = n["mamba_num_heads"] * n["mamba_head_dim"]
    conv_dim = inner + 2 * n["n_groups"] * n["ssm_state_size"]
    mamba = D * (inner + conv_dim + n["mamba_num_heads"]) + inner * D
    routed = n["num_experts_per_tok"] * held_experts / n["n_routed_experts"]
    moe = (D * n["n_routed_experts"]
           + 2 * D * n["moe_shared_expert_intermediate_size"]
           + 2 * D * n["moe_intermediate_size"] * routed)
    size = n["head_dim"]
    attn = (D * (n["num_attention_heads"] + 2 * n["num_key_value_heads"])
            * size + n["num_attention_heads"] * size * D)
    per = {MAMBA: mamba, MOE: moe, ATTENTION: attn}
    return sum(per[k] for k in kinds) + held_vocab * D


class Family:
    unit = "tokens"

    def __init__(self, config: dict, job: dict):
        published = config["published"]
        n = {**published, **config["held"]}
        self.numbers = n
        options = config["program_options"]["pinned"]
        self.seq_len = int(job["seq_len"])
        if self.seq_len > n["max_position_embeddings"]:
            raise ValueError(f"seq_len {self.seq_len} is beyond the model's "
                             f"{n['max_position_embeddings']} positions")
        pattern = nemotron_h.kinds_of(published["hybrid_override_pattern"])
        if (len(pattern) != published["num_hidden_layers"]
                or len(n["layers"]) != n["num_hidden_layers"]
                or len(n["experts"]) != n["n_routed_experts"]):
            raise ValueError("the configuration's counts disagree with its "
                             "pattern or its lists")
        if (n["mlp_hidden_act"], n["n_shared_experts"], n["n_group"],
                n["topk_group"], n["norm_topk_prob"]) != (
                    "relu2", 1, 1, 1, True):
            raise ValueError("nemotronh family: relu2 experts, one shared "
                             "expert, no group limit and normed weights "
                             "are what is written here")
        self.kinds = tuple(pattern[i] for i in n["layers"])
        self.cfg = nemotron_h.NemotronHConfig(
            vocab_size=n["vocab_size"], vocab_start=n["vocab_start"],
            hidden_size=n["hidden_size"], layer_kinds=self.kinds,
            num_heads=n["num_attention_heads"],
            num_kv_heads=n["num_key_value_heads"], head_dim=n["head_dim"],
            mamba_n_heads=n["mamba_num_heads"],
            mamba_d_head=n["mamba_head_dim"],
            mamba_d_state=n["ssm_state_size"], mamba_n_groups=n["n_groups"],
            mamba_d_conv=n["conv_kernel"], mamba_chunk_size=n["chunk_size"],
            moe_intermediate_size=n["moe_intermediate_size"],
            moe_shared_intermediate_size=n[
                "moe_shared_expert_intermediate_size"],
            num_experts=published["n_routed_experts"],
            num_experts_per_tok=n["num_experts_per_tok"],
            held_experts=tuple(n["experts"]),
            route_scale=n["routed_scaling_factor"],
            rms_norm_eps=n["layer_norm_epsilon"], **options)
        self.reference_check = config["reference_check"]
        self.spec = {
            "layer_kinds": self.kinds,
            "heads": n["num_attention_heads"],
            "kv_heads": n["num_key_value_heads"], "head_dim": n["head_dim"],
            "mamba_heads": n["mamba_num_heads"],
            "mamba_head_dim": n["mamba_head_dim"],
            "mamba_state": n["ssm_state_size"],
            "mamba_groups": n["n_groups"],
            "top_k": n["num_experts_per_tok"], "held": tuple(n["experts"]),
            "route_scale": n["routed_scaling_factor"],
            "eps": n["layer_norm_epsilon"], "vocab_start": n["vocab_start"],
            **self.reference_check["reference_blocks"]}
        self.units_per_sample = self.seq_len
        for name in ("selection_eps", "scan_rel_tol", "router_rel_tol",
                     "experts_rel_tol", "attn_row_tol"):
            setattr(self, name, float(self.reference_check[name]))
        self.selection, self.routing_counters = [], []
        opt = job["optimizer"]
        if opt["name"] != "adamw":
            raise ValueError(f"nemotronh family: no optimizer "
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
        params = nemotron_h.init_params(key, self.cfg)
        params["embed"] = params["embed"] * self._embed_rows_times
        return params

    def make_batch(self, key, n_samples: int):
        return nemotron_h.synthetic_batch(key, n_samples, self.seq_len,
                                          self.cfg)

    def loss(self, params, batch):
        return nemotron_h.loss_fn(params, batch, self.cfg)

    def _record(self, selection, counters):
        self.selection.append(jax.tree.map(float, selection))
        self.routing_counters.append(
            jax.tree.map(lambda a: [float(x) for x in a], counters))

    # -- the parts alone ---------------------------------------------------
    def scan_shape(self) -> dict:
        """What `benchmark/reduce/ssd_cost.py` needs of one sequence's
        scan."""
        c = self.cfg
        return dict(tokens=self.seq_len, heads=c.mamba_n_heads,
                    head_dim=c.mamba_d_head, state=c.mamba_d_state,
                    groups=c.mamba_n_groups,
                    chunk=min(c.mamba_chunk_size, self.seq_len))

    def scan_disagreement(self, u, p):
        """The program's scan on float32 operands against the reference's
        recurrence, in what the carried state alone gives, as
        `benchmark/families/granitehybrid.py` says it: the operands are a
        mamba layer's on its own normed input `u` (float32, `p` the
        layer's float32 leaves), x zero after the first chunk, so that
        every later position's result is state handed from chunk to
        chunk; the median over the heads of a head's difference over its
        size.  Each head reads its own group's B and C: one group's given
        to all reads as a state that is wrong in 56 heads of 64."""
        x, dt, a, bm, cm, d = reference.scan_operands(u, p, self.spec)[0]
        chunk = self.scan_shape()["chunk"]
        if x.shape[1] <= chunk:
            return jnp.zeros((), jnp.float32)
        x = x.at[:, chunk:].set(0.0)
        no_d = jnp.zeros_like(d)
        want = reference.recurrence(
            x, dt, a, bm, cm, no_d, self.spec["scan_segment"])[:, chunk:]
        got = ssd.ssd_scan(x, dt, a, bm, cm, no_d, chunk=chunk)[:, chunk:]

        def per_head(t):                      # [B, S, H, P] -> [H]
            return jnp.sqrt((t * t).sum((0, 1, 3)))
        size = per_head(want)
        live = size >= 1e-3 * size.max()
        return jnp.nanmedian(jnp.where(live, per_head(got - want) / size,
                                       jnp.nan))

    def _attention_alone(self, q, k, v):
        """The program's attention call (at the cell's length the
        STREAMING kernels) against the reference's float32 attention on
        the SAME operands: q [group, S, size], k and v [1, S, size], one
        key-value head's group as the layer's own step computes them.
        Two numbers as `benchmark/families/mellum.py` reads them: the
        relative norm of the difference, and how far the ROWS are
        scaled, each the worst over the result and the gradients of q, k
        and v."""
        cfg, (group, S, size) = self.cfg, q.shape
        g = jax.random.normal(
            jax.random.fold_in(jax.random.key(0), size * S + group),
            q.shape, jnp.float32).astype(q.dtype)

        def program(q, k, v):
            k, v = (jnp.repeat(t, group, axis=0) for t in (k, v))
            return granite_hybrid._attend(q[None], k[None], v[None], cfg)[0]

        block = min(self.spec["q_block"], S)

        def plain(q, k, v):
            @jax.checkpoint
            def rows(start):
                qb = lax.dynamic_slice_in_dim(q, start, block, axis=1)
                return reference.attention(qb[None], k, v, start)[0]
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
        """Four parts of the program ALONE, each against the reference's
        float32 on operands that are the same on both sides and are THE
        STEP'S OWN: the first sequence of `tokens` walked through the
        program's layers as the timed step walks them.

          - `scan`: `scan_disagreement` on the first mamba layer's normed
            input.
          - `router`: `dropless_moe.route` on the float32 of each expert
            layer's normed input against the reference's weights at the
            same choice; the relative norm of the [T, k] weights, worst
            layer.
          - `experts`: `dropless_moe.held_experts` on each expert layer's
            normed input (bfloat16 in the step) against the reference's
            held experts on the float32 of the same numbers, at the same
            choice; the relative norm of the [T, D] result, worst layer.
          - `attention`, `attention_rows`: `_attention_alone` on the first
            key-value head's group of the first attention layer."""
        cfg, spec = self.cfg, self.spec
        group = cfg.num_heads // cfg.num_kv_heads
        x = nemotron_h._embed(params, tokens[:1], cfg)
        scan, attention, router, experts = None, None, [], []

        def rel(a, b):
            return jnp.linalg.norm(a.astype(jnp.float32) - b) / (
                jnp.linalg.norm(b))
        highest = jax.default_matmul_precision("highest")
        for kind, j in nemotron_h.layer_plan(cfg):
            lp = jax.tree.map(lambda a: a[j], params[kind])
            plain = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
            if kind == MAMBA and scan is None:
                # float32 operands: the reference's and the `jnp` form's
                # products at `highest`, which the kernels' float32
                # products read the same as on the chip
                with highest:
                    u = reference.rms_norm(x.astype(jnp.float32),
                                           plain["input_ln"], spec["eps"])
                    scan = self.scan_disagreement(u, plain)
            if kind == ATTENTION and attention is None:
                q, k, v = granite_hybrid._qkv(x, lp, cfg)
                attention = self._attention_alone(
                    q[0, :group], k[0, :1], v[0, :1])
            if kind == MOE:
                m = nemotron_h._experts_input(x, lp, cfg)
                m32 = m.astype(jnp.float32)
                sel, weights = dropless_moe.route(
                    m32, lp["router_w"], cfg.moe)
                routed, _ = dropless_moe.held_experts(
                    m, lp["router_w"],
                    {"up_w": lp["expert_up_w"],
                     "down_w": lp["expert_down_w"]}, cfg.moe, sel=sel)
                with highest:
                    want_weights = reference.chosen_weights(
                        jax.nn.sigmoid(m32 @ plain["router_w"]), sel,
                        spec["route_scale"])
                    want_routed, _ = reference.routed_experts(
                        m32, plain, spec, sel)
                router.append(rel(weights, want_weights))
                experts.append(rel(routed, want_routed))
            x, _ = nemotron_h._layer(x, lp, None, cfg, kind)
        zero = jnp.zeros((), jnp.float32)
        whole, rows = attention if attention is not None else (zero, zero)
        return {"scan_rel_diff": zero if scan is None else scan,
                "router_rel_diff": jnp.stack(router or [zero]).max(),
                "experts_rel_diff": jnp.stack(experts or [zero]).max(),
                "attn_rel_diff": whole, "attn_row_diff": rows}

    def reference_loss(self, params, batch):
        """The reference's loss at the program's choice of experts, plus
        the number of tokens whose choice rounding does not explain, plus
        1 for each part of the program that alone is further from float32
        than its limit (`parts_disagreement`)."""
        tokens = batch[0]
        frozen = lax.stop_gradient(params)
        sel = None
        if self.cfg.count(MOE):
            routing = nemotron_h.routing(frozen, tokens, self.cfg)
            sel = routing.sel
        value, stats = reference.loss(params, batch, self.spec, sel=sel,
                                      with_stats=True)
        parts = self.parts_disagreement(frozen, tokens)
        off = ((parts["scan_rel_diff"] > self.scan_rel_tol).astype(jnp.int32)
               + (parts["router_rel_diff"] > self.router_rel_tol)
               + (parts["experts_rel_diff"] > self.experts_rel_tol)
               + (parts["attn_row_diff"] > self.attn_row_tol))
        if sel is not None:
            gaps = stats["gaps"]                           # [layers, T]
            unexplained = (gaps >= self.selection_eps).sum()
            selection = {
                "tokens": gaps.size,
                "swapped_share": stats["swapped_tokens"].sum() / gaps.size,
                "max_gap": gaps.max(), "unexplained_tokens": unexplained,
                **parts}
            counters = jax.vmap(
                lambda r: dropless_moe.counters(r, tokens.size))(routing)
            jax.debug.callback(self._record, selection, counters)
            off = off + unexplained
        return value + lax.stop_gradient(off.astype(jnp.float32))

    def model_flops_per_sample(self) -> float:
        """Model FLOPs to train on one sequence, forward and backward, no
        recompute: 6 per matmul parameter a token meets on this chip
        (`matmul_params_per_token`); the scan's products at what the
        chunked form needs (`ssd_cost.model_flops`), a mamba layer;
        attention's two matmuls over the causal triangle, 2 FLOPs a
        multiply-add, three passes, an attention layer."""
        n = self.numbers
        params = matmul_params_per_token(
            n | {"n_routed_experts": self.cfg.num_experts}, self.kinds,
            len(self.cfg.held), n["vocab_size"])
        pairs = self.seq_len * (self.seq_len + 1) // 2
        width = n["num_attention_heads"] * n["head_dim"]
        return (6.0 * params * self.seq_len
                + self.cfg.count(MAMBA)
                * ssd_cost.model_flops(**self.scan_shape())
                + self.cfg.count(ATTENTION) * 12.0 * pairs * width)
