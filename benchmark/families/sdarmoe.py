"""The sdarmoe family: an `sdar_moe` `config.json` (JetLM's SDAR) TRAINED
BY BLOCK DIFFUSION through the program's `byteps_tpu.models.sdar` as ONE
CHIP'S SHARE of an expert-parallel deployment, with the plain reference
of `benchmark/reference/sdarmoe.py` beside it, told the same share and
handed the same noise (a batch is `(tokens, masked, weight)`).  See
`benchmark/families/gpt2.py` for what a family is,
`benchmark/families/afmoe.py` for how a share is written down
(`published` and `held`) and `benchmark/families/mellum.py` for how
`correct` is decided where top-k is discontinuous and for the parts
compared alone.

A sample is one sequence of `seq_len` TOKENS, and `tokens_per_s` counts
those, each once; the layers run 2 x `seq_len` ROWS of it, the clean copy
and the noised one, and the routers' counters are per row.

What the existing readers ask of a family is here under the names they
use: `cfg` (with `.moe`, `.held`, `.num_experts`, `.num_experts_per_tok`,
`.moe_intermediate_size`), `seq_len`, `routing_counters`, `selection`, and
the model FLOPs of a sample with attention counted at the pairs the mask
needs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax import lax

from benchmark.reduce.bd_cost import needed_pairs
from benchmark.reference import sdarmoe as reference
from byteps_tpu.models import afmoe, mellum, sdar
from byteps_tpu.parallel import dropless_moe


class Family:
    unit = "tokens"

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
                or len(n["experts"]) != n["num_experts"]
                or published["mlp_only_layers"]
                or published["decoder_sparse_step"] != 1
                or published["rope_scaling"] is not None):
            raise ValueError("the configuration's `held` counts disagree "
                             "with its lists, a layer is not sparse, or the "
                             "rotary positions are scaled")
        self.cfg = sdar.SdarConfig(
            vocab_size=n["vocab_size"], vocab_start=n["vocab_start"],
            hidden_size=n["hidden_size"],
            num_heads=n["num_attention_heads"],
            num_kv_heads=n["num_key_value_heads"], head_dim=n["head_dim"],
            moe_intermediate_size=n["moe_intermediate_size"],
            num_experts=published["num_experts"],
            num_experts_per_tok=n["num_experts_per_tok"],
            held_experts=tuple(n["experts"]), num_layers=len(n["layers"]),
            block_length=int(assumed["block_length"]),
            noise_eps=float(assumed["noise_eps"]),
            norm_topk_prob=n["norm_topk_prob"],
            rms_norm_eps=n["rms_norm_eps"],
            rope_theta=float(n["rope_theta"]), **options)
        self.spec = {
            "heads": n["num_attention_heads"],
            "kv_heads": n["num_key_value_heads"], "head_dim": n["head_dim"],
            "top_k": n["num_experts_per_tok"], "held": tuple(n["experts"]),
            "norm_topk_prob": n["norm_topk_prob"], "eps": n["rms_norm_eps"],
            "theta": float(n["rope_theta"]),
            "vocab_start": n["vocab_start"],
            "mask_token": self.cfg.mask_token,
            "block_length": self.cfg.block_length, "q_block": 64,
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
            raise ValueError(f"sdarmoe family: no optimizer {opt['name']!r}")
        self._learning_rate = float(opt["learning_rate"])
        self._embed_rows_times = float(
            config["initial_weights"]["embed_rows_times"])
        self._mask_row_times = float(
            config["initial_weights"]["mask_row_times"])
        self._q_norm_times = float(
            config["initial_weights"]["q_norm_times"])

    def optimizer(self) -> optax.GradientTransformation:
        return optax.adamw(self._learning_rate)

    def init(self, key):
        """The program's own initial weights, the embedding's rows times
        the cell's `initial_weights.embed_rows_times` and the MASK token's
        row times `mask_row_times` (the configuration says why, and what
        was measured: a quarter of the rows the layers run are that one
        row)."""
        params = sdar.init_params(key, self.cfg)
        mask = self.cfg.mask_token - self.cfg.vocab_start
        params["embed"] = (params["embed"] * self._embed_rows_times).at[
            mask].set(params["embed"][mask] * self._mask_row_times)
        params["moe"]["q_norm"] = params["moe"]["q_norm"] * self._q_norm_times
        return params

    def make_batch(self, key, n_samples: int):
        return sdar.synthetic_batch(key, n_samples, self.seq_len, self.cfg)

    def loss(self, params, batch):
        return sdar.loss_fn(params, batch, self.cfg)

    def _record(self, selection, counters, noise):
        self.selection.append(jax.tree.map(float, selection))
        self.routing_counters.append(
            jax.tree.map(lambda a: [float(x) for x in a], counters))
        sdar.record_batch(noise)

    def _attention_alone(self, q, k, v):
        """The program's attention call under the block-diffusion mask
        (at the cell's length the STREAMING kernels on the table of live
        tiles) against the reference's float32 attention on the SAME
        operands: q [group, 2 L, size], k and v [1, 2 L, size], one
        key-value head's group as the layer's own step computes them.
        Two numbers, each the worst over the result and the gradients of
        q, k and v: the relative norm of the difference, and how far the
        ROWS are scaled (`benchmark/families/mellum.py`
        `_attention_alone` says why rows)."""
        cfg, (group, S, size) = self.cfg, q.shape
        g = jax.random.normal(
            jax.random.fold_in(jax.random.key(0), size * S + group),
            q.shape, jnp.float32).astype(q.dtype)
        attend = afmoe._attn_fn(cfg, afmoe.BLOCK_DIFFUSION)

        def program(q, k, v):
            k, v = (jnp.repeat(t, group, axis=0) for t in (k, v))
            return attend(q[None], k[None], v[None])[0]

        block = min(self.spec["q_block"], S // 2)

        def plain(q, k, v):
            @jax.checkpoint
            def rows(start):
                qb = lax.dynamic_slice_in_dim(q, start, block, axis=1)
                return reference.attention(qb[None], k, v, start,
                                           cfg.block_length)[0]
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

    def parts_disagreement(self, params, batch):
        """Three parts of the program ALONE, each against the reference's
        float32 on operands that are the same on both sides and are THE
        STEP'S OWN: the two copies of the first sequence of `batch` walked
        through the program's layers as the timed step walks them
        (`benchmark/families/mellum.py` says why alone).

          - `router`, `experts`: as mellum's, the worst layer.
          - `attention`, `attention_rows`: `_attention_alone` on the first
            key-value head's group of the first layer."""
        cfg, spec = self.cfg, self.spec
        group = cfg.num_heads // cfg.num_kv_heads
        first = jax.tree.map(lambda a: a[:1], batch)
        x, positions = sdar.two_copies(params, first, cfg)
        router, experts, attention = [], [], None

        def rel(a, b):
            return jnp.linalg.norm(a.astype(jnp.float32) - b) / (
                jnp.linalg.norm(b))
        for i, kind in enumerate(cfg.layer_types):
            lp = jax.tree.map(lambda a: a[i], params["moe"])
            plain = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
            if attention is None:
                q, k, v = sdar._qkv(x, lp, cfg, positions)
                attention = self._attention_alone(
                    q[0, :group], k[0, :1], v[0, :1])
            x = sdar._attention(x, lp, cfg, kind, positions)
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
            x, _ = mellum._experts(x, lp, None, cfg, family="sdar")
        return {"router_rel_diff": jnp.stack(router).max(),
                "experts_rel_diff": jnp.stack(experts).max(),
                "attn_rel_diff": attention[0], "attn_row_diff": attention[1]}

    def reference_loss(self, params, batch):
        """The reference's loss at the program's choice of experts, plus
        the number of rows whose choice rounding does not explain, plus 1
        for each part of the program that alone is further from float32
        than its limit (`parts_disagreement`)."""
        held = lax.stop_gradient(params)
        routing = sdar.routing(held, batch, self.cfg)
        value, stats = reference.loss(params, batch, self.spec,
                                      sel=routing.sel, with_stats=True)
        gaps = stats["gaps"]                               # [layers, rows]
        unexplained = (gaps >= self.selection_eps).sum()
        parts = self.parts_disagreement(held, batch)
        selection = {
            "tokens": gaps.size,
            "swapped_share": stats["swapped_tokens"].sum() / gaps.size,
            "max_gap": gaps.max(), "unexplained_tokens": unexplained,
            **parts}
        rows = 2 * batch[0].size
        counters = jax.vmap(
            lambda r: dropless_moe.counters(r, rows))(routing)
        jax.debug.callback(self._record, selection, counters,
                           sdar.batch_counters(batch))
        off = (unexplained
               + (parts["router_rel_diff"] > self.router_rel_tol)
               + (parts["experts_rel_diff"] > self.experts_rel_tol)
               + (parts["attn_row_diff"] > self.attn_row_tol))
        return value + lax.stop_gradient(off.astype(jnp.float32))

    def model_flops_per_sample(self) -> float:
        """Model FLOPs to train on one sequence of L tokens, forward and
        backward, no recompute: 6 per matmul parameter a ROW meets on this
        chip (`mellum.matmul_params_per_token`'s count, written out) over
        the 2 L rows the layers run and over the L rows the head reads,
        plus attention's two matmuls over the pairs the mask NEEDS, L^2 +
        L beta a head: not the causal call's over 2 L rows, not the
        square's.  2 FLOPs a multiply-add, two matmuls, three passes."""
        n, cfg, L = self.numbers, self.cfg, self.seq_len
        D, size = n["hidden_size"], n["head_dim"]
        H, Hkv = n["num_attention_heads"], n["num_key_value_heads"]
        routed = cfg.num_experts_per_tok * len(cfg.held) / cfg.num_experts
        layer = (D * (H + 2 * Hkv) * size + H * size * D
                 + D * cfg.num_experts
                 + 3 * D * n["moe_intermediate_size"] * routed)
        pairs = needed_pairs(L, cfg.block_length)
        return (6.0 * cfg.num_layers * layer * 2 * L
                + 6.0 * n["vocab_size"] * D * L
                + 12.0 * cfg.num_layers * pairs * H * size)
