"""The keye family: the language decoder of a `KeyeVL2` `config.json`
(Kwai-Keye's Keye-VL-2.0) run through the program's
`byteps_tpu.models.keye` as ONE CHIP'S SHARE of an expert-parallel
deployment, with the plain reference of `benchmark/reference/keye.py`
beside it, told the same share.  See `benchmark/families/gpt2.py` for what
a family is and `benchmark/families/afmoe.py` for how a share is written
down (`published` and `held`).

How `correct` is decided.  This decoder makes TWO discontinuous choices a
layer, the router's 8 of 128 experts and the indexer's 2,048 of up to
32,768 keys, and bfloat16 activations move both a little.  So the
comparison is parted twice over:

  - the arithmetic: the reference computes its own index scores and
    router scores in float32 but attends over the keys and runs the
    experts THE PROGRAM chose (`keye.chosen_keys`, the attention kernels'
    own mask; `keye.routing`).  Loss and every gradient leaf are then held
    to the configuration's tolerances;
  - the choices: every token whose experts differ from the reference's
    own top-8 by a logit gap of `selection_eps` or more, and every row
    whose keys differ from the reference's own top-2048 by a score gap of
    `index_selection_eps` or more (the best score it left out over the
    worst it took, in the reference's float32 scores), ADDS 1 to the
    reference's loss; so does every row whose attention did not keep
    exactly min(t + 1, topk) keys (the program's counter);
  - four parts ALONE, on operands that are the step's own and the same on
    both sides (`parts_disagreement`): the index scores, the attention
    kernels over a given selection, the router, the held experts.  Each
    has a limit of its own and adds 1 when past it.

`selection` keeps what the reference saw, a record a sample, and
`routing_counters` the program's own counters, under the names the
`route.*` and `sparse.*` readers use.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax import lax

from benchmark.reduce import sparse_cost
from benchmark.reference import keye as reference
from byteps_tpu.models import keye, mellum
from byteps_tpu.models.transformer import _rms_norm
from byteps_tpu.ops import sparse_attention
from byteps_tpu.parallel import dropless_moe


def grid_positions(tokens):
    """Three streams that DIFFER, [3, B, S]: what a sequence of image
    patches 16 wide would give (time, row, column).  No cell sends them;
    the tests and one broken variant do."""
    B, S = tokens.shape
    t = jnp.arange(S, dtype=jnp.int32)
    return jnp.broadcast_to(jnp.stack([t, t // 16, t % 16])[:, None],
                            (3, B, S))


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
                or published["mlp_only_layers"]
                or published["decoder_sparse_step"] != 1
                or published["use_sliding_window"]):
            raise ValueError("the configuration's `held` counts disagree "
                             "with its lists, or a layer is not sparse, or "
                             "has a window")
        sa, rope = n["sa_config"], n["rope_scaling"]
        if sa["indexer_num_kv_heads"] != 1 or rope["rope_type"] != "default":
            raise ValueError("one indexer key head and plain rotary "
                             "frequencies are what is written here")
        self.cfg = keye.KeyeConfig(
            vocab_size=n["vocab_size"], vocab_start=n["vocab_start"],
            hidden_size=n["hidden_size"],
            num_heads=n["num_attention_heads"],
            num_kv_heads=n["num_key_value_heads"], head_dim=n["head_dim"],
            moe_intermediate_size=n["moe_intermediate_size"],
            num_experts=published["num_experts"],
            num_experts_per_tok=n["num_experts_per_tok"],
            held_experts=tuple(n["experts"]), num_layers=len(n["layers"]),
            index_heads=sa["indexer_num_heads"],
            index_head_dim=sa["indexer_head_dim"], index_topk=sa["topk"],
            mrope_section=tuple(rope["mrope_section"]),
            norm_topk_prob=n["norm_topk_prob"],
            rms_norm_eps=n["rms_norm_eps"], rope_theta=float(n["rope_theta"]),
            **options)
        self.spec = {
            "heads": n["num_attention_heads"],
            "kv_heads": n["num_key_value_heads"], "head_dim": n["head_dim"],
            "index_heads": sa["indexer_num_heads"],
            "index_head_dim": sa["indexer_head_dim"], "topk": sa["topk"],
            "sections": tuple(rope["mrope_section"]),
            "top_k": n["num_experts_per_tok"], "held": tuple(n["experts"]),
            "norm_topk_prob": n["norm_topk_prob"], "eps": n["rms_norm_eps"],
            "theta": float(n["rope_theta"]),
            "vocab_start": n["vocab_start"], "q_block": 64,
            "ce_block": 2048}
        self.units_per_sample = self.seq_len
        self.reference_check = config["reference_check"]
        self.limits = {k: float(self.reference_check[k]) for k in (
            "selection_eps", "index_selection_eps", "index_rel_tol",
            "attn_row_tol", "router_rel_tol", "experts_rel_tol")}
        self.selection, self.routing_counters = [], []
        # None: a text batch's streams; a function of the tokens: others
        self.positions = None
        opt = job["optimizer"]
        if opt["name"] != "adamw":
            raise ValueError(f"keye family: no optimizer {opt['name']!r}")
        self._learning_rate = float(opt["learning_rate"])
        self._embed_rows_times = float(
            config["initial_weights"]["embed_rows_times"])

    def optimizer(self) -> optax.GradientTransformation:
        return optax.adamw(self._learning_rate)

    def init(self, key):
        """The program's own initial weights, the embedding's rows times
        the cell's `initial_weights.embed_rows_times` (which says why)."""
        params = keye.init_params(key, self.cfg)
        params["embed"] = params["embed"] * self._embed_rows_times
        return params

    def make_batch(self, key, n_samples: int):
        return keye.synthetic_batch(key, n_samples, self.seq_len, self.cfg)

    def _streams(self, tokens):
        return None if self.positions is None else self.positions(tokens)

    def loss(self, params, batch):
        return keye.loss_fn(params, batch, self.cfg,
                            positions=self._streams(batch[0]))

    def _record(self, selection, counters):
        self.selection.append(jax.tree.map(float, selection))
        self.routing_counters.append(
            jax.tree.map(lambda a: [float(x) for x in a], counters))

    def _attention_alone(self, q, k, v, index, words):
        """The program's attention kernels over the selection they are
        given (`sparse_fwd`, `sparse_dq`, `sparse_dkv`: one key-value
        head's group, q [G, S, size], k and v [1, S, size], the indexer's
        operands `index` = (qi, kit, aux) of the same sequence) against
        the reference's float32 attention on the SAME operands under the
        SAME mask (`words`, the kernels' own, packed).  Two numbers, each
        the worst over the result and the gradients of q, k and v: the
        relative norm of the difference, and how far the ROWS are scaled
        (`benchmark/families/mellum.py` `_attention_alone` says why)."""
        group, S, size = q.shape
        g = jax.random.normal(
            jax.random.fold_in(jax.random.key(0), size * S + group),
            q.shape, jnp.float32).astype(q.dtype)
        cfg = self.cfg

        def program(q, k, v):
            return sparse_attention.sparse_attention(
                q[None], k[None], v[None], *index, None, cfg.attn_block,
                cfg.attn_block_k)[0][0]

        block = min(self.spec["q_block"], S)

        def plain(q, k, v):
            @jax.checkpoint
            def rows(start):
                qb = lax.dynamic_slice_in_dim(q, start, block, axis=1)
                keep = reference.unpack(
                    lax.dynamic_slice_in_dim(words, start, block), S)
                return reference.attention(qb[None], k, v, keep)[0]
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

    def _index_alone(self, qi, ki, w):
        """The program's index scores of the sequence's LAST rows (128, or
        512 of a long one) against every key, as its kernels compute a
        tile (`sparse_attention.index_rows`, or the einsum where no kernel
        runs), against the reference's float32 scores of the SAME
        operands; the relative norm over the pairs a row sees."""
        S = ki.shape[1]
        rows = min(512, S) if S % 128 == 0 else S
        qi, w = qi[:, :, S - rows:], w[:, S - rows:]
        if self.cfg.attn_impl == "flash":
            got = sparse_attention.index_rows(
                qi, ki.transpose(0, 2, 1), w, self.cfg.attn_block_k)[0]
        else:
            got = sparse_attention.index_scores(qi, ki, w)[0, :, :]
        with jax.default_matmul_precision("highest"):
            want = reference.index_scores(
                qi[0].astype(jnp.float32), ki[0].astype(jnp.float32), w[0])
        seen = (jnp.arange(S)[None, :]
                <= (S - rows + jnp.arange(rows))[:, None])
        return (jnp.linalg.norm(jnp.where(seen, got - want, 0.0))
                / jnp.linalg.norm(jnp.where(seen, want, 0.0)))

    def parts_disagreement(self, params, tokens, words):
        """Four parts of the program ALONE, each against the reference's
        float32 on operands that are the same on both sides and are THE
        STEP'S OWN: the first sequence of `tokens` walked through the
        program's layers as the timed step walks them.

          - `index`: `_index_alone` on the first layer's indexer operands.
          - `attention`, `attention_rows`: `_attention_alone` on the first
            key-value head's group of the first layer, under the
            program's own selection `words` [S, S / 32] of that layer.
          - `router`, `experts`: as `benchmark/families/mellum.py`'s, the
            worst layer."""
        cfg, spec = self.cfg, self.spec
        group = cfg.num_heads // cfg.num_kv_heads
        streams = self._streams(tokens[:1])
        x = keye._embed(params, tokens[:1], cfg)
        router, experts, first = [], [], {}

        def rel(a, b):
            return jnp.linalg.norm(a.astype(jnp.float32) - b) / (
                jnp.linalg.norm(b))
        for i, kind in enumerate(cfg.layer_types):
            lp = jax.tree.map(lambda a: a[i], params["moe"])
            plain = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
            if not first:
                a = _rms_norm(x, lp["input_ln"], None,
                                   eps=cfg.rms_norm_eps)
                q, k, v = keye._qkv(a, lp, cfg, streams)
                qi, ki, w = keye._index(a, lp, cfg, streams)
                first["index"] = self._index_alone(qi, ki, w)
                if cfg.attn_impl == "flash":
                    kit = ki.transpose(0, 2, 1)
                    aux = sparse_attention.select(
                        qi, kit, w, cfg.index_topk, cfg.attn_block_k)
                    first["attention"] = self._attention_alone(
                        q[0, :group], k[0, :1], v[0, :1], (qi, kit, aux),
                        words)
                else:
                    first["attention"] = (jnp.zeros(()), jnp.zeros(()))
            x, _ = keye._attention(x, lp, cfg, kind, streams)
            m = mellum._experts_input(x, lp, cfg)
            m32 = m.astype(jnp.float32)
            sel, weights = dropless_moe.route(m32, lp["router_w"], cfg.moe)
            held = {n: lp["expert_" + n] for n in ("gate_w", "up_w",
                                                   "down_w")}
            routed, _ = dropless_moe.held_experts(
                m, lp["router_w"], held, cfg.moe, sel=sel)
            with jax.default_matmul_precision("highest"):
                want_weights = reference.chosen_weights(
                    jax.nn.softmax(m32 @ plain["router_w"], -1), sel,
                    spec["norm_topk_prob"])
                want_routed, _ = reference.experts_layer(m32, plain, spec,
                                                         sel)
            router.append(rel(weights, want_weights))
            experts.append(rel(routed, want_routed))
            x = x + routed.reshape(x.shape)
        return {"router_rel_diff": jnp.stack(router).max(),
                "experts_rel_diff": jnp.stack(experts).max(),
                "index_rel_diff": first["index"],
                "attn_rel_diff": first["attention"][0],
                "attn_row_diff": first["attention"][1]}

    def reference_loss(self, params, batch):
        """The reference's loss at the program's choice of experts and of
        keys, plus the number of tokens and of rows whose choice rounding
        does not explain, plus the rows whose attention kept another
        number of keys than min(t + 1, topk), plus 1 for each part of the
        program that alone is further from float32 than its limit."""
        tokens = batch[0]
        cfg, limits = self.cfg, self.limits
        held = lax.stop_gradient(params)
        streams = self._streams(tokens)
        routing, kept = keye.forward_hidden(
            held, tokens, cfg, with_routing=True, positions=streams)[1]
        words = keye.chosen_keys(held, tokens, cfg, streams)
        value, stats = reference.loss(
            params, batch, self.spec, sel=routing.sel, keys=words,
            positions=streams, with_stats=True)
        gaps, key_gaps = stats["gaps"], stats["key_gaps"]
        unexplained = (gaps >= limits["selection_eps"]).sum()
        unexplained_rows = (key_gaps >= limits["index_selection_eps"]).sum()
        want = jnp.minimum(jnp.arange(tokens.shape[1]) + 1, cfg.index_topk)
        miscounted = (kept != want).sum()
        parts = self.parts_disagreement(held, tokens, words[0, 0])
        selection = {
            "tokens": gaps.size,
            "swapped_share": stats["swapped_tokens"].sum() / gaps.size,
            "max_gap": gaps.max(), "unexplained_tokens": unexplained,
            "key_swapped_share": (key_gaps > 0).sum() / key_gaps.size,
            "max_key_gap": key_gaps.max(),
            "key_gap_p99": jnp.quantile(key_gaps.reshape(-1), 0.99),
            "unexplained_rows": unexplained_rows,
            "miscounted_rows": miscounted,
            "selected_share": kept.sum() / (
                kept.shape[0] * kept.shape[1]
                * (kept.shape[2] * (kept.shape[2] + 1) / 2)),
            **parts}
        counters = jax.vmap(
            lambda r: dropless_moe.counters(r, tokens.size))(routing)
        jax.debug.callback(self._record, selection, counters)
        off = (unexplained + unexplained_rows + miscounted
               + (parts["router_rel_diff"] > limits["router_rel_tol"])
               + (parts["experts_rel_diff"] > limits["experts_rel_tol"])
               + (parts["index_rel_diff"] > limits["index_rel_tol"])
               + (parts["attn_row_diff"] > limits["attn_row_tol"]))
        return value + lax.stop_gradient(off.astype(jnp.float32))

    def model_flops_per_sample(self) -> float:
        """Model FLOPs to train on one sequence, forward and backward, no
        recompute.  6 per parameter of the matrices a token is multiplied
        by on this chip and that HAVE a backward pass (attention's
        projections, the router, the routed experts a token meets here,
        the held rows of the head); attention's two matmuls over the pairs
        the SELECTION leaves, three passes (12 a pair and column).  The
        indexer has a forward pass alone: 2 per parameter of its three
        matrices a token, and 2 a multiply-add over the CAUSAL pairs, 16
        heads of 64."""
        n, cfg, S = self.numbers, self.cfg, self.seq_len
        D, size = n["hidden_size"], n["head_dim"]
        H, Hkv = n["num_attention_heads"], n["num_key_value_heads"]
        J, Di = cfg.index_heads, cfg.index_head_dim
        routed = cfg.num_experts_per_tok * len(cfg.held) / cfg.num_experts
        trained = (D * (H + 2 * Hkv) * size + H * size * D
                   + D * cfg.num_experts
                   + 3 * D * n["moe_intermediate_size"] * routed)
        indexer = D * ((J + 1) * Di + J)
        pairs = sparse_cost.selected_pairs(S, cfg.index_topk)
        causal = sparse_cost.causal_pairs(S)
        layers = cfg.num_layers
        return (6.0 * (layers * trained + n["vocab_size"] * D) * S
                + 2.0 * layers * indexer * S
                + layers * (12.0 * pairs * H * size + 2.0 * causal * J * Di))
