"""Plain float32 reference of the `joyai_llm_flash` language-model loss
(JD's JoyAI-LLM-Flash, whose `config.json` carries DeepSeek-V3's keys),
told which layers, which experts and which slice of the vocabulary one
chip of a deployment holds.

Written from the equations of the DeepSeek-V2 and DeepSeek-V3 reports
(latent attention: V2 section 2.1; the sigmoid router with a bias for the
choice alone and multi-token prediction: V3 sections 2.1.2 and 2.2), whose
keys the model's `config.json` carries; what neither states is listed in
the configuration's `assumed`.  With x = embed[ids] (no scale), a layer is

    a = rms(x; input_ln)
    cQ_t = rms(a_t W_DQ; q_a_ln)                         in R^q_lora
    [qN_{t,h} | qR_{t,h}] = cQ_t W_UQ,h                  nope + rope a head
    [cKV_t | kR_t] = a_t W_DKV;  cKV_t <- rms(cKV_t; kv_a_ln)
    [kN_{t,h} | v_{t,h}] = cKV_t W_UKV,h                 nope + v a head
    qR_{t,h} <- R_t(qR_{t,h});  kR_t <- R_t(kR_t): ONE rotary key a token,
        the same for every head; R rotary at `theta` over the pairs
        (i, i + rope / 2), no scaling
    o_{t,h} = sum_{s<=t} softmax_s((qN_{t,h} . kN_{s,h} + qR_{t,h} . kR_s)
              / sqrt(nope + rope)) v_{s,h}
    x = x + concat_h(o) W_O
    m = rms(x; post_attn_ln)
    the first `dense_layers` layers:  x = x + (silu(m Wg) * (m Wu)) Wd
    the others:  s = sigmoid(m W_r) over ALL the experts
                 choice = top-k(s + expert_bias)    (the bias: a leaf where
                          the tree has it, else zero; no gradient)
                 w = route_scale * s[choice] / (sum s[choice] + 1e-20)
                 x = x + shared(m) + sum_{e chosen, e held} w_e expert_e(m)

then a final RMS norm, the UNTIED head and the mean next-token
cross-entropy over the held rows: the MAIN loss.  The prediction module
(`num_nextn_predict_layers` 1) reads the last layer's x BEFORE that norm:

    h'_i = [rms(embed[t_{i+1}]; enorm) | rms(x_i; hnorm)] W_eh
    one more layer of the expert kind on h', with weights of its own
    logits = rms(.; the module's final_ln) head^T    the main model's
             embedding and head
    the MTP loss: the mean cross-entropy of t_{i+2} over the positions
    that have one (all but a sequence's last)

and the loss is `main + mtp_weight x MTP`.  What the experts held
elsewhere would add is left out, as in the program.

A SHARE'S BACKWARD PASS (the program's
`dropless_moe.MoEConfig.hold_held_weight`, the same here, as
`benchmark/reference/mellum.py` says it): where fewer experts are held
than the router scores, the weight a token gives the held experts
together is a constant of the backward pass, w := w stop(W) / W.

Nothing of byteps_tpu is imported.  The logits of a query and a key are
the SUM of the two products above: no key of width nope + rope is ever
laid out, and the rotary key is never repeated over the heads.  An expert
is computed on every token and multiplied by the token's weight for it,
zero where the token did not choose it.  What is shared with the program
is the layout of its parameter tree: groups `dense` and `moe` with leaves
stacked on a leading layer axis, `mtp` the module's own leaves;
`down_w` [hidden, .] holds W_DQ and W_DKV side by side, `[cQ | cKV |
kR]`; a head's columns of `q_up_w` are `[qN | qR]` and of `kv_up_w`
`[kN | v]`, head after head; `eh_proj_w` [2 hidden, hidden] meets the
embedding's half first; `expert_*_w` are stacked over the held experts in
the order of `spec["held"]`.

Departures from a naive transcription, each for memory at 16,384
positions and none changing the arithmetic of a row: the layers of a
group are walked by `lax.scan` over the group's stack with
`jax.checkpoint` around each (`_layers` says why); attention walks the query rows
in blocks of `spec["q_block"]` (each block's queries made, turned,
attended and projected back by itself), the dense and shared feed-forwards their
rows in blocks of `spec["mlp_block"]` and the head in blocks of
`spec["ce_block"]` (`lax.map`, each block rematerialised), a row's softmax
being taken over all its keys, or all the held logits, at once; the routed
experts are a `lax.scan` over the held ids, each step rematerialised.
The prediction module runs over all S positions, the last one's loss
left out: no position reads a later one.  No kernel, no grouping of rows,
no bfloat16 anywhere: every matmul is float32 at `highest` precision.

Top-k is discontinuous, so the choice is compared apart from the
arithmetic, as `benchmark/reference/afmoe.py` does: with `sel` given, the
scores and weights are this reference's own but the experts are those
`sel` names, and `stats` says how `sel` differs from this reference's own
top-k, the gap read in the scores the choice is made by (s + b).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax


def rms_norm(x, scale, eps):
    return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _blocks(n, block):
    block = min(block, n)
    if n % block:
        raise ValueError(f"{n} rows do not come in blocks of {block}")
    return block


def rotary(x, theta, start=0):
    """x [..., rows, size], the rows at positions `start ...`; pairs
    (i, i + size / 2)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = (start + jnp.arange(x.shape[-2])).astype(
        jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(qn, qr, kn, kr, v, start=0):
    """The rows `start ...` of one sequence against ALL its keys: qn
    [H, rows, nope] and qr [H, rows, rope], a query's two parts; kn
    [H, S, nope]; kr [S, rope], every head's; v [H, S, v] -> [H, rows, v].
    A pair's logit is the SUM of the two products; a row's softmax is
    over all its keys at once."""
    scores = (jnp.einsum("hqd,hsd->hqs", qn, kn)
              + jnp.einsum("hqd,sd->hqs", qr, kr)) / math.sqrt(
                  qn.shape[-1] + qr.shape[-1])
    i = start + jnp.arange(qn.shape[1])[:, None]
    j = jnp.arange(kn.shape[1])[None, :]
    probs = jax.nn.softmax(jnp.where(i >= j, scores, -jnp.inf), -1)
    return jnp.einsum("hqs,hsd->hqd", probs, v)


def latent_attention(x, p, spec):
    """x [B, S, hidden] -> concat_h(o) W_O.  Keys and values are computed
    for the whole sequence; the query rows are walked in blocks of
    `spec["q_block"]`, each block's queries made from its rows of the
    query chain, turned, attended and projected back by itself."""
    H, nope = spec["heads"], spec["nope"]
    rq, rkv, eps, theta = (spec["q_lora"], spec["kv_lora"], spec["eps"],
                           spec["theta"])
    w_dq, w_dkv = p["down_w"][:, :rq], p["down_w"][:, rq:]

    def heads(t):                       # [rows, H * size] -> [H, rows, size]
        return t.reshape(t.shape[0], H, -1).transpose(1, 0, 2)

    def sequence(a):                    # [S, hidden]
        S = a.shape[0]
        q_block = _blocks(S, spec["q_block"])
        cq = rms_norm(a @ w_dq, p["q_a_ln"], eps)
        ckv_kr = a @ w_dkv
        kv = heads(rms_norm(ckv_kr[:, :rkv], p["kv_a_ln"], eps)
                   @ p["kv_up_w"])
        kn, v = kv[..., :nope], kv[..., nope:]
        kr = rotary(ckv_kr[:, rkv:], theta)

        @jax.checkpoint
        def rows(start):
            q = heads(lax.dynamic_slice_in_dim(cq, start, q_block)
                      @ p["q_up_w"])
            o = attention(q[..., :nope], rotary(q[..., nope:], theta, start),
                          kn, kr, v, start)
            return (o.transpose(1, 0, 2).reshape(q_block, -1)
                    @ p["attn_out_w"])
        return lax.map(rows, jnp.arange(0, S, q_block)).reshape(a.shape)

    return lax.map(sequence, rms_norm(x, p["input_ln"], eps))


def swiglu(x, gate_w, up_w, down_w):
    return (jax.nn.silu(x @ gate_w) * (x @ up_w)) @ down_w


def swiglu_by_rows(x, gate_w, up_w, down_w, block):
    """`swiglu` of x [T, hidden], `block` rows at a time."""
    block = _blocks(x.shape[0], block)

    @jax.checkpoint
    def rows(xb):
        return swiglu(xb, gate_w, up_w, down_w)
    return lax.map(rows, x.reshape(-1, block, x.shape[-1])).reshape(x.shape)


def selection_stats(scores, sel, own):
    """How the choice `sel` differs from this reference's `own` top-k,
    both [T, k], given its `scores` [T, E] (the bias added)."""
    ids = jnp.arange(scores.shape[-1])
    took = (sel[..., None] == ids).any(-2)
    mine = (own[..., None] == ids).any(-2)
    left_out = jnp.where(mine & ~took, scores, -jnp.inf).max(-1)
    instead = jnp.where(took & ~mine, scores, jnp.inf).min(-1)
    differs = (took != mine).any(-1)
    gap = jnp.where(differs, left_out - instead, 0.0)
    # scores lie in (0, 1): a choice of another size is wrong by the most
    gap = jnp.where(took.sum(-1) != mine.sum(-1), 1.0, gap)
    return {"swapped_tokens": differs.sum(), "gaps": gap}


def chosen_weights(scores, sel, route_scale):
    w = jnp.take_along_axis(scores, sel, -1)
    return w / (w.sum(-1, keepdims=True) + 1e-20) * route_scale


def routed_experts(m, p, spec, sel=None):
    """m [T, hidden] -> `(the held experts' weighted sum, stats)`."""
    scores = jax.nn.sigmoid(m @ p["router_w"])
    biased = scores + p["expert_bias"] if "expert_bias" in p else scores
    _, own = lax.top_k(lax.stop_gradient(biased), spec["top_k"])
    stats = None
    if sel is None:
        sel = own
    else:
        stats = selection_stats(lax.stop_gradient(biased), sel, own)
    w = chosen_weights(scores, sel, spec["route_scale"])
    if len(spec["held"]) < scores.shape[-1]:
        # A share's backward pass (the head of this file): the value as
        # it was, the token's weight on the held experts a constant.
        here = jnp.isin(sel, jnp.asarray(spec["held"], sel.dtype))
        held = jnp.where(here, w, 0.0).sum(-1, keepdims=True)
        scaled = w * jnp.where(
            held > 0, lax.stop_gradient(held) / jnp.where(held > 0, held, 1.0),
            1.0)
        w = lax.stop_gradient(w) + (scaled - lax.stop_gradient(scaled))

    @jax.checkpoint
    def one(e, gate_w, up_w, down_w):
        coef = jnp.where(sel == e, w, 0.0).sum(-1)           # [T]
        return coef[:, None] * swiglu(m, gate_w, up_w, down_w)

    def add(acc, xs):
        return acc + one(*xs), None

    routed, _ = lax.scan(
        add, jnp.zeros_like(m),
        (jnp.asarray(spec["held"], jnp.int32), p["expert_gate_w"],
         p["expert_up_w"], p["expert_down_w"]))
    return routed, stats


def layer(x, p, spec, is_moe, sel=None):
    """x [B, S, hidden]; p the layer's own leaves -> `(x, stats)`."""
    B, S, D = x.shape
    # rematerialised by itself inside the layer: where what follows reads
    # its result, the blocks' loop would else keep every block's mask,
    # [blocks, H, rows, S] (8 GB at 16,384 positions)
    x = x + jax.checkpoint(lambda x, p: latent_attention(x, p, spec))(x, p)
    m = rms_norm(x, p["post_attn_ln"], spec["eps"]).reshape(B * S, D)
    if not is_moe:
        f = swiglu_by_rows(m, p["mlp_gate_w"], p["mlp_up_w"],
                           p["mlp_down_w"], spec["mlp_block"])
        return x + f.reshape(B, S, D), None
    routed, stats = routed_experts(m, p, spec, sel)
    shared = swiglu_by_rows(m, p["shared_gate_w"], p["shared_up_w"],
                            p["shared_down_w"], spec["mlp_block"])
    return x + (shared + routed).reshape(B, S, D), stats


def _layers(x, group, spec, is_moe, sel=None):
    """x through the layers of one group, whose leaves are stacked on a
    leading layer axis: `lax.scan` over the stack, each layer
    rematerialised, so that one layer's float32 activations are alive at
    a time and the gradient comes out stacked as the program's is, written
    a layer at a time (a Python loop over split leaves holds a copy of
    every layer's leaves and of every layer's gradient beside the tree:
    3.4 GB at the published widths).  -> `(x, stats stacked over the
    layers, or None)`."""
    @jax.checkpoint
    def step(x, xs):
        p, s = xs
        return layer(x, p, spec, is_moe, s)
    return lax.scan(step, x, (group, sel))


def hidden(params, tokens, spec, sel=None):
    """tokens [B, S] -> `(x after the last layer, BEFORE the final norm;
    the expert layers' stats, stacked, or None)`."""
    x = params["embed"][tokens - spec["vocab_start"]]
    stats = None
    if "dense" in params:
        x, _ = _layers(x, params["dense"], spec, False)
    if "moe" in params:
        x, stats = _layers(x, params["moe"], spec, True, sel)
    return x, stats


def prediction_module(params, x, next_tokens, spec, sel=None):
    """x [B, S, hidden] the last layer's output before the final norm;
    `next_tokens` [B, S] the token after each position -> `(the module's
    hidden states after its own final norm, stats)`."""
    p, eps = params["mtp"], spec["eps"]
    e = params["embed"][next_tokens - spec["vocab_start"]]
    both = jnp.concatenate([rms_norm(e, p["enorm"], eps),
                            rms_norm(x, p["hnorm"], eps)], -1)
    x, stats = jax.checkpoint(
        lambda x, p, sel: layer(x, p, spec, True, sel))(
            both @ p["eh_proj_w"], p, sel)
    return rms_norm(x, p["final_ln"], eps), stats


def nll_sum(x, head, targets, weights, ce_block):
    """The weighted sum of the rows' cross-entropies: `x` [N, hidden]
    against `head` [V, hidden]."""
    n = x.shape[0]
    ce_block = _blocks(n, ce_block)

    @jax.checkpoint
    def rows(start):
        xb, tb, wb = (lax.dynamic_slice_in_dim(t, start, ce_block)
                      for t in (x, targets, weights))
        logp = jax.nn.log_softmax(xb @ head.T, axis=-1)
        return -(jnp.take_along_axis(logp, tb[:, None], -1)[:, 0] * wb).sum()

    return lax.map(rows, jnp.arange(0, n, ce_block)).sum()


def losses(params, batch, spec, sel=None):
    """`(main, mtp, stats)`: batch = (tokens, targets), both [batch,
    position] int32 ids of the slice, targets the tokens one position on;
    `params` is the program's tree, any dtype; `spec` the model's numbers
    (see `benchmark/families/joyai.py`).  `sel` [expert layers (the
    module's last), tokens, k] puts somebody else's choice of experts in
    place of the top-k.  `mtp` is 0 where the tree has no module."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        tokens, targets = batch
        B, S = tokens.shape
        D = params["embed"].shape[-1]
        held = targets - spec["vocab_start"]
        n_main = params["moe"]["router_w"].shape[0] if "moe" in params else 0
        x, stats = hidden(params, tokens, spec,
                          None if sel is None else sel[:n_main])
        main = nll_sum(
            rms_norm(x, params["final_ln"], spec["eps"]).reshape(-1, D),
            params["head"], held.reshape(-1), jnp.ones((B * S,)),
            spec["ce_block"]) / (B * S)
        mtp = jnp.zeros(())
        if "mtp" in params:
            x2, s2 = prediction_module(
                params, x, targets, spec, None if sel is None else sel[-1])
            if s2 is not None:
                s2 = jax.tree.map(lambda a: a[None], s2)
                stats = s2 if stats is None else jax.tree.map(
                    lambda a, b: jnp.concatenate([a, b]), stats, s2)
            # position i predicts the token two on, which is the target of
            # position i + 1; a sequence's last position has none
            second = jnp.concatenate([held[:, 1:], held[:, :1]], axis=1)
            has = jnp.broadcast_to(jnp.arange(S) < S - 1, (B, S))
            mtp = nll_sum(x2.reshape(-1, D), params["head"],
                          second.reshape(-1),
                          has.reshape(-1).astype(jnp.float32),
                          spec["ce_block"]) / (B * (S - 1))
    return main, mtp, stats


def loss(params, batch, spec, sel=None, with_stats=False):
    """`main + spec["mtp_weight"] x mtp` of `losses`."""
    main, mtp, stats = losses(params, batch, spec, sel)
    value = main + spec["mtp_weight"] * mtp
    return (value, stats) if with_stats else value
