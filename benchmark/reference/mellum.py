"""Plain float32 reference of the `mellum` language-model loss (JetBrains'
Mellum 2, `model_type: mellum`), told which experts and which slice of
the vocabulary one chip of a deployment holds.

Written from the model's `config.json`; what its keys do not state is the
convention of the Qwen3-MoE lineage they come from (the configuration's
`assumed` lists each).  The layer, written down:

    h = embed[ids]                                         (no scale)
    every layer:
      a = rms(x; input_ln)
      q, k, v = a Wq, a Wk, a Wv          (no bias; 32 / 4 / 4 heads of 128)
      q, k = rms over the head's 128, learned scale        (ASSUMED)
      q, k = rot(q), rot(k)               half-split layout, theta 500000
         sliding layer: inv_freq_i = theta^(-2i/128), amplitude 1
         full layer (YaRN): extrap_i = theta^(-2i/128),
            interp_i = extrap_i / 16
            d(r) = 128 ln(8192 / (2 pi r)) / (2 ln theta);
            low = floor(d(32)), high = ceil(d(1)), clipped to [0, 127]
            ramp_i = clip((i - low) / (high - low), 0, 1), i = 0..63
            inv_freq_i = interp_i ramp_i + extrap_i (1 - ramp_i);
            cos and sin TIMES 1.2772588722239782
      ctx = causal softmax(q k^T / sqrt(128)) v; sliding: keys
            i - 1024 < j <= i; a kv head serves 8 query heads
      x = x + ctx Wo
      m = rms(x; post_attention_ln)
      p = softmax(m Wr) over all 64 experts, float32;  sel = top-8(p)
      w = p[sel] / sum p[sel]                              (norm_topk_prob)
      x = x + sum_{e in sel, e held} w_e (silu(m Wgate_e) * (m Wup_e)) Wdown_e
    final rms, untied head, mean next-token cross-entropy over the held rows

With these numbers d(32) = 18.08 and d(1) = 34.98, so low = 18 and
high = 35: pairs 0-18 keep their frequency, pairs 35-63 turn 16 times
slower, the sixteen between are blended.  The model's card names a
multi-token-prediction head; `config.json` has no key for one and none is
computed.  What the experts held elsewhere would add is left out, as in
the program.

A SHARE'S BACKWARD PASS (the program's
`dropless_moe.MoEConfig.hold_held_weight`, the same here): where fewer
experts are held than the router scores, the weight a token gives the
held experts together is a constant of the backward pass,

    W = sum_{e in sel, e held} w_e;   w := w stop(W) / W    (the same numbers)

so that a weight moved from an absent expert to a held one earns nothing
by the move alone: the held experts compete among themselves, and the
absent experts' logits get no gradient (what they would get if each
returned the weighted mean of the held ones the token chose).  Without it
every gradient of a share says "send the held experts more", since only
they add to the result, which no deployment's does.  With all experts
held the term is not there.

Nothing of byteps_tpu is imported.  What is shared with the program is
the layout of its parameter tree: one group `moe` with leaves stacked on
a leading layer axis; `qkv_w` [hidden, .] holds q, k and v side by side;
`expert_*_w` are stacked over the held experts in the order of
`spec["held"]`.

Departures from a naive transcription, each for memory at 32,768
positions and none changing the arithmetic of a row: the layers are
walked by `lax.scan` with `jax.checkpoint` around each, what tells a
sliding layer from a full one (window, frequencies, amplitude) being data
(`layer_kinds`; a full layer's window is the sequence); attention
computes K and V for the whole sequence and then walks the query rows in
blocks of `spec["q_block"]`, each block projected, normed, turned,
attended and projected back by itself (a [32, 64, 32768] float32 block of
logits is 268 MB; neither the [S, S] square nor a float32 [S, 4096] of
queries is ever held), each half of a layer rematerialised by itself, and
the head the rows in blocks of `spec["ce_block"]` (`lax.map`, each block
rematerialised), a row's softmax being taken over all its keys, or all
the held logits, at once; a held expert is computed on every token and
multiplied by the token's weight for it, zero where the token did not
choose it (`lax.scan` over the held experts, each step rematerialised:
sixteen experts' [S, 896] activations would be 5.6 GB).  No kernel, no
grouping of rows, no bfloat16 anywhere: every matmul is float32 at
`highest` precision.

Top-k is discontinuous, so the choice is compared apart from the
arithmetic, as `benchmark/reference/afmoe.py` does.  With `sel` given,
the scores and weights are this reference's own but the experts are those
`sel` names, and `stats` says how `sel` differs from this reference's own
top-k: the tokens whose sets differ, and for each the gap between the
best it left out and the worst it took instead, measured in the router's
LOGITS (the softmax's normaliser is the token's own and cancels; a
probability of 1/64 would make every gap look small).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

SLIDING = "sliding_attention"
# the gap of a choice of another SIZE than k: no rounding explains it
WRONG_SIZE = 1e3


def rms_norm(x, scale, eps):
    return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def yarn(size, theta, factor, original, beta_fast, beta_slow):
    """`inv_freq` [size / 2] of a full layer, the closed form above."""
    i = np.arange(size // 2, dtype=np.float64)
    extrap = theta ** (-2 * i / size)

    def d(r):
        return size * math.log(original / (2 * math.pi * r)) / (
            2 * math.log(theta))
    low = min(max(math.floor(d(beta_fast)), 0), size - 1)
    high = min(max(math.ceil(d(beta_slow)), 0), size - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return extrap / factor * ramp + extrap * (1 - ramp)


def rotary(x, inv_freq, amplitude=1.0, start=0):
    """x [..., S, size], half-split layout, its first row at position
    `start`; `inv_freq` [size / 2]."""
    half = x.shape[-1] // 2
    where = start + jnp.arange(x.shape[-2])
    angles = where.astype(jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32)
    cos, sin = jnp.cos(angles) * amplitude, jnp.sin(angles) * amplitude
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def positions(spec, kind):
    """`(inv_freq, amplitude)` of a layer of `kind`."""
    size, theta = spec["head_dim"], spec["theta"]
    y = spec.get("yarn")
    if kind == SLIDING or y is None:
        return theta ** (-2 * np.arange(size // 2, dtype=np.float64)
                         / size), 1.0
    return (yarn(size, theta, y["factor"],
                 y["original_max_position_embeddings"], y["beta_fast"],
                 y["beta_slow"]), y["attention_factor"])


def layer_kinds(spec, seq_len):
    """What tells one layer from the next, as arrays over the layers (the
    layers are walked by `lax.scan`, so a layer's kind is data): its
    `inv_freq` [half], its `amplitude`, and its `window`, the sequence's
    length in a full layer, where `i - j < window` then holds for every
    key a causal row sees."""
    per = [positions(spec, kind) for kind in spec["layer_types"]]
    return {
        "inv_freq": jnp.asarray(np.stack([f for f, _ in per]), jnp.float32),
        "amplitude": jnp.asarray([a for _, a in per], jnp.float32),
        "window": jnp.asarray(
            [spec["window"] if kind == SLIDING else seq_len
             for kind in spec["layer_types"]], jnp.int32)}


def attention(q, k, v, start, window):
    """The rows `start ...` of one sequence: q [Hkv, G, rows, size]
    against ALL the keys, k, v [Hkv, S, size]; `window` the sequence's
    length in a full layer.  A row's softmax is over all its keys at
    once."""
    scores = jnp.einsum("kgqd,ksd->kgqs", q, k) / math.sqrt(q.shape[-1])
    i = start + jnp.arange(q.shape[2])[:, None]
    j = jnp.arange(k.shape[1])[None, :]
    keep = (i >= j) & (i - j < window)
    probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), -1)
    return jnp.einsum("kgqs,ksd->kgqd", probs, v)


def swiglu(x, gate_w, up_w, down_w):
    return (jax.nn.silu(x @ gate_w) * (x @ up_w)) @ down_w


def selection_stats(logits, sel, own):
    """How the choice `sel` differs from this reference's `own` top-k,
    both [T, k], given its router `logits` [T, E]."""
    ids = jnp.arange(logits.shape[-1])
    took = (sel[..., None] == ids).any(-2)
    mine = (own[..., None] == ids).any(-2)
    left_out = jnp.where(mine & ~took, logits, -jnp.inf).max(-1)
    instead = jnp.where(took & ~mine, logits, jnp.inf).min(-1)
    differs = (took != mine).any(-1)
    gap = jnp.where(differs, left_out - instead, 0.0)
    gap = jnp.where(took.sum(-1) != mine.sum(-1), WRONG_SIZE, gap)
    return {"swapped_tokens": differs.sum(), "max_gap": gap.max(),
            "gaps": gap}


def chosen_weights(scores, sel, norm_topk_prob):
    """The weights of the experts `sel` [T, k] names, from `scores`
    [T, E]: the chosen probabilities, over their sum where
    `norm_topk_prob`."""
    w = jnp.take_along_axis(scores, sel, -1)
    return w / w.sum(-1, keepdims=True) if norm_topk_prob else w


def experts_layer(m, p, spec, sel=None):
    """m [T, hidden] -> `(f, stats)`: the held experts' part."""
    logits = m @ p["router_w"]
    scores = jax.nn.softmax(logits, -1)
    _, own = lax.top_k(lax.stop_gradient(scores), spec["top_k"])
    stats = None
    if sel is None:
        sel = own
    else:
        stats = selection_stats(lax.stop_gradient(logits), sel, own)
    w = chosen_weights(scores, sel, spec["norm_topk_prob"])
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


def attention_half(x, p, spec, kind):
    """x [B, S, hidden] -> ctx Wo; `kind` the layer's row of
    `layer_kinds`.  Keys and values are computed for the whole sequence;
    then the query rows are walked in blocks of `spec["q_block"]`, each
    block's queries projected, normed, turned, attended and projected
    back by itself and rematerialised, so that nothing [S, heads * size]
    wide is held in float32 but K and V."""
    B, S, D = x.shape
    H, Hkv, size = spec["heads"], spec["kv_heads"], spec["head_dim"]
    eps, q_block = spec["eps"], min(spec["q_block"], S)
    inv_freq, amplitude = kind["inv_freq"], kind["amplitude"]
    w_q, w_k, w_v = jnp.split(p["qkv_w"], [H * size, (H + Hkv) * size],
                              axis=-1)

    def heads(t):                       # [rows, n * size] -> [n, rows, size]
        return t.reshape(t.shape[0], -1, size).transpose(1, 0, 2)

    def sequence(a):                    # [S, hidden]
        k = rotary(rms_norm(heads(a @ w_k), p["k_norm"], eps), inv_freq,
                   amplitude)
        v = heads(a @ w_v)

        @jax.checkpoint
        def rows(start):
            ab = lax.dynamic_slice_in_dim(a, start, q_block)
            q = rotary(rms_norm(heads(ab @ w_q), p["q_norm"], eps),
                       inv_freq, amplitude, start)
            ctx = attention(q.reshape(Hkv, H // Hkv, q_block, size), k, v,
                            start, kind["window"])
            ctx = ctx.reshape(H, q_block, size).transpose(1, 0, 2)
            return ctx.reshape(q_block, H * size) @ p["attn_out_w"]

        return lax.map(rows, jnp.arange(0, S, q_block)).reshape(S, D)

    return lax.map(sequence, rms_norm(x, p["input_ln"], eps))


def experts_half(x, p, spec, sel=None):
    """x [B, S, hidden] -> `(the held experts' sum, stats)`."""
    B, S, D = x.shape
    m = rms_norm(x, p["post_attn_ln"], spec["eps"])
    f, stats = experts_layer(m.reshape(B * S, D), p, spec, sel)
    return f.reshape(B, S, D), stats


def layer(x, p, spec, kind, sel=None):
    """x [B, S, hidden]; p the layer's own leaves, `kind` its row of
    `layer_kinds`.  Each half is rematerialised by itself, so that the
    float32 activations of the two never stand side by side."""
    x = x + jax.checkpoint(
        lambda x, p, kind: attention_half(x, p, spec, kind))(x, p, kind)
    f, stats = jax.checkpoint(
        lambda x, p, sel: experts_half(x, p, spec, sel))(x, p, sel)
    return x + f, stats


def hidden(params, tokens, spec, sel=None):
    """tokens [B, S] -> `(final hidden states, stats stacked over the
    layers or None)`.  The layers are walked by `lax.scan` over the
    leading axis their leaves are stacked on, each rematerialised: the
    gradient then comes out stacked as the program's is, with no copy of
    a layer's leaves beside it (a Python loop over split leaves held two
    more trees of 2.4 GB)."""
    x = params["embed"][tokens - spec["vocab_start"]]

    @jax.checkpoint
    def step(x, xs):
        p, kind, s = xs
        return layer(x, p, spec, kind, s)

    x, stats = lax.scan(
        step, x, (params["moe"], layer_kinds(spec, tokens.shape[1]), sel))
    return rms_norm(x, params["final_ln"], spec["eps"]), stats


def nll_mean(x, head, targets, ce_block):
    """Mean cross-entropy of `x` [N, hidden] against `head` [V, hidden]."""
    n = x.shape[0]
    ce_block = min(ce_block, n)

    @jax.checkpoint
    def rows(start):
        xb = lax.dynamic_slice_in_dim(x, start, ce_block)
        tb = lax.dynamic_slice_in_dim(targets, start, ce_block)
        logp = jax.nn.log_softmax(xb @ head.T, axis=-1)
        return -jnp.take_along_axis(logp, tb[:, None], -1).sum()

    return lax.map(rows, jnp.arange(0, n, ce_block)).sum() / n


def loss(params, batch, spec, sel=None, with_stats=False):
    """Mean next-token cross-entropy over the held slice.  batch =
    (tokens, targets), both [batch, position] int32 ids of the slice;
    `params` is the program's tree, any dtype; `spec` the model's numbers
    (see `benchmark/families/mellum.py`).  `sel` [layers, tokens, k] puts
    somebody else's choice of experts in place of the top-k."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        tokens, targets = batch
        x, stats = hidden(params, tokens, spec, sel)
        value = nll_mean(x.reshape(-1, x.shape[-1]), params["head"],
                         targets.reshape(-1) - spec["vocab_start"],
                         spec["ce_block"])
    return (value, stats) if with_stats else value
