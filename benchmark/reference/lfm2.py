"""Plain float32 reference of the `lfm2_moe` language-model loss
(LiquidAI's LFM2 mixture-of-experts decoders), told which layers, which
experts and which slice of the vocabulary one chip of a deployment holds.

Written from the family's layer equations as the configuration's
`assumed` gives them (the modelling code was not read here: no network).
RMS norms with `eps`, no bias anywhere.  With x = embed[ids],

    a layer:   x = x + mixer(rms(x; operator_norm))
               x = x + ffn  (rms(x; ffn_norm))
    conv(u):   [B | C | X] = u W_in,   W_in [hidden, 3 hidden]
               g = B * X
               z_t = w_{K-1} g_t + w_{K-2} g_{t-1} + ... + w_0 g_{t-(K-1)},
                     a sequence at a time, g_s = 0 for s < 0: depthwise,
                     causal, no bias, no activation
               conv(u) = (C * z) W_out
    attn(u):   q, k, v = u Wq, u Wk, u Wv  (`qkv_w`'s columns `[q | k | v]`,
               a head's lanes side by side)
               q_h <- R_t(rms(q_h; q_norm)),  k_g <- R_t(rms(k_g; k_norm)):
               the norm over each head's lanes FIRST, then rotary at
               `theta` over the pairs (i, i + size / 2)
               o_{t,h} = sum_{s<=t} softmax_s(q_{t,h} . k_{s,g} / sqrt(size))
                         v_{s,g},   g = h // (heads / kv_heads)
               attn(u) = concat_h(o) W_O
    the first `dense_layers` layers:  ffn(m) = (silu(m W1) * (m W3)) W2
    the others:  s = sigmoid(m W_r) over ALL the experts
                 choice = top-k(s + expert_bias)    (the bias: a leaf where
                          the tree has it, else zero; no gradient)
                 w = route_scale * s[choice] / (sum s[choice] + norm_eps)
                 ffn(m) = sum_{e chosen, e held} w_e expert_e(m), each a
                          SwiGLU; no shared expert

then a final RMS norm, the TIED head (logits = x embed^T) and the mean
next-token cross-entropy over the held rows.  What the experts held
elsewhere would add is left out, as in the program.

A SHARE'S BACKWARD PASS (the program's
`dropless_moe.MoEConfig.hold_held_weight`, the same here, as
`benchmark/reference/mellum.py` says it): where fewer experts are held
than the router scores, the weight a token gives the held experts
together is a constant of the backward pass, w := w stop(W) / W.

Nothing of byteps_tpu is imported.  The convolution is K explicit shifted
sums a sequence; an expert is computed on every token and multiplied by
the token's weight for it, zero where the token did not choose it; a
key-value head is never repeated.  What is shared with the program is the
layout of its parameter tree: `layers` a list with one group of leaves a
RUN (consecutive layers of one mixer and one feed-forward), stacked on a
leading layer axis; which run is which is read from `spec["layer_types"]`
and `spec["dense_layers"]` here (`runs`).

Departures from a naive transcription, each for memory at 8,192 positions
and none changing the arithmetic of a row: a run's layers are walked by
`lax.scan` over its stack with `jax.checkpoint` around each; attention
walks the query rows in blocks of `spec["q_block"]`, the dense feed-forward
its rows in blocks of `spec["mlp_block"]` and the head in blocks of
`spec["ce_block"]` (`lax.map`, each block rematerialised), a row's softmax
being taken over all its keys, or all the held logits, at once; the routed
experts are a `lax.scan` over the held ids, each step rematerialised.  No
kernel, no grouping of rows, no bfloat16 anywhere: every matmul is float32
at `highest` precision.

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

CONV = "conv"           # `layer_types`' other entry is "full_attention"


def rms_norm(x, scale, eps):
    return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _blocks(n, block):
    block = min(block, n)
    if n % block:
        raise ValueError(f"{n} rows do not come in blocks of {block}")
    return block


def runs(spec):
    """`[(mixer, is_moe, layers)]`: consecutive layers of one kind."""
    out = []
    for i, mixer in enumerate(spec["layer_types"]):
        kind = (mixer, i >= spec["dense_layers"])
        if out and out[-1][:2] == kind:
            out[-1] = (*kind, out[-1][2] + 1)
        else:
            out.append((*kind, 1))
    return out


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


def gated_conv(bcx, taps):
    """ONE sequence: bcx [S, 3 C] = `[B | C | X]`, taps [K, C] ->
    C * conv(B * X), [S, C]; `taps[K - 1]` meets the current position."""
    K, C = taps.shape
    b, c, x = bcx[:, :C], bcx[:, C:2 * C], bcx[:, 2 * C:]
    g = b * x
    z = taps[K - 1] * g
    for d in range(1, K):
        earlier = jnp.concatenate([jnp.zeros_like(g[:d]), g[:-d]])
        z = z + taps[K - 1 - d] * earlier
    return c * z


def conv_mixer(u, p):
    """u [B, S, hidden], normed -> [B, S, hidden]."""
    return lax.map(
        lambda s: gated_conv(s @ p["in_proj_w"], p["conv_w"])
        @ p["out_proj_w"], u)


def attention(q, k, v, start=0):
    """The rows `start ...` of one sequence against ALL its keys: q
    [H, rows, size], k and v [Hkv, S, size], a key-value head serving
    H / Hkv query heads -> [H, rows, size].  A row's softmax is over all
    its keys at once."""
    H, rows, size = q.shape
    G = k.shape[0]
    qg = q.reshape(G, H // G, rows, size)
    scores = jnp.einsum("gjqd,gsd->gjqs", qg, k) / math.sqrt(size)
    i = start + jnp.arange(rows)[:, None]
    j = jnp.arange(k.shape[1])[None, :]
    probs = jax.nn.softmax(jnp.where(i >= j, scores, -jnp.inf), -1)
    return jnp.einsum("gjqs,gsd->gjqd", probs, v).reshape(H, rows, size)


def normed_and_turned(a, w, scale, spec, start=0):
    """A sequence's rows `start ...` as queries or keys: a [rows, hidden]
    through `w` [hidden, n * size], split into heads, each head RMS-normed
    over its lanes by `scale` [size], THEN turned -> [n, rows, size]."""
    size = spec["head_dim"]
    t = (a @ w).reshape(a.shape[0], -1, size).transpose(1, 0, 2)
    return rotary(rms_norm(t, scale, spec["eps"]), spec["theta"], start)


def attention_mixer(u, p, spec):
    """u [B, S, hidden], normed -> concat_h(o) W_O.  Keys and values are
    computed for the whole sequence; the query rows are walked in blocks of
    `spec["q_block"]`, each block's queries made, normed, turned, attended
    and projected back by itself."""
    H, G, size = spec["heads"], spec["kv_heads"], spec["head_dim"]
    w_q = p["qkv_w"][:, :H * size]
    w_k = p["qkv_w"][:, H * size:(H + G) * size]
    w_v = p["qkv_w"][:, (H + G) * size:]

    def sequence(a):                    # [S, hidden]
        S = a.shape[0]
        q_block = _blocks(S, spec["q_block"])
        k = normed_and_turned(a, w_k, p["k_norm"], spec)
        v = (a @ w_v).reshape(S, G, size).transpose(1, 0, 2)

        @jax.checkpoint
        def rows(start):
            q = normed_and_turned(
                lax.dynamic_slice_in_dim(a, start, q_block), w_q,
                p["q_norm"], spec, start)
            o = attention(q, k, v, start)
            return (o.transpose(1, 0, 2).reshape(q_block, -1)
                    @ p["attn_out_w"])
        return lax.map(rows, jnp.arange(0, S, q_block)).reshape(a.shape)

    return lax.map(sequence, u)


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


def chosen_weights(scores, sel, spec):
    w = jnp.take_along_axis(scores, sel, -1)
    return w / (w.sum(-1, keepdims=True) + spec["norm_eps"]) * spec[
        "route_scale"]


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
    w = chosen_weights(scores, sel, spec)
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


def layer(x, p, spec, mixer, is_moe, sel=None):
    """x [B, S, hidden]; p the layer's own leaves -> `(x, stats)`."""
    B, S, D = x.shape
    u = rms_norm(x, p["operator_norm"], spec["eps"])
    if mixer == CONV:
        x = x + conv_mixer(u, p)
    else:
        # rematerialised by itself inside the layer: where what follows
        # reads its result, the blocks' loop would else keep every block's
        # mask (`benchmark/reference/joyai.py`)
        x = x + jax.checkpoint(
            lambda u, p: attention_mixer(u, p, spec))(u, p)
    m = rms_norm(x, p["ffn_norm"], spec["eps"]).reshape(B * S, D)
    if not is_moe:
        f = swiglu_by_rows(m, p["mlp_gate_w"], p["mlp_up_w"],
                           p["mlp_down_w"], spec["mlp_block"])
        return x + f.reshape(B, S, D), None
    routed, stats = routed_experts(m, p, spec, sel)
    return x + routed.reshape(B, S, D), stats


def hidden(params, tokens, spec, sel=None):
    """tokens [B, S] -> `(x after the last layer, before the final norm;
    the expert layers' stats, stacked, or None)`."""
    x = params["embed"][tokens - spec["vocab_start"]]
    stats, seen = [], 0
    for (mixer, is_moe, n), group in zip(runs(spec), params["layers"]):
        sels = None
        if is_moe and sel is not None:
            sels = sel[seen:seen + n]
        seen += n if is_moe else 0

        @jax.checkpoint
        def step(x, xs, mixer=mixer, is_moe=is_moe):
            p, s = xs
            return layer(x, p, spec, mixer, is_moe, s)
        x, s = lax.scan(step, x, (group, sels))
        if s is not None:
            stats.append(s)
    if not stats:
        return x, None
    return x, jax.tree.map(lambda *a: jnp.concatenate(a), *stats)


def nll_sum(x, head, targets, ce_block):
    """The sum of the rows' cross-entropies: `x` [N, hidden] against
    `head` [V, hidden]."""
    n = x.shape[0]
    ce_block = _blocks(n, ce_block)

    @jax.checkpoint
    def rows(start):
        xb, tb = (lax.dynamic_slice_in_dim(t, start, ce_block)
                  for t in (x, targets))
        logp = jax.nn.log_softmax(xb @ head.T, axis=-1)
        return -jnp.take_along_axis(logp, tb[:, None], -1).sum()

    return lax.map(rows, jnp.arange(0, n, ce_block)).sum()


def loss(params, batch, spec, sel=None, with_stats=False):
    """batch = (tokens, targets), both [batch, position] int32 ids of the
    slice, targets the tokens one position on; `params` is the program's
    tree, any dtype; `spec` the model's numbers (see
    `benchmark/families/lfm2.py`).  `sel` [expert layers, tokens, k] puts
    somebody else's choice of experts in place of the top-k."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        tokens, targets = batch
        D = params["embed"].shape[-1]
        x, stats = hidden(params, tokens, spec, sel)
        x = rms_norm(x, params["final_ln"], spec["eps"]).reshape(-1, D)
        value = nll_sum(x, params["embed"],
                        (targets - spec["vocab_start"]).reshape(-1),
                        spec["ce_block"]) / targets.size
    return (value, stats) if with_stats else value
