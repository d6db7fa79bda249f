"""Plain float32 reference of the `kimi_linear` language-model loss
(Moonshot's Kimi Linear models; arXiv:2510.26692), told which layers,
which experts and which slice of the vocabulary one chip of a deployment
holds.

Written from the layer equations as the configuration's `assumed` states
them (the report and its modelling code were not read here: no network).
With x = embed[ids] (no scale), a layer is x = x + mixer(rms(x; input_ln)),
x = x + ffn(rms(x; post_ln)), the mixer one of

    KDA, position by position, a head h of K = 128 channels:
        [q | k | v]_t = silu(sum_i w_i (u W_qkv)_{t-3+i})   4 taps a channel,
                        zeros before the sequence's first position
        q_t <- q_t / sqrt(|q_t|^2 + 1e-6) / sqrt(K);  k_t likewise, no scale
        g_t = -exp(A_log_h) softplus((u_t W_fa) W_fb + dt_bias)    [K], <= 0
        beta_t = sigmoid(u_t W_b)
        S_t = Diag(exp(g_t)) S_{t-1}                         the decay FIRST
        S_t <- S_t - beta_t k_t (k_t^T S_t) + beta_t k_t v_t^T
        o_t = S_t^T q_t                                      S_0 = 0 [K, V]
        y_t = rms(o_t; o_norm) * sigmoid((u_t W_ga) W_gb);  out = y W_o
    latent attention WITHOUT positions:
        q_{t,h} = u_t W_q,h                                  nope + rope wide
        [c_t | kr_t] = u_t W_down
        [kn_{t,h} | v_{t,h}] = rms(c_t; kv_a_ln) W_up,h
        o_{t,h} = sum_{s<=t} softmax_s((q_{t,h}[:nope] . kn_{s,h}
                  + q_{t,h}[nope:] . kr_s) / sqrt(nope + rope)) v_{s,h}
        out = concat_h(o) W_o

and the feed-forward the dense SwiGLU (the first `dense_layers` layers) or

    s = sigmoid(m W_r) over ALL the experts
    choice = top-k(s + expert_bias)   (the bias: a leaf where the tree has
             it, else zero; no gradient)
    w = route_scale * s[choice] / (sum s[choice] + 1e-20)
    shared(m) + sum_{e chosen, e held} w_e expert_e(m)

then a final RMS norm, the UNTIED head and the mean next-token
cross-entropy over the held rows.  What the experts held elsewhere would
add is left out, as in the program.

A SHARE'S BACKWARD PASS (the program's
`dropless_moe.MoEConfig.hold_held_weight`, the same here, as
`benchmark/reference/mellum.py` says it): where fewer experts are held
than the router scores, the weight a token gives the held experts
together is a constant of the backward pass, w := w stop(W) / W.

Nothing of byteps_tpu is imported, and there is NO CHUNK ALGEBRA: the
state is walked a position at a time, so this file is independent of the
form the program gives the scan.  An expert is computed on every token
and multiplied by the token's weight for it.  What is shared with the
program is the layout of its parameter tree: `params["layers"]` a list of
RUNS (consecutive layers of one mixer and one feed-forward), a run's
leaves stacked on a leading layer axis; `qkv_w` [hidden, 3 W] holds q, k
and v side by side and `conv_w` [4, 3 W] their taps, `conv_w[3]` meeting
the current position; a head's columns of `kv_up_w` are `[kn | v]`;
`down_w` is `[c | kr]`; `expert_*_w` are stacked over the held experts in
the order of `spec["held"]`.

Departures from a naive transcription, each for memory at the cell's
length and none changing the arithmetic of a row: a run's layers are
walked by `lax.scan` with `jax.checkpoint` around each; the KDA mixer
walks the heads `spec["head_block"]` at a time (everything in it but the
two ends is a head's own), and inside, the positions in blocks of
`spec["scan_block"]`, each block rematerialised, so that the backward
pass holds a state a block and not a state a position; attention walks
the query rows in blocks of `spec["q_block"]`, the dense and shared
feed-forwards their rows in blocks of `spec["mlp_block"]`, the head in
blocks of `spec["ce_block"]`; the routed experts are a `lax.scan` over
the held ids.  No kernel, no bfloat16: every matmul is float32 at
`highest` precision.

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

KDA, MLA = "kda", "mla"


def rms_norm(x, scale, eps):
    return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _blocks(n, block):
    block = min(block, n)
    if n % block:
        raise ValueError(f"{n} rows do not come in blocks of {block}")
    return block


# ---------------------------------------------------------------------------
# KDA
# ---------------------------------------------------------------------------
def conv_silu(x, taps):
    """x [S, C], taps [K, C], `taps[K-1]` meeting the current position and
    zeros standing before the first: silu of the K shifted sums."""
    K, S = taps.shape[0], x.shape[0]
    padded = jnp.pad(x, ((K - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[i:i + S] * taps[i] for i in range(K)))


def l2_normed(x, eps=1e-6):
    return x * lax.rsqrt((x * x).sum(-1, keepdims=True) + eps)


def recurrence(q, k, v, g, beta, scan_block):
    """The delta rule, a position at a time: q, k, g [S, H, K], v
    [S, H, V], beta [S, H]; q and k as the convolution left them (normed
    here) -> o [S, H, V]."""
    S, H, K = q.shape
    q = l2_normed(q) / math.sqrt(K)
    k = l2_normed(k)
    block = _blocks(S, scan_block)

    def position(state, xs):
        q, k, v, g, beta = xs
        state = jnp.exp(g)[:, :, None] * state               # the decay
        seen = jnp.einsum("hk,hkv->hv", k, state)
        state = state + (beta[:, None] * k)[:, :, None] * (v - seen)[:, None]
        return state, jnp.einsum("hk,hkv->hv", q, state)

    @jax.checkpoint
    def rows(state, xs):
        return lax.scan(position, state, xs)

    def cut(t):
        return t.reshape(S // block, block, *t.shape[1:])
    _, o = lax.scan(rows, jnp.zeros((H, K, v.shape[-1]), q.dtype),
                    tuple(cut(t) for t in (q, k, v, g, beta)))
    return o.reshape(S, H, -1)


def kda_operands(u, p):
    """`(q, k, v, g, beta)` of u [S, hidden] under a KDA layer's leaves `p`
    (all its heads, or the heads whose columns `p` holds): q, k, v after
    the convolution and silu, NOT normed; g and beta as the recurrence
    takes them."""
    H, K = p["A_log"].shape[0], p["o_norm"].shape[0]
    qkv = conv_silu(u @ p["qkv_w"], p["conv_w"]).reshape(-1, 3, H, K)
    f = ((u @ p["f_a_w"]) @ p["f_b_w"] + p["dt_bias"]).reshape(-1, H, K)
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(f)
    return (qkv[:, 0], qkv[:, 1], qkv[:, 2], g,
            jax.nn.sigmoid(u @ p["beta_w"]))


def heads_of(p, first, n):
    """A KDA layer's leaves cut to the heads `first ... first + n` (every
    leaf but the two low-rank pairs' first halves and the head norm is a
    head's own columns; `g_b_w` and `out_w` too, which `kda_operands` does
    not read)."""
    H, K = p["A_log"].shape[0], p["o_norm"].shape[0]

    def take(t, axis):
        return jnp.take(t, first + jnp.arange(n), axis=axis)
    return {
        "qkv_w": take(p["qkv_w"].reshape(-1, 3, H, K), 2).reshape(
            -1, 3 * n * K),
        "conv_w": take(p["conv_w"].reshape(-1, 3, H, K), 2).reshape(
            -1, 3 * n * K),
        "A_log": take(p["A_log"], 0), "o_norm": p["o_norm"],
        "f_a_w": p["f_a_w"], "g_a_w": p["g_a_w"],
        "f_b_w": take(p["f_b_w"].reshape(-1, H, K), 1).reshape(-1, n * K),
        "g_b_w": take(p["g_b_w"].reshape(-1, H, K), 1).reshape(-1, n * K),
        "dt_bias": take(p["dt_bias"].reshape(H, K), 0).reshape(-1),
        "beta_w": take(p["beta_w"], 1),
        "out_w": take(p["out_w"].reshape(H, K, -1), 0).reshape(n * K, -1)}


def kda(x, p, spec):
    """x [B, S, hidden] (already normed) -> the mixer's result."""
    H, K = p["A_log"].shape[0], p["o_norm"].shape[0]
    hb = min(spec["head_block"], H)
    if H % hb:
        raise ValueError(f"{H} heads do not come in blocks of {hb}")

    def sequence(u):
        @jax.checkpoint
        def some(first):
            mine = heads_of(p, first, hb)
            o = recurrence(*kda_operands(u, mine), spec["scan_block"])
            z = (u @ mine["g_a_w"]) @ mine["g_b_w"]
            y = rms_norm(o, p["o_norm"], spec["eps"]) * jax.nn.sigmoid(
                z.reshape(-1, hb, K))
            return y.reshape(-1, hb * K) @ mine["out_w"]

        def add(acc, first):
            return acc + some(first), None
        return lax.scan(add, jnp.zeros_like(u), jnp.arange(0, H, hb))[0]

    return lax.map(sequence, x)


# ---------------------------------------------------------------------------
# Latent attention without positions
# ---------------------------------------------------------------------------
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
    """x [B, S, hidden] (already normed) -> concat_h(o) W_O."""
    H, nope, rkv, eps = (spec["heads"], spec["nope"], spec["kv_lora"],
                         spec["eps"])

    def heads(t):                       # [rows, H * size] -> [H, rows, size]
        return t.reshape(t.shape[0], H, -1).transpose(1, 0, 2)

    def sequence(a):                    # [S, hidden]
        S = a.shape[0]
        q_block = _blocks(S, spec["q_block"])
        down = a @ p["down_w"]
        kv = heads(rms_norm(down[:, :rkv], p["kv_a_ln"], eps) @ p["kv_up_w"])
        kn, v, kr = kv[..., :nope], kv[..., nope:], down[:, rkv:]

        @jax.checkpoint
        def rows(start):
            q = heads(lax.dynamic_slice_in_dim(a, start, q_block) @ p["q_w"])
            o = attention(q[..., :nope], q[..., nope:], kn, kr, v, start)
            return (o.transpose(1, 0, 2).reshape(q_block, -1)
                    @ p["attn_out_w"])
        return lax.map(rows, jnp.arange(0, S, q_block)).reshape(a.shape)

    return lax.map(sequence, x)


# ---------------------------------------------------------------------------
# Feed-forwards
# ---------------------------------------------------------------------------
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


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------
def layer(x, p, spec, mixer, is_moe, sel=None):
    """x [B, S, hidden]; p the layer's own leaves -> `(x, stats)`."""
    B, S, D = x.shape
    mix = kda if mixer == KDA else latent_attention
    # rematerialised by itself inside the layer: where what follows reads
    # its result, the blocks' loops would else keep every block's own
    x = x + jax.checkpoint(lambda x, p: mix(
        rms_norm(x, p["input_ln"], spec["eps"]), p, spec))(x, p)
    m = rms_norm(x, p["post_ln"], spec["eps"]).reshape(B * S, D)
    if not is_moe:
        f = swiglu_by_rows(m, p["mlp_gate_w"], p["mlp_up_w"],
                           p["mlp_down_w"], spec["mlp_block"])
        return x + f.reshape(B, S, D), None
    routed, stats = routed_experts(m, p, spec, sel)
    shared = swiglu_by_rows(m, p["shared_gate_w"], p["shared_up_w"],
                            p["shared_down_w"], spec["mlp_block"])
    return x + (shared + routed).reshape(B, S, D), stats


def runs(spec):
    """`(mixer, is_moe, layers)` of every run, as the program stacks
    them."""
    out = []
    for i, mixer in enumerate(spec["layer_types"]):
        kind = (mixer, i >= spec["dense_layers"])
        if out and out[-1][:2] == kind:
            out[-1] = (*kind, out[-1][2] + 1)
        else:
            out.append((*kind, 1))
    return out


def hidden(params, tokens, spec, sel=None):
    """tokens [B, S] -> `(x after the last layer, BEFORE the final norm;
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
        x, st = lax.scan(step, x, (group, sels))
        if is_moe and st is not None:
            stats.append(st)
    return x, (jax.tree.map(lambda *a: jnp.concatenate(a), *stats)
               if stats else None)


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
    slice; `params` is the program's tree, any dtype; `spec` the model's
    numbers (see `benchmark/families/kimilinear.py`).  `sel` [expert
    layers, tokens, k] puts somebody else's choice of experts in place of
    the top-k."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        tokens, targets = batch
        D = params["embed"].shape[-1]
        x, stats = hidden(params, tokens, spec, sel)
        value = nll_sum(
            rms_norm(x, params["final_ln"], spec["eps"]).reshape(-1, D),
            params["head"], (targets - spec["vocab_start"]).reshape(-1),
            spec["ce_block"]) / targets.size
    return (value, stats) if with_stats else value
