"""Plain float32 reference of the `afmoe` language-model loss (Arcee's
Trinity family), told which experts and which slice of the vocabulary one
chip of a deployment holds.

Written from the model's `config.json` and its public modelling code
(`transformers`, `models/afmoe`).  With `h = embed[ids] * sqrt(hidden)`
(`mup_enabled`), every layer is

    a = rms(x; input_ln)
    q, k, v, g = a Wq, a Wk, a Wv, a Wg          (Wg: hidden -> heads * size)
    q, k = rms over the head's size, learned scales (q_norm, k_norm)
    rotary positions on q, k (half-split, theta)   IN SLIDING LAYERS ONLY
    ctx = causal softmax(q k^T / sqrt(size)) v, in a sliding layer over the
          keys i - window < j <= i; a key-value head serves
          heads / kv_heads query heads
    x = x + rms((ctx * sigmoid(g)) Wo; post_attn_ln)
    m = rms(x; pre_mlp_ln)
    dense layers:  f = (silu(m Wgate) * (m Wup)) Wdown
    expert layers: s = sigmoid(m Wr) over all the experts
                   sel = top-k(s + expert_bias)
                   w = s[sel] / (sum s[sel] + 1e-20) * route_scale
                   f = shared(m) + sum_{e in sel, e held} w_e expert_e(m)
    x = x + rms(f; post_mlp_ln)

then a final RMS norm, an untied head and the mean next-token
cross-entropy, over the held rows of embedding and head.  What the experts
held elsewhere would add is left out, as in the program.

Nothing of byteps_tpu is imported.  What is shared with the program is
the layout of its parameter tree: groups `dense` and `moe` with leaves
stacked on a leading layer axis; `qkvg_w` [hidden, .] holds q, k, v and
the gate side by side; `expert_*_w` are stacked over the held experts in
the order of `spec["held"]`; `expert_bias`, where a group has the leaf, is
added before the top-k.

Departures from a naive transcription, each for memory at 8192 positions
and none changing the arithmetic of a row: the layers are walked in a
Python loop with `jax.checkpoint` around each; attention walks the query
rows in blocks of `spec["q_block"]` and the head the rows in blocks of
`spec["ce_block"]` (`lax.map`, each block rematerialised), a row's softmax
being taken over all its keys, or all the held logits, at once; a held
expert is computed on every token and multiplied by the token's weight for
it, zero where the token did not choose it (`lax.scan` over the held
experts).  No kernel, no grouping of rows, no bfloat16 anywhere: every
matmul is float32 at `highest` precision.

Top-k is discontinuous, so the choice is compared apart from the
arithmetic.  With `sel` given, the scores and weights are this reference's
own but the experts are those `sel` names, and `stats` says how `sel`
differs from this reference's own top-k: the tokens whose sets differ, and
for each the gap between the best score it left out and the worst it took
instead.  A choice made from scores that were a little different swaps
only experts whose scores here are nearly equal.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

SLIDING = "sliding_attention"


def rms_norm(x, scale, eps):
    return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def rotary(x, theta):
    """x [B, H, S, size], half-split layout."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window, q_block):
    """q [B, Hkv, G, S, size]; k, v [B, Hkv, S, size]; `window` None in a
    full layer."""
    n = q.shape[3]
    q_block = min(q_block, n)
    cols = jnp.arange(n)[None, :]

    @jax.checkpoint
    def rows(start):
        qb = lax.dynamic_slice_in_dim(q, start, q_block, axis=3)
        scores = jnp.einsum("bkgqd,bksd->bkgqs", qb, k) / math.sqrt(
            q.shape[-1])
        i = start + jnp.arange(q_block)[:, None]
        keep = i >= cols
        if window is not None:
            keep &= i - cols < window
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), -1)
        return jnp.einsum("bkgqs,bksd->bkgqd", probs, v)

    out = lax.map(rows, jnp.arange(0, n, q_block))    # [blocks, B,Hkv,G,qb,d]
    return jnp.moveaxis(out, 0, 3).reshape(q.shape)


def swiglu(x, gate_w, up_w, down_w):
    return (jax.nn.silu(x @ gate_w) * (x @ up_w)) @ down_w


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
    return {"swapped_tokens": differs.sum(), "max_gap": gap.max(),
            "gaps": gap}


def experts_layer(m, p, spec, sel=None):
    """m [T, hidden] -> `(f, stats)`."""
    scores = jax.nn.sigmoid(m @ p["router_w"])
    biased = scores + p["expert_bias"] if "expert_bias" in p else scores
    _, own = lax.top_k(lax.stop_gradient(biased), spec["top_k"])
    stats = None
    if sel is None:
        sel = own
    else:
        stats = selection_stats(lax.stop_gradient(biased), sel, own)
    w = jnp.take_along_axis(scores, sel, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * spec["route_scale"]

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
    shared = swiglu(m, p["shared_gate_w"], p["shared_up_w"],
                    p["shared_down_w"])
    return shared + routed, stats


def layer(x, p, spec, kind, is_moe, sel=None):
    """x [B, S, hidden]; p the layer's own leaves."""
    B, S, D = x.shape
    H, Hkv, size = spec["heads"], spec["kv_heads"], spec["head_dim"]
    eps = spec["eps"]
    a = rms_norm(x, p["input_ln"], eps)
    q, k, v, g = jnp.split(
        a @ p["qkvg_w"],
        [H * size, (H + Hkv) * size, (H + 2 * Hkv) * size], axis=-1)

    def heads(t):
        return t.reshape(B, S, -1, size).transpose(0, 2, 1, 3)
    q = rms_norm(heads(q), p["q_norm"], eps)
    k = rms_norm(heads(k), p["k_norm"], eps)
    v = heads(v)
    if kind == SLIDING:
        q, k = rotary(q, spec["theta"]), rotary(k, spec["theta"])
    ctx = attention(q.reshape(B, Hkv, H // Hkv, S, size), k, v,
                    spec["window"] if kind == SLIDING else None,
                    spec["q_block"])
    ctx = ctx.reshape(B, H, S, size).transpose(0, 2, 1, 3).reshape(B, S, -1)
    o = (ctx * jax.nn.sigmoid(g)) @ p["attn_out_w"]
    x = x + rms_norm(o, p["post_attn_ln"], eps)

    m = rms_norm(x, p["pre_mlp_ln"], eps)
    stats = None
    if is_moe:
        f, stats = experts_layer(m.reshape(B * S, D), p, spec, sel)
        f = f.reshape(B, S, D)
    else:
        f = swiglu(m, p["mlp_gate_w"], p["mlp_up_w"], p["mlp_down_w"])
    return x + rms_norm(f, p["post_mlp_ln"], eps), stats


def unstack(group):
    """The layers' own leaves from a group's leaves, which are stacked on
    a leading layer axis.  A split and not n indexings: the gradient of a
    split is one concatenate, that of n indexings n padded copies to sum
    (gigabytes, at the published widths)."""
    n = next(iter(group.values())).shape[0]
    pieces = {k: lax.split(a, (1,) * n) for k, a in group.items()}
    return [{k: pieces[k][j][0] for k in group} for j in range(n)]


def hidden(params, tokens, spec, sel=None):
    """tokens [B, S] -> `(final hidden states, [stats of each expert
    layer])`."""
    x = params["embed"][tokens - spec["vocab_start"]] * math.sqrt(
        params["embed"].shape[-1])
    nd = spec["dense_layers"]
    all_stats = []
    layers = {g: unstack(params[g]) for g in ("dense", "moe") if g in params}
    for i, kind in enumerate(spec["layer_types"]):
        is_moe = i >= nd
        group, j = ("moe", i - nd) if is_moe else ("dense", i)
        p = layers[group][j]
        s = sel[j] if is_moe and sel is not None else None
        x, stats = jax.checkpoint(
            lambda x, p, s, kind=kind, is_moe=is_moe:
            layer(x, p, spec, kind, is_moe, s))(x, p, s)
        if stats is not None:
            all_stats.append(stats)
    return rms_norm(x, params["final_ln"], spec["eps"]), all_stats


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
    (see `benchmark/families/afmoe.py`).  `sel` [expert layers, tokens, k]
    puts somebody else's choice of experts in place of the top-k."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        tokens, targets = batch
        x, stats = hidden(params, tokens, spec, sel)
        value = nll_mean(x.reshape(-1, x.shape[-1]), params["head"],
                         targets.reshape(-1) - spec["vocab_start"],
                         spec["ce_block"])
    return (value, stats) if with_stats else value


def logits(params, tokens, spec):
    """The held slice's logits, [batch, position, held rows]."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        return hidden(params, tokens, spec)[0] @ params["head"].T
