"""Plain float32 reference of the block-diffusion training loss of an
`sdar_moe` decoder (JetLM's SDAR-30B-A3B-Chat), told which experts and
which slice of the vocabulary one chip of a deployment holds.

Written from the model's `config.json` and from block diffusion as it is
published (BD3-LMs, arXiv:2503.09573, section 3 and its vectorised
training; SDAR, arXiv:2510.06303); what neither states is the convention
of the Qwen3-MoE lineage the keys come from or of the family's released
models (the configuration's `assumed` lists each).  Written down, for a
sequence x of L tokens in blocks of beta, block b(i) = i // beta:

    batch = (tokens x [B, L], masked m [B, L], weight w = m / t [B, L]):
       for each block one t = eps + (1 - eps) u, u uniform on [0, 1);
       m_i = 1 with probability t_b(i); made by whoever makes the batch
    x_t = where(m, MASK, x);   rows = [x ; x_t], 2 L of them
    h = embed[rows]                                        (no scale)
    position of row r: r mod L
    every layer:
      a = rms(h; input_ln)
      q, k, v = a Wq, a Wk, a Wv          (no bias; 32 / 4 / 4 heads of 128)
      q, k = rms over the head's 128, learned scale        (ASSUMED)
      q, k = rot(q), rot(k)    half-split layout, theta 1e6, at r mod L
      row r sees key c, clean copy first:
         r <  L:  c < L and b(c) <= b(r)
         r >= L:  (c < L and b(c) < b(r - L))
                  or (c >= L and b(c - L) == b(r - L))
      ctx = softmax over the seen keys of q k^T / sqrt(128), times v; a kv
            head serves 8 query heads
      h = h + ctx Wo
      g = rms(h; post_attention_ln)
      p = softmax(g Wr) over all 128 experts, float32;  sel = top-8(p)
      w_e = p[sel] / sum p[sel]                            (norm_topk_prob)
      h = h + sum_{e in sel, e held} w_e (silu(g Wgate_e) * (g Wup_e)) Wdown_e
    z = rms(h[L:]; final_ln) head^T          the NOISED rows alone, no shift
    loss = mean over samples of (1 / L) sum_i w_i CE(z_i, x_i)

What the experts held elsewhere would add is left out, as in the program;
a share's backward pass holds the weight a token gives the held experts
together constant (`benchmark/reference/mellum.py` says why and how; the
same lines here).

Nothing of byteps_tpu is imported, and nothing of another reference.
What is shared with the program is the layout of its parameter tree: one
group `moe` with leaves stacked on a leading layer axis; `qkv_w`
[hidden, .] holds q, k and v side by side; `expert_*_w` are stacked over
the held experts in the order of `spec["held"]`.

Departures from a naive transcription, each for memory at 2 L = 32,768
rows and none changing the arithmetic of a row: the layers are walked by
`lax.scan` with `jax.checkpoint` around each; attention computes K and V
for all the rows and then walks the query rows in blocks of
`spec["q_block"]`, the MASK built from the rule above a block of rows at
a time as a boolean [rows, 2 L] (the [2 L, 2 L] square is never held),
each block rematerialised; a held expert is computed on every row and
multiplied by the row's weight for it (`lax.scan` over the held experts);
the head walks the rows in blocks of `spec["ce_block"]`.  No kernel, no
grouping of rows, no bfloat16 anywhere: every matmul is float32 at
`highest` precision.

Top-k is discontinuous, so the choice is compared apart from the
arithmetic, as `benchmark/reference/afmoe.py` does: with `sel` given, the
scores and weights are this reference's own but the experts are those
`sel` names, and `stats` says how `sel` differs from this reference's own
top-k, the gap measured in the router's LOGITS.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# the gap of a choice of another SIZE than k: no rounding explains it
WRONG_SIZE = 1e3


def rms_norm(x, scale, eps):
    return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def rotary(x, theta, where):
    """x [..., rows, size], half-split layout; `where` [rows] the rows'
    positions."""
    size = x.shape[-1]
    inv_freq = theta ** (-2 * np.arange(size // 2, dtype=np.float64) / size)
    angles = where.astype(jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :size // 2], x[..., size // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def seen(start, rows, L, beta):
    """The mask's rows `start ... start + rows` of the two copies, a
    boolean [rows, 2 L]: step 3's rule, clean copy first."""
    r = start + jnp.arange(rows)[:, None]
    c = jnp.arange(2 * L)[None, :]
    row_block, key_block = r % L // beta, c % L // beta
    clean_key = c < L
    return jnp.where(r < L, clean_key & (key_block <= row_block),
                     (clean_key & (key_block < row_block))
                     | (~clean_key & (key_block == row_block)))


def attention(q, k, v, start, beta):
    """The rows `start ...` of the two copies of one sequence: q
    [Hkv, G, rows, size] against ALL the keys, k, v [Hkv, 2 L, size].  A
    row's softmax is over all the keys it sees at once."""
    scores = jnp.einsum("kgqd,ksd->kgqs", q, k) / math.sqrt(q.shape[-1])
    keep = seen(start, q.shape[2], k.shape[1] // 2, beta)
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
        # A share's backward pass: the value as it was, the row's weight
        # on the held experts a constant.
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


def attention_half(x, p, spec):
    """x [B, 2 L, hidden] -> ctx Wo.  Keys and values are computed for
    all the rows; then the query rows are walked in blocks of
    `spec["q_block"]`, each block's queries projected, normed, turned,
    attended under its rows of the mask and projected back by itself and
    rematerialised."""
    B, S, D = x.shape
    L = S // 2
    H, Hkv, size = spec["heads"], spec["kv_heads"], spec["head_dim"]
    eps, theta, beta = spec["eps"], spec["theta"], spec["block_length"]
    q_block = min(spec["q_block"], L)
    w_q, w_k, w_v = jnp.split(p["qkv_w"], [H * size, (H + Hkv) * size],
                              axis=-1)

    def heads(t):                       # [rows, n * size] -> [n, rows, size]
        return t.reshape(t.shape[0], -1, size).transpose(1, 0, 2)

    def sequence(a):                    # [2 L, hidden]
        k = rotary(rms_norm(heads(a @ w_k), p["k_norm"], eps), theta,
                   jnp.arange(S) % L)
        v = heads(a @ w_v)

        @jax.checkpoint
        def rows(start):
            ab = lax.dynamic_slice_in_dim(a, start, q_block)
            q = rotary(rms_norm(heads(ab @ w_q), p["q_norm"], eps), theta,
                       (start + jnp.arange(q_block)) % L)
            ctx = attention(q.reshape(Hkv, H // Hkv, q_block, size), k, v,
                            start, beta)
            ctx = ctx.reshape(H, q_block, size).transpose(1, 0, 2)
            return ctx.reshape(q_block, H * size) @ p["attn_out_w"]

        return lax.map(rows, jnp.arange(0, S, q_block)).reshape(S, D)

    return lax.map(sequence, rms_norm(x, p["input_ln"], eps))


def experts_half(x, p, spec, sel=None):
    """x [B, 2 L, hidden] -> `(the held experts' sum, stats)`."""
    B, S, D = x.shape
    m = rms_norm(x, p["post_attn_ln"], spec["eps"])
    f, stats = experts_layer(m.reshape(B * S, D), p, spec, sel)
    return f.reshape(B, S, D), stats


def layer(x, p, spec, sel=None):
    """x [B, 2 L, hidden]; p the layer's own leaves.  Each half is
    rematerialised by itself."""
    x = x + jax.checkpoint(
        lambda x, p: attention_half(x, p, spec))(x, p)
    f, stats = jax.checkpoint(
        lambda x, p, sel: experts_half(x, p, spec, sel))(x, p, sel)
    return x + f, stats


def two_copies(tokens, masked, spec):
    """`[x ; x_t]` [B, 2 L], ids of the held slice counted from its
    first."""
    noised = jnp.where(masked, spec["mask_token"], tokens)
    return jnp.concatenate([tokens, noised], axis=1) - spec["vocab_start"]


def hidden(params, batch, spec, sel=None):
    """-> `(the rows [B, 2 L, hidden] after the last layer, stats stacked
    over the layers or None)`."""
    tokens, masked, _ = batch
    x = params["embed"][two_copies(tokens, masked, spec)]

    @jax.checkpoint
    def step(x, xs):
        p, s = xs
        return layer(x, p, spec, s)

    return lax.scan(step, x, (params["moe"], sel))


def weighted_nll_sum(x, head, targets, weights, ce_block):
    """Sum of `weights` times the cross-entropy of `x` [N, hidden] against
    `head` [V, hidden]."""
    n = x.shape[0]
    ce_block = min(ce_block, n)

    @jax.checkpoint
    def rows(start):
        xb = lax.dynamic_slice_in_dim(x, start, ce_block)
        tb = lax.dynamic_slice_in_dim(targets, start, ce_block)
        wb = lax.dynamic_slice_in_dim(weights, start, ce_block)
        logp = jax.nn.log_softmax(xb @ head.T, axis=-1)
        return -(wb * jnp.take_along_axis(logp, tb[:, None], -1)[:, 0]).sum()

    return lax.map(rows, jnp.arange(0, n, ce_block)).sum()


def loss(params, batch, spec, sel=None, with_stats=False):
    """The block-diffusion loss over the held slice.  batch = (tokens,
    masked, weight), each [batch, token]; `params` is the program's tree,
    any dtype; `spec` the model's numbers (see
    `benchmark/families/sdarmoe.py`).  `sel` [layers, rows, k] puts
    somebody else's choice of experts in place of the top-k."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        tokens, _, weight = batch
        L = tokens.shape[1]
        x, stats = hidden(params, batch, spec, sel)
        x = rms_norm(x[:, L:], params["final_ln"], spec["eps"])
        value = weighted_nll_sum(
            x.reshape(-1, x.shape[-1]), params["head"],
            tokens.reshape(-1) - spec["vocab_start"],
            weight.reshape(-1).astype(jnp.float32),
            spec["ce_block"]) / tokens.size
    return (value, stats) if with_stats else value
