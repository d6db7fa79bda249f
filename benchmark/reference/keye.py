"""Plain float32 reference of the language-model loss of Keye-VL-2.0's
decoder (Kwai-Keye, `model_type: KeyeVL2`), told which experts and which
slice of the vocabulary one chip of a deployment holds.

Written from the model's `config.json`; what its keys do not state is
listed under `assumed` in the configuration.  The layer, written down:

    h = embed[ids]                                         (no scale)
    every layer:
      a = rms(x; input_ln)
      q, k, v = a Wq, a Wk, a Wv          (no bias; 32 / 4 / 4 heads of 128)
      q, k = rms over the head's 128, learned scale        (ASSUMED)
      q, k = R(q), R(k)     half-split layout, theta 1e7, inv_freq_i =
                            theta^(-2i/128); pair i turns by the position
                            stream of its section, [16, 24, 24] pairs of
                            (temporal, height, width); text: all three
                            are 0 .. S - 1
      qI_j = R'(a Wqi_j)            16 indexer heads of 64     (ASSUMED)
      kI = R'(rms(a Wki; learned scale))   ONE indexer key head of 64
      w_j = (a Ww)_j / sqrt(16 x 64)
                            R': the same rotary on the indexer's 32
                            pairs, inv_freq_i = theta^(-2i/64), sections
                            [8, 12, 12]
      I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])
      S_t = the min(t + 1, 2048) keys s <= t of highest I[t, s]; equal
            scores: the lowest key first
      ctx[t, h] = sum_{s in S_t} softmax_{s in S_t}(q[t, h] . k[s, h // 8]
                  / sqrt(128)) v[s, h // 8]
      x = x + ctx Wo
      m = rms(x; post_attention_ln)
      p = softmax(m Wr) over all 128 experts, float32;  sel = top-8(p)
      w = p[sel] / sum p[sel]                              (norm_topk_prob)
      x = x + sum_{e in sel, e held} w_e (silu(m Wgate_e) * (m Wup_e)) Wdown_e
    final rms, untied head, mean next-token cross-entropy over the held rows

The indexer reads a as a CONSTANT and the selection is a constant of the
backward pass: no gradient reaches Wqi, Wki, Ww or the indexer key's
scale, and none reaches a through them.  The vision tower is not here:
ids are text.  What the experts held elsewhere would add is left out, and
a share's backward pass holds the weight a token gives the held experts
together constant, both as `benchmark/reference/mellum.py` says.

Nothing of byteps_tpu is imported.  What is shared with the program is the
layout of its parameter tree: one group `moe` with leaves stacked on a
leading layer axis; `in_w` [hidden, .] holds Wq, Wk, Wv, Wqi, Wki and Ww
side by side; `k_norm` the main keys' scale and then the indexer key's;
`expert_*_w` are stacked over the held experts in the order of
`spec["held"]`.

Departures from a naive transcription, each for memory at 32,768
positions and none changing the arithmetic of a row: the layers are walked
by `lax.scan` with `jax.checkpoint` around each; attention computes K, V
and kI for the whole sequence and then walks the query rows in blocks of
`spec["q_block"]`, each block projected, scored against every key,
selected, attended and projected back by itself (a [32, 64, 32768] float32
block of logits is 268 MB, the block's [16, 64, 32768] indexer products
134 MB; the [S, S] scores are never held); the head the rows in blocks of
`spec["ce_block"]`; a held expert is computed on every token and
multiplied by the token's weight for it.  No kernel, no bfloat16 anywhere:
every matmul is float32 at `highest` precision.

Top-k is discontinuous, TWICE here, so each choice is compared apart from
the arithmetic.  With `sel` given the experts are those `sel` names, as
in `benchmark/reference/mellum.py`.  With `keys` given (uint32
[layers, B, S, S / 32], bit b of word c of row t: the row takes key
32 c + b) attention runs over the keys `keys` names, and `stats` says how
they differ from this reference's own choice: for every row the GAP
between the best score it left out and the worst it took, in this
reference's own float32 scores.  A gap above zero is a set that this
reference would not have chosen; a row whose set has another size than
min(t + 1, topk) reads WRONG_SIZE.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# the gap of a choice of another SIZE than asked: no rounding explains it
WRONG_SIZE = 1e3


def rms_norm(x, scale, eps):
    return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def rotary(x, theta, positions, sections):
    """x [..., rows, size], half-split layout; `positions` [3, rows] the
    three streams of these rows; `sections` how many pairs, in order,
    turn by each."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-np.arange(half, dtype=np.float64) / half)
    stream = np.repeat(np.arange(len(sections)), sections)     # [half]
    where = positions.astype(jnp.float32)[stream].T             # [rows, half]
    angles = where * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def index_scores(qi, ki, w):
    """I [rows, S]: qi [J, rows, Di], ki [S, Di], w [rows, J]."""
    products = jax.nn.relu(jnp.einsum("jrd,sd->jrs", qi, ki))
    scores = jnp.einsum("rj,jrs->rs", w, products)
    return jnp.where(scores == 0.0, 0.0, scores)


def unpack(words, seq_len):
    """uint32 [rows, S / 32] -> bool [rows, S]."""
    bits = (words[:, :, None] >> jnp.arange(32, dtype=jnp.uint32)) & 1
    return bits.reshape(words.shape[0], seq_len).astype(bool)


def own_keys(scores, visible, topk):
    """This reference's own choice: bool [rows, S], the `topk` highest of
    the visible scores a row (`lax.top_k`: equal scores, the lowest key
    first), all of them where fewer are visible."""
    _, chosen = lax.top_k(jnp.where(visible, scores, -jnp.inf),
                          min(topk, scores.shape[-1]))
    mine = jax.vmap(lambda at: jnp.zeros(scores.shape[-1], bool)
                    .at[at].set(True))(chosen)
    return mine & visible


def key_stats(scores, visible, keep, topk):
    """How the choice `keep` differs from this reference's own, a row:
    the gap between the best visible score left out and the worst taken
    (at most 0 where `keep` is a set this reference could have chosen),
    WRONG_SIZE where its size is not min(visible, topk)."""
    left_out = jnp.where(visible & ~keep, scores, -jnp.inf).max(-1)
    worst = jnp.where(keep, scores, jnp.inf).min(-1)
    gap = jnp.where(jnp.isfinite(left_out), left_out - worst, 0.0)
    size = jnp.minimum(visible.sum(-1), topk)
    wrong = ((keep & visible).sum(-1) != size) | (keep & ~visible).any(-1)
    return jnp.where(wrong, WRONG_SIZE, gap)


def attention(q, k, v, keep):
    """q [Hkv, G, rows, size] against k, v [Hkv, S, size] under the mask
    `keep` [rows, S]; a row's softmax is over all its keys at once."""
    scores = jnp.einsum("kgqd,ksd->kgqs", q, k) / math.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), -1)
    return jnp.einsum("kgqs,ksd->kgqd", probs, v)


def swiglu(x, gate_w, up_w, down_w):
    return (jax.nn.silu(x @ gate_w) * (x @ up_w)) @ down_w


def selection_stats(logits, sel, own):
    """How the choice of experts `sel` differs from this reference's
    `own` top-k, both [T, k], given its router `logits` [T, E]."""
    ids = jnp.arange(logits.shape[-1])
    took = (sel[..., None] == ids).any(-2)
    mine = (own[..., None] == ids).any(-2)
    left_out = jnp.where(mine & ~took, logits, -jnp.inf).max(-1)
    instead = jnp.where(took & ~mine, logits, jnp.inf).min(-1)
    differs = (took != mine).any(-1)
    gap = jnp.where(differs, left_out - instead, 0.0)
    gap = jnp.where(took.sum(-1) != mine.sum(-1), WRONG_SIZE, gap)
    return {"swapped_tokens": differs.sum(), "gaps": gap}


def chosen_weights(scores, sel, norm_topk_prob):
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
        # a share's backward pass: the value as it was, the token's
        # weight on the held experts together a constant
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


def split_in(p, spec):
    """`in_w` and `k_norm` taken apart: Wq, Wk, Wv, Wqi, Wki, Ww and the
    two key scales."""
    H, Hkv, size = spec["heads"], spec["kv_heads"], spec["head_dim"]
    J, Di = spec["index_heads"], spec["index_head_dim"]
    edges = np.cumsum([H * size, Hkv * size, Hkv * size, J * Di, Di])
    return (*jnp.split(p["in_w"], edges, axis=-1),
            p["k_norm"][:size], p["k_norm"][size:])


def attention_half(x, p, spec, positions, keys=None):
    """x [B, S, hidden], `positions` [3, B, S] -> `(ctx Wo, gaps [B, S] or
    None)`.  K, V and the indexer's key are computed for the whole
    sequence; then the query rows are walked in blocks of
    `spec["q_block"]`, each projected, scored, selected (or given its keys
    by `keys` [B, S, S / 32]), attended and projected back by itself."""
    B, S, D = x.shape
    H, Hkv, size = spec["heads"], spec["kv_heads"], spec["head_dim"]
    J, Di, topk = spec["index_heads"], spec["index_head_dim"], spec["topk"]
    eps, theta, q_block = spec["eps"], spec["theta"], min(spec["q_block"], S)
    sections = tuple(spec["sections"])
    narrow = tuple(n * Di // size for n in sections)
    w_q, w_k, w_v, w_qi, w_ki, w_w, k_scale, ki_scale = split_in(p, spec)

    def heads(t, n):                    # [rows, n * size] -> [n, rows, size]
        return t.reshape(t.shape[0], n, -1).transpose(1, 0, 2)

    def sequence(args):
        a, where, words = args          # [S, hidden], [3, S], [S, S/32]|None
        k = rotary(rms_norm(heads(a @ w_k, Hkv), k_scale, eps), theta, where,
                   sections)
        v = heads(a @ w_v, Hkv)
        fixed = lax.stop_gradient(a)
        ki = rotary(rms_norm(fixed @ w_ki, ki_scale, eps), theta, where,
                    narrow)

        @jax.checkpoint
        def rows(start):
            ab = lax.dynamic_slice_in_dim(a, start, q_block)
            at = lax.dynamic_slice_in_dim(where, start, q_block, axis=1)
            q = rotary(rms_norm(heads(ab @ w_q, H), p["q_norm"], eps), theta,
                       at, sections)
            fb = lax.stop_gradient(ab)
            qi = rotary(heads(fb @ w_qi, J), theta, at, narrow)
            scores = lax.stop_gradient(
                index_scores(qi, ki, (fb @ w_w) / math.sqrt(J * Di)))
            t = start + jnp.arange(q_block)[:, None]
            visible = jnp.arange(S)[None, :] <= t
            if words is None:
                keep, gaps = own_keys(scores, visible, topk), None
            else:
                keep = unpack(lax.dynamic_slice_in_dim(words, start, q_block),
                              S)
                gaps = key_stats(scores, visible, keep, topk)
                keep = keep & visible
            ctx = attention(q.reshape(Hkv, H // Hkv, q_block, size), k, v,
                            keep)
            ctx = ctx.reshape(H, q_block, size).transpose(1, 0, 2)
            return ctx.reshape(q_block, H * size) @ p["attn_out_w"], gaps

        out, gaps = lax.map(rows, jnp.arange(0, S, q_block))
        return out.reshape(S, D), None if gaps is None else gaps.reshape(S)

    return lax.map(sequence, (rms_norm(x, p["input_ln"], eps),
                              positions.transpose(1, 0, 2), keys))


def experts_half(x, p, spec, sel=None):
    B, S, D = x.shape
    m = rms_norm(x, p["post_attn_ln"], spec["eps"])
    f, stats = experts_layer(m.reshape(B * S, D), p, spec, sel)
    return f.reshape(B, S, D), stats


def layer(x, p, spec, positions, sel=None, keys=None):
    """x [B, S, hidden]; p the layer's own leaves.  Each half is
    rematerialised by itself."""
    ctx, gaps = jax.checkpoint(
        lambda x, p, keys: attention_half(x, p, spec, positions, keys))(
            x, p, keys)
    x = x + ctx
    f, stats = jax.checkpoint(
        lambda x, p, sel: experts_half(x, p, spec, sel))(x, p, sel)
    found = {**(stats or {}), **({} if gaps is None else {"key_gaps": gaps})}
    return x + f, found or None


def text_positions(tokens):
    """The three streams of a text batch: 0 .. S - 1, three times."""
    B, S = tokens.shape
    return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (3, B, S))


def hidden(params, tokens, spec, sel=None, keys=None, positions=None):
    """tokens [B, S] -> `(final hidden states, stats stacked over the
    layers or None)`."""
    x = params["embed"][tokens - spec["vocab_start"]]
    if positions is None:
        positions = text_positions(tokens)

    @jax.checkpoint
    def step(x, xs):
        p, s, k = xs
        return layer(x, p, spec, positions, s, k)

    x, stats = lax.scan(step, x, (params["moe"], sel, keys))
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


def loss(params, batch, spec, sel=None, keys=None, positions=None,
         with_stats=False):
    """Mean next-token cross-entropy over the held slice.  batch =
    (tokens, targets), both [batch, position] int32 ids of the slice;
    `params` is the program's tree, any dtype; `spec` the model's numbers
    (see `benchmark/families/keye.py`).  `sel` [layers, tokens, k] puts
    somebody else's choice of experts in place of the top-k, `keys`
    [layers, B, S, S / 32] somebody else's choice of keys in place of the
    top-2048; `positions` [3, B, S] other streams than a text batch's."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        tokens, targets = batch
        x, stats = hidden(params, tokens, spec, sel, keys, positions)
        value = nll_mean(x.reshape(-1, x.shape[-1]), params["head"],
                         targets.reshape(-1) - spec["vocab_start"],
                         spec["ce_block"])
    return (value, stats) if with_stats else value
