"""Plain float32 reference of the `nemotron_h` language-model loss (the
causal context tower of NVIDIA's Nemotron-Labs-TwoTower-30B-A3B-Base,
`model_type: nemotron_h`), told which layers, which experts and which
slice of the vocabulary one chip of a deployment holds.

Written from the model's `config.json` and the family's public modelling
code (`transformers`, `models/nemotron_h`); what neither states is listed
in the configuration's `assumed`.  `hybrid_override_pattern` names each
layer's ONE part; with h = embed[ids] (no scale) a layer is

    x = x + f(rms(x; norm))

    M:  [z | xBC | dt] = u W_in          widths inner | inner + 2 G N | H
        xBC_t = silu(bias + sum_k w[k] xBC_{t-(K-1)+k})   depthwise, causal
        [x | B | C] = xBC; x as H heads of P; B, C [G, N], head h reads
        group h // (H / G)
        dt = softplus(dt + dt_bias);  A = -exp(A_log)          a head
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t       S_0 = 0, [P, N]
        y_t = S_t C_t + D x_t
        y = y * silu(z), THEN rms over each of the G groups of inner / G
        channels apart, one learned scale [inner];  f = y W_out
    E:  s = sigmoid(u W_r), [T, experts]; the choice is the top k of
        s + b (b the `e_score_correction_bias`: the leaf `expert_bias`
        where the tree has it, else zero; no gradient reaches it);
        w = route_scale * s[choice] / (sum s[choice] + 1e-20)
        f = sum_{e chosen, e held} w_e relu(u V_e)^2 U_e + relu(u V_s)^2 U_s
    *:  q, k, v = u Wq, u Wk, u Wv    no bias, NO positions of any kind
        ctx = causal softmax(q k^T / sqrt(size)) v, a key-value head
        serving heads / kv_heads query heads;  f = ctx Wo

then a final RMS norm, the UNTIED head and the mean next-token
cross-entropy over the held rows.  What the experts held elsewhere would
add is left out, as in the program.  The released model runs a second
tower over this one (a denoiser); nothing of it is here.

A SHARE'S BACKWARD PASS (the program's
`dropless_moe.MoEConfig.hold_held_weight`, the same here, as
`benchmark/reference/mellum.py` says it): where fewer experts are held
than the router scores, the weight a token gives the held experts
together is a constant of the backward pass, w := w stop(W) / W.

Nothing of byteps_tpu is imported.  The recurrence is computed AS a
recurrence, one position after another (`lax.scan` over the positions),
never in the chunked form the program uses; an expert is computed on every
token and multiplied by the token's weight for it, zero where the token
did not choose it (a loop over the held ids, no grouping of rows).  What
is shared with the program is the layout of its parameter tree: one stack
of leaves a KIND of layer (`mamba`, `moe`, `attention`), stacked over the
layers of that kind in the order they run; `in_proj_w` [hidden, .] holds
z, xBC and dt side by side, `qkv_w` q, k and v; `conv_w` is
[K, channels] with the taps in front, `conv_w[K - 1]` meeting the current
position; `expert_*_w` are stacked over the held experts in the order of
`spec["held"]`.

Departures from a naive transcription, each for memory at 16,384
positions and none changing the arithmetic of a row: the layers are
walked with `jax.checkpoint` around each, so that one layer's float32
activations are alive at a time, and where they come as rounds of `ME`
or `M*E` (the published pattern does) by `lax.scan` over the rounds
(`hidden` says why); attention walks the
query rows in blocks of `spec["q_block"]`, the shared expert its rows in
blocks of `spec["mlp_block"]`, the head in blocks of `spec["ce_block"]`
(`lax.map`, each block rematerialised), a row's softmax being taken over
all its keys, or all the held logits, at once; the routed experts are a
`lax.scan` over the held ids, each step rematerialised; a mixer walks
the positions in blocks of `spec["mamba_block"]`, handing on the
recurrence's state and the convolution's last inputs (`mamba_part`), and
inside a block the recurrence is rematerialised by segments of
`spec["scan_segment"]` positions.  No
kernel, no chunked form, no bfloat16 anywhere: every matmul is float32 at
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

MAMBA, MOE, ATTENTION = "mamba", "moe", "attention"


def rms_norm(x, scale, eps):
    return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _blocks(n, block):
    block = min(block, n)
    if n % block:
        raise ValueError(f"{n} rows do not come in blocks of {block}")
    return block


# ---------------------------------------------------------------------------
# M
# ---------------------------------------------------------------------------
def causal_conv(x, w, bias, before=None):
    """x [B, S, C]; w [K, C]; y_t = bias + sum_k w[k] x_{t-(K-1)+k};
    `before` [B, K - 1, C] is what stood before the first position (None:
    zeros, a sequence's start)."""
    K = w.shape[0]
    S = x.shape[1]
    if before is None:
        before = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    padded = jnp.concatenate([before, x], axis=1)
    out = jnp.broadcast_to(bias, x.shape)
    for k in range(K):
        out = out + lax.slice_in_dim(padded, k, k + S, axis=1) * w[k]
    return out


def recurrence(x, dt, a, bm, cm, d, segment, state=None, with_state=False):
    """x [B, S, H, P]; dt [B, S, H]; a [H] (negative); bm, cm [B, S, G, N];
    d [H] -> y [B, S, H, P], one position at a time; head h reads group
    h // (H / G).  `state` [B, H, P, N] is what the positions before left
    (None: zeros); with `with_state` the result is `(y, the state the
    last position leaves)`."""
    B, S, H, P = x.shape
    G, N = bm.shape[2], bm.shape[3]
    segment = _blocks(S, segment)

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp      # [B, H, P], [B, H], [B, G, N] twice
        b_t = jnp.repeat(b_t, H // G, axis=1)
        c_t = jnp.repeat(c_t, H // G, axis=1)
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        y_t = jnp.einsum("bhpn,bhn->bhp", state, c_t) + d[:, None] * x_t
        return state, y_t

    @jax.checkpoint
    def some(state, inps):
        return lax.scan(step, state, inps)

    def by_segment(t):                 # [B, S, ...] -> [S/seg, seg, B, ...]
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape(S // segment, segment, *t.shape[1:])

    if state is None:
        state = jnp.zeros((B, H, P, N), x.dtype)
    state, ys = lax.scan(some, state, tuple(map(by_segment, (x, dt, bm, cm))))
    y = jnp.moveaxis(ys.reshape(S, B, H, P), 0, 1)
    return (y, state) if with_state else y


def scan_operands(u, p, spec, before=None):
    """What a mamba layer's recurrence is fed, from the layer's normed
    input u [B, S, hidden]: `(x, dt, a, bm, cm, d)` as `recurrence` takes
    them, the gate z [B, S, inner], and the convolution's own input
    [B, S, channels] (`before`: its last K - 1 rows of the positions
    before these)."""
    B, S, _ = u.shape
    H, P = spec["mamba_heads"], spec["mamba_head_dim"]
    G, N = spec["mamba_groups"], spec["mamba_state"]
    inner = H * P
    z, raw_xbc, raw = jnp.split(u @ p["in_proj_w"],
                                [inner, 2 * inner + 2 * G * N], axis=-1)
    xbc = jax.nn.silu(causal_conv(raw_xbc, p["conv_w"], p["conv_b"], before))
    x, bm, cm = jnp.split(xbc, [inner, inner + G * N], axis=-1)
    return (x.reshape(B, S, H, P), jax.nn.softplus(raw + p["dt_bias"]),
            -jnp.exp(p["A_log"]), bm.reshape(B, S, G, N),
            cm.reshape(B, S, G, N), p["D"]), z, raw_xbc


def gated_group_norm(y, z, scale, groups, eps):
    """y, z [B, S, inner]: the gate first, then the mean square over each
    of the `groups` stretches of the inner width apart."""
    gated = y * jax.nn.silu(z)
    parts = gated.reshape(*gated.shape[:-1], groups, -1)
    parts = parts * lax.rsqrt((parts * parts).mean(-1, keepdims=True) + eps)
    return parts.reshape(gated.shape) * scale


def mamba_part(u, p, spec):
    """u [B, S, hidden] -> the mixer's result, the positions walked in
    blocks of `spec["mamba_block"]`, each rematerialised: a block hands
    the next the recurrence's state and the convolution's last K - 1
    inputs, so every position's arithmetic is what one pass over the
    whole sequence gives it, and one block's [block, 10304] activations
    are alive at a time and not the sequence's (5.6 GB a layer with their
    gradients at 16,384 positions, which the chip did not have)."""
    B, S, D = u.shape
    block = _blocks(S, spec["mamba_block"])
    H, P = spec["mamba_heads"], spec["mamba_head_dim"]
    G, N = spec["mamba_groups"], spec["mamba_state"]
    K = p["conv_w"].shape[0]

    @jax.checkpoint
    def some(carry, ub):
        state, before = carry
        operands, z, raw_xbc = scan_operands(ub, p, spec, before)
        y, state = recurrence(*operands, spec["scan_segment"], state,
                              with_state=True)
        y = gated_group_norm(y.reshape(z.shape), z, p["gate_norm"], G,
                             spec["eps"])
        return (state, raw_xbc[:, block - (K - 1):]), y @ p["out_proj_w"]

    start = (jnp.zeros((B, H, P, N), u.dtype),
             jnp.zeros((B, K - 1, H * P + 2 * G * N), u.dtype))
    _, out = lax.scan(
        some, start, jnp.moveaxis(u.reshape(B, S // block, block, D), 1, 0))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, D)


# ---------------------------------------------------------------------------
# *
# ---------------------------------------------------------------------------
def attention(q, k, v, start=0):
    """The rows `start ...` of one sequence: q [Hkv, G, rows, size]
    against ALL the keys, k, v [Hkv, S, size]; causal, no positions.  A
    row's softmax is over all its keys at once."""
    scores = jnp.einsum("kgqd,ksd->kgqs", q, k) / math.sqrt(q.shape[-1])
    i = start + jnp.arange(q.shape[2])[:, None]
    j = jnp.arange(k.shape[1])[None, :]
    probs = jax.nn.softmax(jnp.where(i >= j, scores, -jnp.inf), -1)
    return jnp.einsum("kgqs,ksd->kgqd", probs, v)


def attention_part(u, p, spec):
    """u [B, S, hidden] -> ctx Wo.  K and V are computed for the whole
    sequence; the query rows are walked in blocks of `spec["q_block"]`,
    each block projected, attended and projected back by itself."""
    B, S, D = u.shape
    H, Hkv, size = spec["heads"], spec["kv_heads"], spec["head_dim"]
    q_block = _blocks(S, spec["q_block"])
    w_q, w_k, w_v = jnp.split(p["qkv_w"], [H * size, (H + Hkv) * size],
                              axis=-1)

    def heads(t):                       # [rows, n * size] -> [n, rows, size]
        return t.reshape(t.shape[0], -1, size).transpose(1, 0, 2)

    def sequence(a):                    # [S, hidden]
        k, v = heads(a @ w_k), heads(a @ w_v)

        @jax.checkpoint
        def rows(start):
            ab = lax.dynamic_slice_in_dim(a, start, q_block)
            q = heads(ab @ w_q).reshape(Hkv, H // Hkv, q_block, size)
            ctx = attention(q, k, v, start).reshape(H, q_block, size)
            return (ctx.transpose(1, 0, 2).reshape(q_block, H * size)
                    @ p["attn_out_w"])
        return lax.map(rows, jnp.arange(0, S, q_block)).reshape(S, D)
    return lax.map(sequence, u)


# ---------------------------------------------------------------------------
# E
# ---------------------------------------------------------------------------
def relu2(x, up_w, down_w):
    return jnp.square(jax.nn.relu(x @ up_w)) @ down_w


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


def chosen_weights(scores, sel, route_scale):
    """The weights of the experts `sel` [T, k] names, from `scores`
    [T, E]: the chosen scores over their sum, times `route_scale`."""
    w = jnp.take_along_axis(scores, sel, -1)
    return w / (w.sum(-1, keepdims=True) + 1e-20) * route_scale


def routed_experts(m, p, spec, sel=None):
    """m [T, hidden] -> `(the held routed experts' sum, stats)`."""
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
    def one(e, up_w, down_w):
        coef = jnp.where(sel == e, w, 0.0).sum(-1)           # [T]
        return coef[:, None] * relu2(m, up_w, down_w)

    def add(acc, xs):
        return acc + one(*xs), None

    routed, _ = lax.scan(
        add, jnp.zeros_like(m),
        (jnp.asarray(spec["held"], jnp.int32), p["expert_up_w"],
         p["expert_down_w"]))
    return routed, stats


def shared_expert(m, p, block):
    """m [T, hidden], its rows in blocks."""
    T = m.shape[0]
    block = _blocks(T, block)

    @jax.checkpoint
    def some(start):
        return relu2(lax.dynamic_slice_in_dim(m, start, block),
                     p["shared_up_w"], p["shared_down_w"])
    return lax.map(some, jnp.arange(0, T, block)).reshape(m.shape)


def moe_part(u, p, spec, sel=None):
    """u [B, S, hidden] -> `(f, stats)`."""
    m = u.reshape(-1, u.shape[-1])
    routed, stats = routed_experts(m, p, spec, sel)
    f = routed + shared_expert(m, p, spec["mlp_block"])
    return f.reshape(u.shape), stats


# ---------------------------------------------------------------------------
# The stack
# ---------------------------------------------------------------------------
def layer(x, p, spec, kind, sel=None):
    """x [B, S, hidden]; p the layer's own leaves -> `(x, stats)`."""
    u = rms_norm(x, p["input_ln"], spec["eps"])
    if kind == MOE:
        f, stats = moe_part(u, p, spec, sel)
        return x + f, stats
    part = mamba_part if kind == MAMBA else attention_part
    return x + part(u, p, spec), None


def unstack(stack):
    """The layers' own leaves from leaves stacked on a leading axis.  A
    split and not indexings: the gradient of a split is one concatenate,
    that of n indexings n padded copies to sum."""
    n = next(iter(stack.values())).shape[0]
    pieces = {k: lax.split(a, (1,) * n) for k, a in stack.items()}
    return [{k: pieces[k][j][0] for k in stack} for j in range(n)]


def rounds_of(kinds):
    """Whether each ROUND of the layers has an attention layer, where the
    layers come as rounds of a mixer, at most one attention layer and an
    expert layer, in that order (`ME` or `M*E`: the published 52 letters
    are 23 such rounds, the cell's nine are four); None where they do
    not."""
    out, i = [], 0
    while i < len(kinds):
        has = kinds[i + 1:i + 2] == (ATTENTION,)
        if kinds[i] != MAMBA or kinds[i + 1 + has:i + 2 + has] != (MOE,):
            return None
        out.append(has)
        i += 2 + has
    return out


def _one(spec, kind):
    """A layer of `kind`, rematerialised by itself."""
    return jax.checkpoint(lambda x, p, s=None: layer(x, p, spec, kind, s))


def hidden(params, tokens, spec, sel=None):
    """tokens [B, S] -> `(final hidden states, stats stacked over the
    expert layers or None)`.

    Where the layers come as rounds (`rounds_of`) they are walked by
    `lax.scan` over the rounds, the mixers' and the experts' stacks its
    `xs` and a round's attention layer, where it has one, taken from its
    stack by index: the gradient then comes out stacked as the program's
    is, written a round at a time, with no copy of every layer's leaves
    and of every layer's gradient beside it (a Python loop over split
    leaves held two more trees of 2.5 GB at the published widths, which
    the chip did not have).  Any other order of layers is a Python loop
    over split leaves; the arithmetic of a layer is the same."""
    x = params["embed"][tokens - spec["vocab_start"]]
    kinds = tuple(spec["layer_kinds"])
    rounds = rounds_of(kinds)
    if rounds:
        @jax.checkpoint
        def one_round(carry, xs):
            x, at = carry
            mamba, moe, has, s = xs
            x, _ = _one(spec, MAMBA)(x, mamba)
            if ATTENTION in params:
                x = lax.cond(
                    has, lambda x: _one(spec, ATTENTION)(x, jax.tree.map(
                        lambda a: lax.dynamic_index_in_dim(
                            a, at, keepdims=False), params[ATTENTION]))[0],
                    lambda x: x, x)
            x, stats = _one(spec, MOE)(x, moe, s)
            return (x, at + has), stats
        (x, _), stats = lax.scan(
            one_round, (x, jnp.int32(0)),
            (params[MAMBA], params[MOE], jnp.asarray(rounds), sel))
        return rms_norm(x, params["final_ln"], spec["eps"]), stats
    leaves = {kind: unstack(params[kind])
              for kind in (MAMBA, MOE, ATTENTION) if kind in params}
    at = dict.fromkeys(leaves, 0)
    stats = []
    for kind in kinds:
        j = at[kind]
        at[kind] = j + 1
        s = sel[j] if kind == MOE and sel is not None else None
        x, st = _one(spec, kind)(x, leaves[kind][j], s)
        if st is not None:
            stats.append(st)
    x = rms_norm(x, params["final_ln"], spec["eps"])
    return x, (jax.tree.map(lambda *a: jnp.stack(a), *stats)
               if stats else None)


def nll_mean(x, head, targets, ce_block):
    """Mean cross-entropy of `x` [N, hidden] against `head` [V, hidden]."""
    n = x.shape[0]
    ce_block = _blocks(n, ce_block)

    @jax.checkpoint
    def rows(start):
        xb = lax.dynamic_slice_in_dim(x, start, ce_block)
        tb = lax.dynamic_slice_in_dim(targets, start, ce_block)
        logp = jax.nn.log_softmax(xb @ head.T, axis=-1)
        return -jnp.take_along_axis(logp, tb[:, None], -1).sum()

    return lax.map(rows, jnp.arange(0, n, ce_block)).sum() / n


def _float32(params):
    return jax.tree.map(lambda a: a.astype(jnp.float32), params)


def first_of(params, kind):
    """The first layer of `kind`'s own leaves, float32."""
    return {k: a[0].astype(jnp.float32) for k, a in params[kind].items()}


def loss(params, batch, spec, sel=None, with_stats=False):
    """Mean next-token cross-entropy over the held slice.  batch =
    (tokens, targets), both [batch, position] int32 ids of the slice;
    `params` is the program's tree, any dtype; `spec` the model's numbers
    (see `benchmark/families/nemotronh.py`).  `sel` [expert layers,
    tokens, k] puts somebody else's choice of experts in place of the
    top-k."""
    with jax.default_matmul_precision("highest"):
        tokens, targets = batch
        x, stats = hidden(_float32(params), tokens, spec, sel)
        value = nll_mean(x.reshape(-1, x.shape[-1]),
                         params["head"].astype(jnp.float32),
                         targets.reshape(-1) - spec["vocab_start"],
                         spec["ce_block"])
    return (value, stats) if with_stats else value


def logits(params, tokens, spec):
    """The held slice's logits, [batch, position, held rows]."""
    with jax.default_matmul_precision("highest"):
        params = _float32(params)
        return hidden(params, tokens, spec)[0] @ params["head"].T
