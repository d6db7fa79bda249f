"""Plain float32 reference of the `granitemoehybrid` language-model loss
(IBM's Granite 4.0-H family, the members without routed experts), told
which layers and which slice of the vocabulary one chip of a deployment
holds.

Written from the model's `config.json` and its public modelling code
(`transformers`, `models/granitemoehybrid`).  With
`h0 = embed[ids] * embedding_multiplier`, every layer is

    x = x + residual_multiplier * mixer(rms(x; input_ln))
    x = x + residual_multiplier * mlp(rms(x; post_ln))
    mlp(u):  [a, b] = split(u W_in, 2);  (silu(a) * b) W_out

with, in an `attention` layer,

    q, k, v = u Wq, u Wk, u Wv        no bias, no positions of any kind
    ctx = causal softmax(q k^T * attention_multiplier) v, a key-value head
          serving heads / kv_heads query heads;  mixer = ctx Wo

and, in a `mamba` layer,

    [z, xBC, dt] = split(u W_in, [inner, inner + 2 groups state, heads])
    xBC_t = silu(bias + sum_k w[k] xBC_{t-(K-1)+k})    depthwise, causal,
                                                       K - 1 zeros before
    [x, B, C] = split(xBC);  x as heads of size P;  B, C a group's
    dt = softplus(dt + dt_bias);  A = -exp(A_log)           a head
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t          S_0 = 0, [P, N]
    y_t = S_t C_t + D x_t
    mixer = rms(y * silu(z); gate_norm, over the whole inner width) W_out

then a final RMS norm, the TIED head `x embed^T / logits_scaling` and the
mean next-token cross-entropy, over the held rows of the embedding.

Nothing of byteps_tpu is imported.  The recurrence is computed AS a
recurrence, one position after another (`lax.scan` over the positions),
never in the chunked form the program uses.  What is shared with the
program is the layout of its parameter tree: `layers` is a list with one
group of leaves for each RUN of a period of `layer_types` (a period being
the shortest stretch that tiles the list, a run its consecutive layers of
one kind), stacked `[periods, layers of the run, ...]`; `in_proj_w`
[hidden, .] holds z, xBC and dt side by side, `qkv_w` q, k and v,
`mlp_in_w` a and b; `conv_w` is [K, channels] with the taps in front,
`conv_w[K - 1]` meeting the current position.

Departures from a naive transcription, each for memory at 8192 positions
and none changing the arithmetic of a row: the layers are walked in a
Python loop with `jax.checkpoint` around each; attention walks the query
rows in blocks of `spec["q_block"]`, the MLP its rows in blocks of
`spec["mlp_block"]` and the head in blocks of `spec["ce_block"]`
(`lax.map`, each block rematerialised), a row's softmax being taken over
all its keys, or all the held logits, at once; the recurrence is
rematerialised by segments of `spec["scan_segment"]` positions (the state
is kept at every segment's start and the segment run again in the
backward pass).  No kernel, no chunked form, no bfloat16 anywhere: every
matmul is float32 at `highest` precision.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

MAMBA = "mamba"


def rms_norm(x, scale, eps):
    return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _blocks(n, block):
    block = min(block, n)
    if n % block:
        raise ValueError(f"{n} rows do not come in blocks of {block}")
    return block


def attention(q, k, v, scale, q_block):
    """q [B, Hkv, G, S, size]; k, v [B, Hkv, S, size]; causal."""
    n = q.shape[3]
    q_block = _blocks(n, q_block)
    cols = jnp.arange(n)[None, :]

    @jax.checkpoint
    def rows(start):
        qb = lax.dynamic_slice_in_dim(q, start, q_block, axis=3)
        scores = jnp.einsum("bkgqd,bksd->bkgqs", qb, k) * scale
        i = start + jnp.arange(q_block)[:, None]
        probs = jax.nn.softmax(jnp.where(i >= cols, scores, -jnp.inf), -1)
        return jnp.einsum("bkgqs,bksd->bkgqd", probs, v)

    out = lax.map(rows, jnp.arange(0, n, q_block))    # [blocks, B,Hkv,G,qb,d]
    return jnp.moveaxis(out, 0, 3).reshape(q.shape)


def mlp(u, in_w, out_w, block):
    """u [B, S, hidden]."""
    B, S, D = u.shape
    rows = u.reshape(B * S, D)
    block = _blocks(B * S, block)

    @jax.checkpoint
    def some(start):
        a, b = jnp.split(lax.dynamic_slice_in_dim(rows, start, block) @ in_w,
                         2, axis=-1)
        return (jax.nn.silu(a) * b) @ out_w

    return lax.map(some, jnp.arange(0, B * S, block)).reshape(B, S, D)


def causal_conv(x, w, bias):
    """x [B, S, C]; w [K, C]; y_t = bias + sum_k w[k] x_{t-(K-1)+k}."""
    K = w.shape[0]
    S = x.shape[1]
    out = jnp.broadcast_to(bias, x.shape)
    for back in range(K):              # the tap `back` positions behind t
        shifted = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :S]
        out = out + shifted * w[K - 1 - back]
    return out


def recurrence(x, dt, a, bm, cm, d, segment):
    """x [B, S, H, P]; dt [B, S, H]; a [H] (negative); bm, cm [B, S, G, N];
    d [H] -> y [B, S, H, P], one position at a time."""
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

    _, ys = lax.scan(some, jnp.zeros((B, H, P, N), x.dtype),
                     tuple(map(by_segment, (x, dt, bm, cm))))
    return jnp.moveaxis(ys.reshape(S, B, H, P), 0, 1)


def scan_operands(u, p, spec):
    """What a mamba layer's recurrence is fed, from the layer's normed
    input u [B, S, hidden]: `(x, dt, a, bm, cm, d)` as `recurrence` takes
    them, and the gate z [B, S, inner]."""
    B, S, _ = u.shape
    H, P = spec["mamba_heads"], spec["mamba_head_dim"]
    G, N = spec["mamba_groups"], spec["mamba_state"]
    inner = H * P
    z, xbc, raw = jnp.split(u @ p["in_proj_w"],
                            [inner, 2 * inner + 2 * G * N], axis=-1)
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"]))
    x, bm, cm = jnp.split(xbc, [inner, inner + G * N], axis=-1)
    return (x.reshape(B, S, H, P), jax.nn.softplus(raw + p["dt_bias"]),
            -jnp.exp(p["A_log"]), bm.reshape(B, S, G, N),
            cm.reshape(B, S, G, N), p["D"]), z


def mamba_mixer(u, p, spec):
    operands, z = scan_operands(u, p, spec)
    y = recurrence(*operands, spec["scan_segment"])
    y = rms_norm(y.reshape(z.shape) * jax.nn.silu(z), p["gate_norm"],
                 spec["eps"])
    return y @ p["out_proj_w"]


def attention_mixer(u, p, spec):
    B, S, _ = u.shape
    H, Hkv, size = spec["heads"], spec["kv_heads"], spec["head_dim"]
    q, k, v = jnp.split(u @ p["qkv_w"], [H * size, (H + Hkv) * size],
                        axis=-1)

    def heads(t):
        return t.reshape(B, S, -1, size).transpose(0, 2, 1, 3)
    ctx = attention(heads(q).reshape(B, Hkv, H // Hkv, S, size), heads(k),
                    heads(v), spec["attention_multiplier"], spec["q_block"])
    ctx = ctx.reshape(B, H, S, size).transpose(0, 2, 1, 3).reshape(B, S, -1)
    return ctx @ p["attn_out_w"]


def layer(x, p, spec, kind):
    """x [B, S, hidden]; p the layer's own leaves."""
    mixer = mamba_mixer if kind == MAMBA else attention_mixer
    r = spec["residual_multiplier"]
    x = x + r * mixer(rms_norm(x, p["input_ln"], spec["eps"]), p, spec)
    return x + r * mlp(rms_norm(x, p["post_ln"], spec["eps"]), p["mlp_in_w"],
                       p["mlp_out_w"], spec["mlp_block"])


def runs_of(layer_types):
    """`(periods, [(kind, layers)])`: how the tree's `layers` are laid
    out (see the module's docstring)."""
    kinds = tuple(layer_types)
    n = len(kinds)
    p = next(p for p in range(1, n + 1)
             if n % p == 0 and kinds == kinds[:p] * (n // p))
    runs = []
    for kind in kinds[:p]:
        if runs and runs[-1][0] == kind:
            runs[-1] = (kind, runs[-1][1] + 1)
        else:
            runs.append((kind, 1))
    return n // p, runs


def unstack(group):
    """`[period][layer of the run]` -> the layer's own leaves, from a
    run's leaves stacked `[periods, layers, ...]`.  A split and not
    indexings: the gradient of a split is one concatenate, that of n
    indexings n padded copies to sum (gigabytes, at the published
    widths)."""
    periods, n = next(iter(group.values())).shape[:2]
    flat = {k: lax.split(a.reshape(periods * n, *a.shape[2:]),
                         (1,) * (periods * n)) for k, a in group.items()}
    return [[{k: flat[k][i * n + j][0] for k in group} for j in range(n)]
            for i in range(periods)]


def hidden(params, tokens, spec):
    """tokens [B, S] -> the final hidden states."""
    x = (params["embed"][tokens - spec["vocab_start"]]
         * spec["embedding_multiplier"])
    periods, runs = runs_of(spec["layer_types"])
    groups = [unstack(g) for g in params["layers"]]
    for i in range(periods):
        for (kind, n), group in zip(runs, groups):
            for p in group[i]:
                x = jax.checkpoint(
                    lambda x, p, kind=kind: layer(x, p, spec, kind))(x, p)
    return rms_norm(x, params["final_ln"], spec["eps"])


def nll_mean(x, embed, targets, logits_scaling, ce_block):
    """Mean cross-entropy of `x` [N, hidden] against the tied `embed`
    [V, hidden]."""
    n = x.shape[0]
    ce_block = _blocks(n, ce_block)

    @jax.checkpoint
    def rows(start):
        xb = lax.dynamic_slice_in_dim(x, start, ce_block)
        tb = lax.dynamic_slice_in_dim(targets, start, ce_block)
        logp = jax.nn.log_softmax(xb @ embed.T / logits_scaling, axis=-1)
        return -jnp.take_along_axis(logp, tb[:, None], -1).sum()

    return lax.map(rows, jnp.arange(0, n, ce_block)).sum() / n


def first_scan_operands(params, tokens, spec):
    """What a mamba layer's recurrence is fed on `tokens`, float32:
    `(x, dt, a, bm, cm, d)` as `recurrence` takes them, for the first
    mamba layer that is run, given the embedded tokens (in the cell it is
    the first layer; an attention layer before it would be skipped: the
    operands have to be a mamba layer's, not that position's).  What the
    family feeds the program's scan to compare it alone with the
    recurrence (`benchmark/families/granitehybrid.py`)."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        h0 = (params["embed"][tokens - spec["vocab_start"]]
              * spec["embedding_multiplier"])
        _, runs = runs_of(spec["layer_types"])
        group = next(g for (kind, _), g in zip(runs, params["layers"])
                     if kind == MAMBA)
        p = {k: a[0, 0] for k, a in group.items()}
        return scan_operands(rms_norm(h0, p["input_ln"], spec["eps"]), p,
                             spec)[0]


def loss(params, batch, spec):
    """Mean next-token cross-entropy over the held slice.  batch =
    (tokens, targets), both [batch, position] int32 ids of the slice;
    `params` is the program's tree, any dtype; `spec` the model's numbers
    (see `benchmark/families/granitehybrid.py`)."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        tokens, targets = batch
        x = hidden(params, tokens, spec)
        return nll_mean(x.reshape(-1, x.shape[-1]), params["embed"],
                        targets.reshape(-1) - spec["vocab_start"],
                        spec["logits_scaling"], spec["ce_block"])


def logits(params, tokens, spec):
    """The held slice's logits, [batch, position, held rows]."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        return (hidden(params, tokens, spec) @ params["embed"].T
                / spec["logits_scaling"])
