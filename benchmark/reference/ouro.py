"""Plain float32 reference of the training loss of an `ouro` decoder
(ByteDance's Ouro-2.6B): a stack of layers walked `total_ut_steps` times
with the same weights, an exit gate after every walk, and the expected
cross-entropy over the walk a token leaves at, less an entropy bonus.

Written from the model's `config.json` (`model_type` `ouro`) and from the
family's paper ("Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741, its stage-one objective); what neither states is listed
under the configuration's `assumed`.  Per token, hidden size D,
`rms(x; g) = x * rsqrt(mean(x^2) + eps) * g`:

    layer(x):  a = Attn(rms(x; g1));         x = x + rms(a; g2)
               m = SwiGLU(rms(x; g3));       x = x + rms(m; g4)
    Attn(h):   q, k, v = h Wq, h Wk, h Wv    heads of head_dim, no bias
               q, k = rot(q), rot(k)         half-split, inv_freq_i =
                                             theta^(-2i / head_dim), no q / k norm
               o = softmax(q k^T / sqrt(head_dim) + causal) v;  o Wo
    SwiGLU(h): (silu(h Wg) * (h Wu)) Wd
    walks:     h_0 = E[tokens]
               for t = 1..T:  u_t = layer_{L-1}(... layer_0(h_{t-1}))
                              h_t = rms(u_t; g_f)      INSIDE the loop
                              lam_t = sigmoid(h_t . w_e + b_e)
                              nll_t = -log softmax(h_t W_head^T)[target]
    exit:      p_t = lam_t prod_{j<t} (1 - lam_j)  for t < T
               p_T = prod_{j<T} (1 - lam_j)        (lam_T is unused)
    loss:      mean over tokens of  sum_t p_t nll_t - beta H(p),
               H(p) = -sum_t p_t log p_t

Nothing of byteps_tpu is imported, and nothing of another reference.
What is shared with the program is the layout of its parameter tree: one
group `dense` with leaves stacked on a leading layer axis; `qkv_w`
[hidden, .] holds q, k and v side by side; the gate is one leaf,
`exit_gate` [hidden + 1], its weight and then its bias.

The walks are a PYTHON loop: every walk is its own piece of the program,
reads the same stacked leaves, and a shared leaf's gradient is the sum of
the walks' (one stack a walk).  The layers of a walk are walked by
`lax.scan` over the stacked leaves and not a second Python loop: the same
arithmetic in the same order.  (Unrolled, 32 layer applications' float32
temporaries are the compiler's to interleave, and it asks for 22 GB: my
compile for a described v5e, PR 64.)
Departures from a naive transcription, each for memory at 8,192 rows
beside three 2.45 GB trees (the parameters, the program's gradient, this
one's) and none changing the arithmetic of a row: a `jax.checkpoint`
around every layer application (32 layer inputs of 67 MB are kept, not
every activation); attention computes K and V for all the rows and walks
the query rows in blocks of `spec["q_block"]`, each block's [rows, keys]
scores rematerialised; the head walks the rows in blocks of
`spec["ce_block"]`, so the [4 x S, vocabulary] logits (6.4 GB) are never
held; 0 log 0 is 0 in the entropy.  No kernel, no streamed head's
arithmetic, no bfloat16 anywhere: every matmul is float32 at
`highest` precision.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


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


def attention(q, k, v, start):
    """The rows `start ...` of one sequence against ALL the keys: q
    [Hkv, G, rows, size], k and v [Hkv, S, size]; row r sees the keys
    0 .. r.  A row's softmax is over all the keys it sees at once."""
    scores = jnp.einsum("kgqd,ksd->kgqs", q, k) / math.sqrt(q.shape[-1])
    row = start + jnp.arange(q.shape[2])[:, None]
    seen = jnp.arange(k.shape[1])[None, :] <= row
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    return jnp.einsum("kgqs,ksd->kgqd", probs, v)


def swiglu(x, gate_w, up_w, down_w):
    return (jax.nn.silu(x @ gate_w) * (x @ up_w)) @ down_w


def attention_half(a, p, spec):
    """a [S, hidden], one sequence's normed rows -> ctx Wo."""
    S = a.shape[0]
    H, Hkv, size = spec["heads"], spec["kv_heads"], spec["head_dim"]
    q_block = min(spec["q_block"], S)
    w_q, w_k, w_v = jnp.split(p["qkv_w"], [H * size, (H + Hkv) * size],
                              axis=-1)

    def heads(t):                       # [rows, n * size] -> [n, rows, size]
        return t.reshape(t.shape[0], -1, size).transpose(1, 0, 2)

    k = rotary(heads(a @ w_k), spec["theta"], jnp.arange(S))
    v = heads(a @ w_v)

    @jax.checkpoint
    def rows(start):
        ab = lax.dynamic_slice_in_dim(a, start, q_block)
        q = rotary(heads(ab @ w_q), spec["theta"],
                   start + jnp.arange(q_block))
        ctx = attention(q.reshape(Hkv, H // Hkv, q_block, size), k, v, start)
        ctx = ctx.reshape(H, q_block, size).transpose(1, 0, 2)
        return ctx.reshape(q_block, H * size) @ p["attn_out_w"]

    return lax.map(rows, jnp.arange(0, S, q_block)).reshape(S, -1)


def layer(x, p, spec):
    """One layer application: x [B, S, hidden]; p the layer's own
    leaves."""
    eps = spec["eps"]
    a = lax.map(lambda a: attention_half(a, p, spec),
                rms_norm(x, p["input_ln"], eps))
    x = x + rms_norm(a, p["post_attn_ln"], eps)
    m = swiglu(rms_norm(x, p["pre_mlp_ln"], eps), p["mlp_gate_w"],
               p["mlp_up_w"], p["mlp_down_w"])
    return x + rms_norm(m, p["post_mlp_ln"], eps)


def walks(params, tokens, spec):
    """`[h_1, ..., h_T]`, each [B, S, hidden]: every walk's state after
    the final norm."""
    apply = jax.checkpoint(lambda x, p: layer(x, p, spec))
    h, out = params["embed"][tokens], []
    for _ in range(spec["walks"]):
        h, _ = lax.scan(lambda x, p: (apply(x, p), None), h, params["dense"])
        h = rms_norm(h, params["final_ln"], spec["eps"])
        out.append(h)
    return out


def gate(h, exit_gate):
    """`lam` [...] of the normed states `h` [..., hidden]; `exit_gate`
    [hidden + 1] is the gate's weight, then its bias."""
    return jax.nn.sigmoid((h * exit_gate[:-1]).sum(-1) + exit_gate[-1])


def exit_distribution(lams):
    """`[p_1, ..., p_T]` from `[lam_1, ..., lam_T]`: the last walk takes
    what is left."""
    p, left = [], jnp.ones_like(lams[0])
    for lam in lams[:-1]:
        p.append(lam * left)
        left = left * (1.0 - lam)
    return p + [left]


def entropy(p):
    """H(p) [...] in nats of `[p_1, ..., p_T]`; 0 log 0 = 0."""
    tiny = jnp.finfo(jnp.float32).tiny
    return -sum(q * jnp.log(jnp.maximum(q, tiny)) for q in p)


def nll_rows(x, head, targets, ce_block):
    """The cross-entropy of every row of `x` [N, hidden] against `head`
    [V, hidden], [N]."""
    n = x.shape[0]
    ce_block = min(ce_block, n)

    @jax.checkpoint
    def rows(start):
        xb = lax.dynamic_slice_in_dim(x, start, ce_block)
        tb = lax.dynamic_slice_in_dim(targets, start, ce_block)
        logp = jax.nn.log_softmax(xb @ head.T, axis=-1)
        return -jnp.take_along_axis(logp, tb[:, None], -1)[:, 0]

    return lax.map(rows, jnp.arange(0, n, ce_block)).reshape(n)


def exit_and_nll(params, hs, targets, spec):
    """`(p, nll)`, each a list over the walks of [B, S]: the exit
    distribution and the head's cross-entropy of the normed states `hs`
    (a list of [B, S, hidden])."""
    lams = [gate(h, params["exit_gate"]) for h in hs]
    nll = [nll_rows(h.reshape(-1, h.shape[-1]), params["head"],
                    targets.reshape(-1), spec["ce_block"]
                    ).reshape(targets.shape) for h in hs]
    return exit_distribution(lams), nll


def loss(params, batch, spec):
    """The mean over tokens of `sum_t p_t nll_t - beta H(p)`.  batch =
    (tokens, targets), both [batch, position] int32; `params` is the
    program's tree, any dtype; `spec` the model's numbers (see
    `benchmark/families/ouro.py`)."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        tokens, targets = batch
        p, nll = exit_and_nll(params, walks(params, tokens, spec), targets,
                              spec)
        task = sum(q * n for q, n in zip(p, nll)).mean()
        return task - spec["beta"] * entropy(p).mean()
