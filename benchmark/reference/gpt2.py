"""Plain float32 reference of GPT-2's language-model loss.

Written from the published description (Radford et al. 2019, "Language
Models are Unsupervised Multitask Learners", section 2.3, on top of
Radford et al. 2018) and from `config.json`'s names: token plus learned
position embeddings; `n_layer` pre-LN blocks, each
`h += proj(attn(ln_1(h)))` then `h += proj(gelu_new(fc(ln_2(h))))`, with
causal multi-head softmax attention scaled by 1/sqrt(head size) and biases
everywhere; a final layer norm; logits against the token embedding (tied
head); the mean cross-entropy of the next token.

Nothing of byteps_tpu is imported.  The one thing shared with the program
is the layout of its parameter tree, which the reference has to read: the
fused `qkv_w` [d, 3d] holds q, k, v side by side, and the per-layer
leaves are stacked on a leading `n_layer` axis.

Departures from the description, each because the program departs alike
and the two must compute the same function: no dropout (published 0.1);
the layers are walked with `lax.scan` over the stacked leaves and not a
Python loop (the same arithmetic in the same order, a 24 times smaller
program to compile).  No kernel, no fused head, no rematerialisation, no
bfloat16 anywhere: every matmul is float32 at `highest` precision, which
on a TPU is what keeps float32 from running as one bfloat16 pass.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

LAYER_NORM_EPS = 1e-5    # config.json layer_norm_epsilon


def layer_norm(x, scale, bias):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LAYER_NORM_EPS) * scale + bias


def gelu_new(x):
    """config.json `activation_function`: the tanh approximation."""
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def attention(q, k, v, causal: bool = True):
    """q, k, v: [batch, head, position, head size]."""
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1])
    if causal:
        n = q.shape[2]
        scores = jnp.where(jnp.tril(jnp.ones((n, n), bool)), scores,
                           -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v)


def block(h, p, n_head: int, causal: bool = True):
    batch, n, d = h.shape

    def heads(t):
        return t.reshape(batch, n, n_head, d // n_head).transpose(0, 2, 1, 3)

    a = layer_norm(h, p["ln1_scale"], p["ln1_bias"])
    q, k, v = jnp.split(a @ p["qkv_w"] + p["qkv_b"], 3, axis=-1)
    ctx = attention(heads(q), heads(k), heads(v), causal)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(batch, n, d)
    h = h + ctx @ p["attn_out_w"] + p["attn_out_b"]
    m = layer_norm(h, p["ln2_scale"], p["ln2_bias"])
    m = gelu_new(m @ p["mlp_in_w"] + p["mlp_in_b"])
    return h + m @ p["mlp_out_w"] + p["mlp_out_b"]


def loss(params, batch, n_head: int, causal: bool = True):
    """Mean next-token cross-entropy.  batch = (tokens, targets), both
    [batch, position] int32; `params` is the program's tree, any dtype."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
        tokens, targets = batch
        h = params["embed"][tokens] + params["pos_embed"][:tokens.shape[1]]
        h, _ = lax.scan(
            lambda h, p: (block(h, p, n_head, causal), None),
            h, params["layers"])
        h = layer_norm(h, params["ln_f_scale"], params["ln_f_bias"])
        logp = jax.nn.log_softmax(h @ params["embed"].T, axis=-1)
        return -jnp.take_along_axis(logp, targets[..., None], -1).mean()
