"""The `sdar_moe` decoder (JetLM's SDAR-30B-A3B-Chat) and the
block-diffusion objective it is trained with: the model generates a BLOCK
of tokens at a time by masked diffusion inside the block, and block after
block autoregressively, so in training a sequence enters TWICE, clean and
noised, under a mask that is neither causal nor a band nor data.

The decoder's layer is the Qwen3-MoE lineage's, `models/mellum.py`'s with
one kind of attention and plain rotary positions: `h = x +
Attn(rms(x))`, `y = h + MoE(rms(h))`; q, k, v without bias, an RMS norm
over each head's numbers of q and of k, rotary at `rope_theta` over the
whole head, query heads in groups to a key-value head; the router's
logits in float32, softmax, the top `k`, their weights over their sum
(`norm_topk_prob`), SwiGLU experts, no shared expert, no dense layer; a
final RMS norm and an untied head.  The parameter tree is mellum's
(`init_params`), and so is the expert half (`mellum._experts`).

Training, as block diffusion is published (BD3-LMs, arXiv:2503.09573,
section 3 and its vectorised training; SDAR, arXiv:2510.06303, takes it
over with a pretrained autoregressive decoder), for a sequence x of L
tokens in blocks of `beta` = `block_length`, block b(i) = i // beta:

  1. Noise.  For each block one t = eps + (1 - eps) u, u uniform on
     [0, 1); each token of the block becomes the mask token with
     probability t, independently: x_t.  m_i = 1 where token i was
     masked.  (`synthetic_batch`: a batch is `(tokens, masked, weight)`
     with weight = m / t, so the noise is DATA and whoever checks the
     loss sees the same.)
  2. Input.  [x ; x_t], 2 L rows; row r of either copy carries position
     r mod L: a token and its noised copy turn alike.
  3. Mask, on (row r, key c), clean copy first.  A clean row (r < L)
     sees the clean keys with b(c) <= b(r) and no noised key.  A noised
     row (token i = r - L) sees the clean keys with b(c) < b(i) and the
     noised keys with b(c - L) == b(i), before AND after it.  L^2 + L
     beta pairs a head, against 2 L^2 + L for a causal call over the
     same rows.  The mask is the flash kernels' fourth kind
     (`ops/flash_attention.py` `block_diffusion`; `afmoe._ATTENTION`'s
     BLOCK_DIFFUSION) and never an array: this model has no dense
     attention.
  4. Loss.  Logits from the NOISED rows only, token i's logits predict
     x_i itself (no shift): loss = (1 / L) sum_i m_i (1 / t_b(i))
     CE(logits_i, x_i), a mean over samples.  The clean rows' last-layer
     results reach nothing but their keys and values.

The mask token is the LAST id of the held slice of the vocabulary
(`SdarConfig.mask_token`); the data draws from the ids before it, and the
head keeps a row for it.

A share of a deployment is what `afmoe.py` and `mellum.py` say it is
(`held_experts`, `vocab_size`, `vocab_start`, `hold_held_weight`).  What
is shared with the other expert decoders is imported, not copied: the
period scan `afmoe.run_layers` with its remat, the attention adapter
`afmoe._attn_fn`, `dropless_moe.held_experts`, the streamed head
`transformer.fused_nll_sum`, whose `weights` carry m / t here, anything
from 0 to 1 / eps, on half the rows the layers ran.

Generation (a step that yields a block, a cache that is rewritten inside
a block) is not here: ROADMAP.md.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from ..common import telemetry
from ..parallel import dropless_moe
from . import afmoe, mellum
from .afmoe import BLOCK_DIFFUSION
from .transformer import _rms_norm, _rope, fused_nll_sum

PyTree = Any


@dataclasses.dataclass(frozen=True)
class SdarConfig:
    vocab_size: int                    # rows of embedding and head held here
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    moe_intermediate_size: int
    num_experts: int                   # the router's width
    num_experts_per_tok: int
    num_layers: int                    # layers that are run, all alike
    block_length: int = 4              # beta: tokens a block
    noise_eps: float = 1e-3            # the least share of a block masked
    held_experts: Optional[Tuple[int, ...]] = None   # None: all of them
    vocab_start: int = 0               # first token id of the held slice
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attn_impl: str = "flash"           # the mask is the kernels': no other
    attn_block: int = 0                # as TransformerConfig's, tiles of L
    attn_block_k: int = 0
    remat: bool = True                 # per layer
    remat_policy: str = "none"         # "kernels" keeps the flash call's
                                       # o and lse and the router's choice
    ce_chunk_rows: int = 0             # > 0: streamed head + cross-entropy
    moe_capacity_factor: float = 1.25  # dropless_moe's static buffer
    num_dense_layers = 0               # what afmoe's `_stack_plan` reads

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"num_heads={self.num_heads} not divisible by "
                             f"num_kv_heads={self.num_kv_heads}")
        if self.head_dim % 2:
            raise ValueError(f"rotary positions need an even head_dim "
                             f"(got {self.head_dim})")
        if self.attn_impl != "flash":
            raise ValueError(
                f"attn_impl={self.attn_impl!r}: a block-diffusion mask is "
                f"the flash kernels' and never an array; 'flash' alone")
        if self.block_length < 1 or not 0 < self.noise_eps < 1:
            raise ValueError(f"block_length={self.block_length}, "
                             f"noise_eps={self.noise_eps}")

    @property
    def layer_types(self) -> Tuple[str, ...]:
        """What `afmoe._stack_plan` reads, and the scopes' names."""
        return (BLOCK_DIFFUSION,) * self.num_layers

    @property
    def mask_token(self) -> int:
        return self.vocab_start + self.vocab_size - 1

    @property
    def held(self) -> Tuple[int, ...]:
        return (tuple(range(self.num_experts)) if self.held_experts is None
                else tuple(self.held_experts))

    @property
    def moe(self) -> dropless_moe.MoEConfig:
        return dropless_moe.MoEConfig(
            num_experts=self.num_experts, top_k=self.num_experts_per_tok,
            held=self.held, route_norm=self.norm_topk_prob,
            score_func="softmax", capacity_factor=self.moe_capacity_factor,
            hold_held_weight=True)


# The tree is mellum's: one group, `moe`, its leaves stacked over the
# layers; `qkv_w` holds q, k and v side by side.
init_params = mellum.init_params


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------
def two_copies(params, batch, cfg: SdarConfig):
    """Steps 1 and 2 on a batch `(tokens [B, L], masked [B, L], weight)`:
    the embedded rows [B, 2 L, D] of `[x ; x_t]` and their positions
    [2 L], r mod L."""
    tokens, masked, _ = batch
    L = tokens.shape[1]
    if L % cfg.block_length:
        raise ValueError(f"{L} tokens are no whole number of blocks of "
                         f"{cfg.block_length}")
    with jax.named_scope("sdar.noise"):
        noised = jnp.where(masked, cfg.mask_token, tokens)
        rows = jnp.concatenate([tokens, noised], axis=1) - cfg.vocab_start
        positions = jnp.tile(jnp.arange(L, dtype=jnp.int32), 2)
        return params["embed"].astype(cfg.dtype)[rows], positions


def _qkv(x, lp, cfg: SdarConfig, positions):
    """What a layer's attention call is given: x [B, S, D] -> queries
    [B, H, S, Dh], keys and values [B, Hkv, S, Dh], queries and keys
    normed over the head and turned by the rows' `positions` [S]."""
    B, S, D = x.shape
    H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    norm = functools.partial(_rms_norm, bias=None, eps=cfg.rms_norm_eps)
    qkv = jnp.einsum("bsd,de->bse", norm(x, lp["input_ln"]),
                     lp["qkv_w"].astype(cfg.dtype))
    q, k, v = jnp.split(qkv, [H * Dh, (H + Hkv) * Dh], axis=-1)

    def heads(t):
        return t.reshape(B, S, -1, Dh).transpose(0, 2, 1, 3)
    turn = functools.partial(_rope, theta=cfg.rope_theta,
                             positions=positions)
    return (turn(norm(heads(q), lp["q_norm"])),
            turn(norm(heads(k), lp["k_norm"])), heads(v))


def _attention(x, lp, cfg: SdarConfig, kind: str, positions):
    """The attention half of a layer: x [B, 2 L, D] -> x + attn(norm(x))
    under the block-diffusion mask."""
    B, S, D = x.shape
    H, Hkv = cfg.num_heads, cfg.num_kv_heads
    with jax.named_scope(f"sdar.attn.{kind}"):
        with jax.named_scope(".qkv"):
            q, k, v = _qkv(x, lp, cfg, positions)
            if Hkv != H:
                k = jnp.repeat(k, H // Hkv, axis=1)
                v = jnp.repeat(v, H // Hkv, axis=1)
        # the kernels and the transpose after them stay the half's own
        ctx = afmoe._attn_fn(cfg, kind)(q, k, v)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, -1)
        with jax.named_scope(".out"):
            return x + jnp.einsum("bse,ed->bsd", ctx,
                                  lp["attn_out_w"].astype(cfg.dtype))


def _layer(x, lp, sel, cfg: SdarConfig, kind: str, is_moe: bool = True,
           positions=None):
    """One layer.  x [B, 2 L, D]; returns `(x, routing)`."""
    del is_moe                          # every layer is
    return mellum._experts(_attention(x, lp, cfg, kind, positions), lp, sel,
                           cfg, family="sdar")


def run_rows(params: PyTree, batch, cfg: SdarConfig, sel=None,
             with_routing: bool = False):
    """The layers over both copies: `(x [B, 2 L, D] after the last layer,
    before the final norm; Routing or None)`.  `sel` [layers, B * 2 L, k]
    replaces every router's own top-k (`dropless_moe.route`)."""
    x, positions = two_copies(params, batch, cfg)
    return afmoe.run_layers(
        params, x, cfg, sel, with_routing,
        functools.partial(_layer, positions=positions))


def head_loss(params: PyTree, x: jax.Array, batch, cfg: SdarConfig):
    """Step 4 on the rows `x` [B, 2 L, D] of the last layer: the noised
    rows' logits against the tokens themselves, weighted, over ALL L
    tokens."""
    tokens, _, weight = batch
    L = tokens.shape[1]
    with jax.named_scope("sdar.head"):
        x = _rms_norm(x[:, L:], params["final_ln"], None,
                      eps=cfg.rms_norm_eps)
        targets = tokens - cfg.vocab_start
        if cfg.ce_chunk_rows:
            return fused_nll_sum(x, params["head"], targets,
                                 cfg.ce_chunk_rows,
                                 weights=weight) / targets.size
        logp = jax.nn.log_softmax(afmoe.head_logits(x, params["head"]),
                                  axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return (nll * weight).sum() / targets.size


def loss_fn(params: PyTree, batch, cfg: SdarConfig, sel=None) -> jax.Array:
    """The block-diffusion loss over the held slice of the vocabulary.
    batch = (tokens [B, L] int32, masked [B, L] bool, weight [B, L]
    float32 = masked / t)."""
    x, _ = run_rows(params, batch, cfg, sel)
    return head_loss(params, x, batch, cfg)


def routing(params: PyTree, batch, cfg: SdarConfig):
    """The program's own choice of experts on the batch's 2 L rows, a
    `dropless_moe.Routing` with leaves stacked over the layers."""
    return run_rows(params, batch, cfg, with_routing=True)[1]


def synthetic_batch(rng: jax.Array, batch_size: int, seq_len: int,
                    cfg: SdarConfig):
    """`(tokens, masked, weight)`, each [B, L]: token ids uniform over the
    held slice LESS its last id (the mask token), one t a block uniform
    on [eps, 1), each token masked with probability t, weight = masked /
    t."""
    beta = cfg.block_length
    if seq_len % beta:
        raise ValueError(f"seq_len {seq_len} is no whole number of blocks "
                         f"of {beta}")
    k_tokens, k_t, k_masked = jax.random.split(rng, 3)
    tokens = jax.random.randint(k_tokens, (batch_size, seq_len),
                                cfg.vocab_start, cfg.mask_token, jnp.int32)
    u = jax.random.uniform(k_t, (batch_size, seq_len // beta), jnp.float32)
    t = jnp.repeat(cfg.noise_eps + (1.0 - cfg.noise_eps) * u, beta, axis=1)
    masked = jax.random.uniform(k_masked, (batch_size, seq_len),
                                jnp.float32) < t
    return tokens, masked, masked.astype(jnp.float32) / t


def batch_counters(batch) -> dict:
    """A batch's two counters, traceable: the share of its tokens that are
    masked and the mean of its weights over all tokens (1 in
    expectation)."""
    _, masked, weight = batch
    return {"masked_share": jnp.mean(masked.astype(jnp.float32)),
            "weight_mean": jnp.mean(weight)}


def record_batch(counters: dict) -> None:
    """`batch_counters`' numbers, on the host, as the gauges
    `bps_bd_masked_share` and `bps_bd_weight_mean`."""
    telemetry.record_static(
        "block_diffusion_batch",
        **{name: float(value) for name, value in counters.items()})
