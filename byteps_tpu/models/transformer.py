"""TPU-native transformer language model (the flagship model family).

The reference's headline benchmark is BERT-large data-parallel training
(reference: README.md:38-46 — ~90% scaling efficiency at 256 GPUs, GluonNLP
BERT via an external repo; the reference itself ships no model code).  This
module supplies the model the reference outsources: a pure-JAX transformer
encoder/decoder LM designed for the MXU —

  - all matmuls are (batch*seq, d_model) x (d_model, N) shaped, bf16 by
    default, so XLA tiles them onto the systolic array;
  - per-layer `jax.checkpoint` (rematerialisation) trades FLOPs for HBM;
  - params are a flat pytree of named arrays with an accompanying
    PartitionSpec tree (`param_specs`) giving Megatron-style tensor
    parallelism over the 'tp' mesh axis: QKV and MLP-in are column-sharded,
    attention-out and MLP-out row-sharded, everything else replicated;
  - layers are stacked with `lax.scan` over a single stacked param tree
    (compile time stays O(1) in depth, and the leading layer axis doubles as
    the pipeline-stage axis for 'pp').

Configs mirror the reference benchmark suite: bert_base/bert_large
(README.md:38-46) plus tiny variants for tests, and a llama-class decoder
family (RMSNorm + SwiGLU + RoPE + grouped-query attention, no biases) via
the norm/act/pos/num_kv_heads/use_bias knobs — the modern-LLM block on the
same stacked-scan machinery, so TP specs, pipeline stacking, remat, and
the flash/ring attention registry all apply unchanged.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

PyTree = Any


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32768
    num_layers: int = 12
    d_model: int = 768
    num_heads: int = 12
    d_ff: int = 3072
    max_seq_len: int = 512
    dtype: Any = jnp.bfloat16          # activation/compute dtype (MXU-native)
    param_dtype: Any = jnp.float32     # master params stay f32
    causal: bool = True                # decoder LM; False = BERT-style encoder
    # Modern-LLM (llama-class) architecture knobs.  Defaults reproduce the
    # classic BERT/GPT block exactly (same param tree, same math).
    norm: str = "layernorm"            # "layernorm" | "rmsnorm"
    act: str = "gelu"                  # "gelu" | "swiglu"
    pos: str = "learned"               # "learned" | "rope"
    rope_theta: float = 10000.0
    num_kv_heads: Optional[int] = None  # GQA/MQA: < num_heads; None = MHA
    use_bias: bool = True              # llama-class blocks drop biases
    remat: bool = True                 # per-layer rematerialisation
    # What the per-layer checkpoint may keep: "none" saves only layer
    # inputs (max recompute, min HBM); "dots" saves matmul outputs
    # (skips re-running the MXU work in backward but keeps the O(S²) and
    # O(4D) tensors — OOMs first at large batch); "dots_no_batch" drops
    # batch-dim-carrying dots; "proj" saves only the O(B·S·D) projection
    # outputs (qkv / attn ctx+proj / ffn down) and recomputes attention
    # logits + FFN-up in backward — fits where "dots" OOMs at large
    # batch while skipping most of full remat's recompute
    # (measurements: docs/performance.md).
    remat_policy: str = "none"    # "none" | "dots" | "dots_no_batch" | "proj"
    attn_impl: str = "dense"           # "dense" | "flash" | "ring" (sp)
    # Flash-kernel override of the rows of a group (0 = the rule,
    # `flash_auto_tiles`: square tiles, full-sequence at S <= 512 and the
    # largest of 512/256/128 dividing S beyond; causal at S <= 1024,
    # groups of S / 4 rows over all their keys).  Must divide seq_len and
    # be a multiple of 128.
    attn_block: int = 0
    # Override of the tile of keys (0 = same as attn_block, or the rule's
    # where that is 0 too).  The rows set what is computed above the
    # causal diagonal, the tile of keys how often the softmax's per-row
    # bookkeeping is paid.
    attn_block_k: int = 0
    # Fused LM-head cross-entropy: > 0 streams the readout matmul + softmax
    # in row chunks of this size so the [B*S, vocab] logits are never
    # materialized (forward OR backward — each chunk is rematerialised).
    # 0 = classic path through full logits.  At vocab 32768 and 24k rows
    # (batch 48 x 512) the full f32 logits are 3.2 GB and their HBM
    # traffic is the largest non-matmul cost in the step.
    ce_chunk_rows: int = 0
    # Unroll factor for the layer scan (lax.scan unroll=).  > 1 groups
    # that many layers per scan iteration: more code, but XLA can
    # schedule/fuse across adjacent layers and the stacked-param slice
    # overhead amortizes.  Remat granularity is unchanged (each layer
    # body is checkpointed individually).  Must divide num_layers or be
    # 1.
    scan_unroll: int = 1

    def __post_init__(self):
        for field, val, allowed in (
                ("norm", self.norm, ("layernorm", "rmsnorm")),
                ("act", self.act, ("gelu", "swiglu")),
                ("pos", self.pos, ("learned", "rope"))):
            if val not in allowed:
                # A typo here must not silently drop positions/gating.
                raise ValueError(f"{field}={val!r}; options: {allowed}")
        if self.d_model % self.num_heads:
            raise ValueError(f"d_model={self.d_model} not divisible by "
                             f"num_heads={self.num_heads}")
        if self.num_kv_heads is not None:
            if self.num_kv_heads < 1:
                raise ValueError("num_kv_heads must be >= 1 (or None for "
                                 "full multi-head attention)")
            if self.num_heads % self.num_kv_heads:
                raise ValueError(
                    f"num_heads={self.num_heads} not divisible by "
                    f"num_kv_heads={self.num_kv_heads} (GQA shares each kv "
                    f"head across an integer group of query heads)")
        if self.pos == "rope" and self.head_dim % 2:
            raise ValueError(f"pos='rope' needs an even head_dim "
                             f"(got {self.head_dim})")
        if self.ce_chunk_rows < 0:
            raise ValueError(f"ce_chunk_rows={self.ce_chunk_rows} must be "
                             f">= 0 (0 = unfused full-logits path)")
        if self.scan_unroll < 1 or self.num_layers % self.scan_unroll:
            raise ValueError(
                f"scan_unroll={self.scan_unroll} must be >= 1 and divide "
                f"num_layers={self.num_layers} (a remainder iteration "
                f"would compile a second layer-group program)")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads

    @property
    def kv_heads(self) -> int:
        return (self.num_kv_heads if self.num_kv_heads is not None
                else self.num_heads)


# Benchmark-suite configs (reference README.md:38-46 benchmarks BERT-large;
# docs/performance.md benchmarks ResNet50/VGG16 — see models/cnn.py).
CONFIGS: Dict[str, TransformerConfig] = {
    "tiny": TransformerConfig(vocab_size=1024, num_layers=2, d_model=64,
                              num_heads=4, d_ff=128, max_seq_len=128),
    "bert_base": TransformerConfig(num_layers=12, d_model=768, num_heads=12,
                                   d_ff=3072, causal=False),
    "bert_large": TransformerConfig(num_layers=24, d_model=1024, num_heads=16,
                                    d_ff=4096, causal=False),
    "gpt_small": TransformerConfig(num_layers=12, d_model=768, num_heads=12,
                                   d_ff=3072, causal=True),
    "gpt_medium": TransformerConfig(num_layers=24, d_model=1024, num_heads=16,
                                    d_ff=4096, causal=True),
    # Llama-class decoder (RMSNorm + SwiGLU + RoPE + GQA, no biases) — the
    # modern-LLM block shape, at two scales.
    "llama_tiny": TransformerConfig(vocab_size=1024, num_layers=2, d_model=64,
                                    num_heads=4, num_kv_heads=2, d_ff=160,
                                    max_seq_len=128, norm="rmsnorm",
                                    act="swiglu", pos="rope", use_bias=False),
    "llama_1b": TransformerConfig(vocab_size=32768, num_layers=16,
                                  d_model=2048, num_heads=32, num_kv_heads=8,
                                  d_ff=5504, max_seq_len=2048, norm="rmsnorm",
                                  act="swiglu", pos="rope", use_bias=False),
    # ~300M-param llama geometry: the largest modern-LLM config whose f32
    # master weights + Adam moments (~4.8 GB) leave headroom for a real
    # batch at seq 2048 on one 16 GB chip — llama_1b's ~9.3 GB of
    # optimizer state does not fit one chip, so long-sequence
    # single-chip runs use this one (multi-chip llama_1b shards the state).
    "llama_300m": TransformerConfig(vocab_size=32768, num_layers=24,
                                    d_model=1024, num_heads=16,
                                    num_kv_heads=4, d_ff=2816,
                                    max_seq_len=2048, norm="rmsnorm",
                                    act="swiglu", pos="rope", use_bias=False),
}


def get_config(name: str, **overrides) -> TransformerConfig:
    cfg = CONFIGS[name]
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


# ---------------------------------------------------------------------------
# Parameter init.  Layer params are stacked along a leading num_layers axis.
# ---------------------------------------------------------------------------
def init_params(rng: jax.Array, cfg: TransformerConfig) -> PyTree:
    dt = cfg.param_dtype
    k_emb, k_pos, k_layers, k_out = jax.random.split(rng, 4)

    def dense_init(key, shape, fan_in):
        return (jax.random.normal(key, shape, dt) / jnp.sqrt(fan_in)).astype(dt)

    L, D, F = cfg.num_layers, cfg.d_model, cfg.d_ff
    Dh, Hkv = cfg.head_dim, cfg.kv_heads
    qkv_cols = (cfg.num_heads + 2 * Hkv) * Dh
    lkeys = jax.random.split(k_layers, 6)

    def stack(key, shape, fan_in):
        ks = jax.random.split(key, L)
        return jnp.stack([dense_init(k, shape, fan_in) for k in ks])

    layers = {
        "qkv_w": stack(lkeys[0], (D, qkv_cols), D),
        "attn_out_w": stack(lkeys[1], (cfg.num_heads * Dh, D),
                            cfg.num_heads * Dh),
        "mlp_in_w": stack(lkeys[2], (D, F), D),
        "mlp_out_w": stack(lkeys[3], (F, D), F),
        "ln1_scale": jnp.ones((L, D), dt),
        "ln2_scale": jnp.ones((L, D), dt),
    }
    if cfg.act == "swiglu":
        layers["mlp_gate_w"] = stack(lkeys[4], (D, F), D)
    if cfg.use_bias:
        layers.update({
            "ln1_bias": jnp.zeros((L, D), dt),
            "ln2_bias": jnp.zeros((L, D), dt),
            "qkv_b": jnp.zeros((L, qkv_cols), dt),
            "attn_out_b": jnp.zeros((L, D), dt),
            "mlp_in_b": jnp.zeros((L, F), dt),
            "mlp_out_b": jnp.zeros((L, D), dt),
        })
    out = {
        "embed": dense_init(k_emb, (cfg.vocab_size, D), D),
        "layers": layers,
        "ln_f_scale": jnp.ones((D,), dt),
    }
    if cfg.pos == "learned":
        out["pos_embed"] = (jax.random.normal(k_pos, (cfg.max_seq_len, D), dt)
                            * 0.02).astype(dt)
    if cfg.use_bias:
        out["ln_f_bias"] = jnp.zeros((D,), dt)
    return out


def param_specs(cfg: TransformerConfig, tp_axis: str = "tp",
                pp_axis: Optional[str] = None) -> PyTree:
    """PartitionSpec tree for Megatron-style TP (column/row split) with the
    stacked layer axis optionally sharded over the pipeline axis.

    Mirrors init_params' conditional keys (GQA/SwiGLU/no-bias/rope).  The
    GQA qkv layout ([q | k | v] flat columns) is a GSPMD hint, not a
    manual shard index — XLA reshards around the head split as needed.
    """
    pp = pp_axis  # leading stacked-layer dim
    layers = {
        "qkv_w": P(pp, None, tp_axis),
        "attn_out_w": P(pp, tp_axis, None),
        "mlp_in_w": P(pp, None, tp_axis),
        "mlp_out_w": P(pp, tp_axis, None),
        "ln1_scale": P(pp, None),
        "ln2_scale": P(pp, None),
    }
    if cfg.act == "swiglu":
        layers["mlp_gate_w"] = P(pp, None, tp_axis)
    if cfg.use_bias:
        layers.update({
            "ln1_bias": P(pp, None),
            "ln2_bias": P(pp, None),
            "qkv_b": P(pp, tp_axis),
            "attn_out_b": P(pp, None),
            "mlp_in_b": P(pp, tp_axis),
            "mlp_out_b": P(pp, None),
        })
    out = {
        "embed": P(None, None),
        "layers": layers,
        "ln_f_scale": P(None),
    }
    if cfg.pos == "learned":
        out["pos_embed"] = P(None, None)
    if cfg.use_bias:
        out["ln_f_bias"] = P(None)
    return out


# ---------------------------------------------------------------------------
# Forward pass.
# ---------------------------------------------------------------------------
def _layer_norm(x, scale, bias, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    y = y * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def _rms_norm(x, scale, bias, eps=1e-6):
    """RMSNorm (no mean subtraction; llama-class blocks pass bias=None)."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    y = y * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


_NORMS = {"layernorm": _layer_norm, "rmsnorm": _rms_norm}


def _rope(x, theta: float, inv_freq=None, amplitude: float = 1.0,
          positions=None, sections=None):
    """Rotary position embedding on [B, H, S, Dh] (half-split layout).

    The rotation runs in float32: at positions near max_seq_len, bf16
    cos/sin (~3 significant digits) visibly degrade the rotation, so cast
    back to the compute dtype only after rotating (standard practice).

    `inv_freq` [Dh / 2] puts a frequency of its own for each pair in
    place of `theta ** (-2i / Dh)`, and `amplitude` multiplies cos and
    sin: what scaled positions need (`models/mellum.py` `yarn_inv_freq`).

    `positions` puts the rows' positions in place of 0 .. S - 1: ONE
    stream, [S] or [B, S]; or, with `sections`, one stream for each
    section, [n, S] or [n, B, S], and `sections` (n counts that sum to
    Dh / 2) says how many of the pairs, in order, turn by each stream
    (`models/keye.py`: temporal, height, width).  Left alone, the program
    is the one it was."""
    B, H, S, Dh = x.shape
    half = Dh // 2
    if inv_freq is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freqs = jnp.asarray(inv_freq, jnp.float32)
    if positions is None:
        angles = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    else:
        where = jnp.asarray(positions, jnp.float32)
        if sections is None:
            where = where[..., None]                # [.., S, 1]
        else:
            if sum(sections) != half or where.shape[0] != len(sections):
                raise ValueError(
                    f"sections {tuple(sections)} over {where.shape[0]} "
                    f"streams do not divide {half} pairs")
            # each pair's own stream: [half, .., S] -> [.., S, half]
            pair = np.repeat(np.arange(len(sections)), sections)
            where = jnp.moveaxis(where[pair], 0, -1)
        angles = where * freqs                      # [S, half] | [B, S, half]
        if angles.ndim == 3:
            angles = angles[:, None]                # over the heads
    cos = jnp.cos(angles)                   # [S, half], f32
    sin = jnp.sin(angles)
    if amplitude != 1.0:
        cos, sin = cos * amplitude, sin * amplitude
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def dense_attention(q, k, v, causal: bool):
    """q,k,v: [B, H, S, Dh].  Softmax in f32 for stability."""
    dh = q.shape[-1]
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32)
    logits = logits / jnp.sqrt(jnp.asarray(dh, jnp.float32))
    if causal:
        s = q.shape[2]
        mask = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def flash_auto_tiles(S: int, causal: bool = False) -> Tuple[int, int]:
    """The flash adapter's tile rule: `(block_q, block_k)` for a sequence
    of `S`, or (0, 0) when no valid tile exists: the kernel's `block_q`
    must be a multiple of 128 (ops/flash_attention.py `check_blocks`), so
    S must be one.  `block_q` is the rows of a group, each with its own
    stretch of keys; `block_k` the width of a tile of keys.

    Not causal, or S > 1024: square tiles, the whole sequence at
    S <= 512, else the largest of 512/256/128 that divides S.  Causal and
    S <= 1024, where one program owns a head's whole sequence and its
    bounds are static: groups of a quarter of the sequence (at least 128
    rows), each taking ALL its keys as one tile, `block_k` = S, so that
    no pair is computed past the end of a group's diagonal and the
    softmax's per-row bookkeeping is paid once.

    From the chip (TPU v5e, the three calls alone under the profiler,
    us a head for forward + forward + dQ + dK/dV; PERF.md section 6,
    PR 35): at S = 1024, head size 64, causal, (256, 1024) 11.6,
    (128, 1024) 12.1, (512, 512) 12.5, (512, 1024) 12.6, and (256, 256)
    half as much again; at S = 8192, (512, 512) 4% under (256, 512), at
    head size 64 and 128 alike.  A tile's time is the vector unit's, not the
    MXU's: narrow tiles of keys pay the [rows, 1] statistics as often as
    wide ones and lose.

    The rule does not ask which path the kernel takes.  Where K and V of
    a head pass the resident budget (S = 32,768 at head size 128: the
    mellum cell) the same (512, 512) tiles the STREAMING kernels: a tile
    is then a grid step, its K and V one copy of 2 x 128 KB, and a tile
    no row can see is no step at all (`flash_attention.stream_walk`: a
    causal call's grid is the table of its live tiles, 2,080 a head at
    S = 32,768; a windowed call's its blocks' bands, 3 steps each under
    a window of 1024)."""
    if S % 128:
        return 0, 0
    if causal and S <= 1024:
        return (S // 4 if S % 512 == 0 else 128), S
    block = S if S <= 512 else next(b for b in (512, 256, 128) if S % b == 0)
    return block, block


def flash_auto_block(S: int, causal: bool = False) -> int:
    """`block_q` of `flash_auto_tiles`, exported so a caller can state
    the block that actually runs without duplicating the logic."""
    return flash_auto_tiles(S, causal)[0]


def flash_attention_fn(q, k, v, causal: bool, block: int = 0,
                       block_k: int = 0, window: Optional[int] = None,
                       block_diffusion: Optional[tuple] = None):
    """Adapter: [B, H, S, Dh] heads-layout -> the Pallas flash-attention
    kernel's [BH, S, Dh] layout; `v` may be another width than `q` and
    `k` (latent attention's 192 and 128), and the result is as wide as
    `v`.  A shape the kernel cannot tile (S not a
    multiple of 128, or a width not a multiple of 8) raises ValueError: an
    explicit flash request never silently runs dense attention, which
    would materialize the S x S logits the caller chose flash to avoid
    and attribute dense throughput to a flash config.

    block=0 auto-selects both tiles via `flash_auto_tiles`.  A nonzero
    override sets the rows of a group by hand
    (TransformerConfig.attn_block) and, unless `block_k` says otherwise
    (TransformerConfig.attn_block_k), the tile of keys with it.
    Overrides must divide S and be a multiple of 128 (block) or 64
    (block_k), the tile sizes the chip's compiler accepts; anything else
    reverts to the AUTO choice.

    `window` (causal only) is the kernel's sliding window: row i sees the
    keys i - window < j <= i.  None is full attention.

    `block_diffusion` = `(L, beta)` (neither causal nor windowed) is the
    kernel's mask over the two copies of a sequence, S = 2 L
    (`models/sdar.py`): a tile then lies in one copy, so the tiles are
    the rule's for L and an override must divide L."""
    from ..ops.flash_attention import (BLOCK_K_MULTIPLE, BLOCK_Q_MULTIPLE,
                                       flash_attention)
    B, H, S, Dh = q.shape
    Dv = v.shape[-1]
    tiled = S if block_diffusion is None else block_diffusion[0]
    auto_q, auto_k = flash_auto_tiles(tiled, causal)
    if not block or tiled % block or block % BLOCK_Q_MULTIPLE:
        block = 0
    if not block_k or tiled % block_k or block_k % BLOCK_K_MULTIPLE:
        block_k = block or auto_k
    block = block or auto_q
    if block == 0 or Dh % 8 or Dv % 8:
        raise ValueError(
            f"flash attention needs seq_len divisible by "
            f"{BLOCK_Q_MULTIPLE} (got {S}) and head_dim a multiple of 8 "
            f"(got {Dh if Dh % 8 else Dv}); pad the sequence or ask for "
            f"attn='dense' explicitly")

    def fold(t):
        return t.reshape(B * H, S, t.shape[-1])
    windowed = () if window is None else (None, None, window)
    if block_diffusion is not None:
        windowed = (None, None, window, tuple(block_diffusion))
    out = flash_attention(fold(q), fold(k), fold(v), causal, None,
                          block, block_k, *windowed)
    return out.reshape(B, H, S, Dv)


_ATTN_IMPLS = {"dense": dense_attention, "flash": flash_attention_fn}


def _ckpt_name(x, name: str):
    """Tag an intermediate for name-based remat policies.

    A no-op unless the enclosing `jax.checkpoint` uses a name-aware policy
    (remat_policy="proj" below); then the tagged tensors are the ONLY ones
    saved and everything else is recomputed in backward.
    """
    from jax import ad_checkpoint
    return ad_checkpoint.checkpoint_name(x, name)


def _block(x, lp, cfg: TransformerConfig, attn_fn):
    """One transformer block.  x: [B, S, D]; lp: this layer's param slice."""
    dt = cfg.dtype
    B, S, D = x.shape
    H, Hkv, Dh = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    norm = _NORMS[cfg.norm]

    def bias(name):
        return lp[name].astype(dt) if name in lp else None

    def add_bias(t, name):
        b = bias(name)
        return t if b is None else t + b

    with jax.named_scope("transformer.attn"):
        # a child's name is relative, a leading ".": the scope map
        # (`bps.get_step_scopes()`) reads `transformer.attn/qkv`
        with jax.named_scope(".qkv"):
            h = norm(x, lp["ln1_scale"], bias("ln1_bias"))
            qkv = _ckpt_name(
                add_bias(jnp.einsum("bsd,de->bse", h,
                                    lp["qkv_w"].astype(dt)), "qkv_b"), "qkv")
            q, k, v = jnp.split(qkv, [H * Dh, (H + Hkv) * Dh], axis=-1)

            def heads(t):
                return t.reshape(B, S, -1, Dh).transpose(0, 2, 1, 3)
            q, k, v = heads(q), heads(k), heads(v)
            if cfg.pos == "rope":
                q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
            if Hkv != H:
                # GQA: each query-head group shares one kv head — expand
                # for the attention kernel (the bandwidth saving is in
                # params/KV-cache, not this training-time broadcast).
                k = jnp.repeat(k, H // Hkv, axis=1)
                v = jnp.repeat(v, H // Hkv, axis=1)
        # the kernels and the transpose after them stay the half's own:
        # the trace calls an unnamed call after the innermost scope
        attn = attn_fn(q, k, v, cfg.causal)
        attn = _ckpt_name(attn.transpose(0, 2, 1, 3).reshape(B, S, -1),
                          "attn_ctx")
        with jax.named_scope(".out"):
            attn = _ckpt_name(add_bias(
                jnp.einsum("bse,ed->bsd", attn, lp["attn_out_w"].astype(dt)),
                "attn_out_b"), "attn_proj")
            x = x + attn

    with jax.named_scope("transformer.mlp"):
        h = norm(x, lp["ln2_scale"], bias("ln2_bias"))
        up = add_bias(jnp.einsum("bsd,df->bsf", h, lp["mlp_in_w"].astype(dt)),
                      "mlp_in_b")
        if cfg.act == "swiglu":
            gate = jnp.einsum("bsd,df->bsf", h, lp["mlp_gate_w"].astype(dt))
            h = jax.nn.silu(gate) * up
        else:
            h = jax.nn.gelu(up)
        h = _ckpt_name(
            add_bias(jnp.einsum("bsf,fd->bsd", h, lp["mlp_out_w"].astype(dt)),
                     "mlp_out_b"), "ffn_out")
        return x + h


def forward_hidden(params: PyTree, tokens: jax.Array, cfg: TransformerConfig,
                   attn_fn=None) -> jax.Array:
    """tokens [B, S] int32 -> final hidden states [B, S, D] (post ln_f).

    Layers run under `lax.scan` over the stacked params; each step is
    optionally rematerialised.  `attn_fn(q,k,v,causal)` defaults to dense
    attention; ring attention (ops/ring_attention.py) slots in when the
    sequence is sharded over 'sp'.
    """
    if attn_fn is None:
        if cfg.attn_impl not in _ATTN_IMPLS:
            # "ring"/"ulysses" need a mesh-bound fn; anything else is a
            # typo — silently running dense would hide the config error
            # (and the S x S memory blow-up the user tried to avoid).
            raise ValueError(
                f"attn_impl={cfg.attn_impl!r} needs an explicit attn_fn "
                f"(ring/Ulysses: ops.ring_attention.make_ring_attn_fn / "
                f"make_ulysses_attn_fn); built-ins: "
                f"{sorted(_ATTN_IMPLS)}")
        attn_fn = _ATTN_IMPLS[cfg.attn_impl]
        if cfg.attn_impl == "flash" and (cfg.attn_block
                                         or cfg.attn_block_k):
            attn_fn = functools.partial(flash_attention_fn,
                                        block=cfg.attn_block,
                                        block_k=cfg.attn_block_k)
    dt = cfg.dtype
    B, S = tokens.shape
    with jax.named_scope("transformer.embed"):
        x = params["embed"].astype(dt)[tokens]
        if cfg.pos == "learned":
            x = x + params["pos_embed"].astype(dt)[:S]

    def body(carry, lp):
        y = _block(carry, lp, cfg, attn_fn)
        return y, None

    if cfg.remat:
        policies = {
            "none": None,
            "dots": jax.checkpoint_policies.checkpoint_dots,
            "dots_no_batch":
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            # Selective "minimal" remat (the transformer sweet spot): save
            # only the model-dim projection outputs — qkv, attention
            # context/projection, ffn down — which are O(B·S·D), and
            # recompute the expensive-to-store pieces (S x S attention
            # logits/probs, the 4D-wide FFN up + activation, the f32 norm
            # intermediates) in backward.  vs full remat ("none") this
            # skips re-running ~2/3 of the matmul FLOPs; vs "dots" it
            # avoids saving the O(B·H·S²) and O(B·S·4D) tensors that blow
            # HBM at large batch.
            "proj": jax.checkpoint_policies.save_only_these_names(
                "qkv", "attn_ctx", "attn_proj", "ffn_out"),
        }
        if cfg.remat_policy not in policies:
            raise ValueError(f"remat_policy={cfg.remat_policy!r}; "
                             f"options: {sorted(policies)}")
        step = jax.checkpoint(body, policy=policies[cfg.remat_policy])
    else:
        step = body
    x, _ = lax.scan(step, x, params["layers"], unroll=cfg.scan_unroll)
    with jax.named_scope("transformer.head"):
        return _NORMS[cfg.norm](x, params["ln_f_scale"],
                                params.get("ln_f_bias"))


def forward(params: PyTree, tokens: jax.Array, cfg: TransformerConfig,
            attn_fn=None) -> jax.Array:
    """tokens [B, S] int32 -> logits [B, S, vocab] (f32).

    Weight-tied readout against the embedding (keeps the big vocab matmul
    on the MXU once, not twice), computed in the activation dtype with f32
    accumulation — the MXU-native form; an all-f32 matmul would run in
    multi-pass emulation on TPU.
    """
    x = forward_hidden(params, tokens, cfg, attn_fn=attn_fn)
    with jax.named_scope("transformer.head"):
        return jnp.einsum("bsd,vd->bsv", x,
                          params["embed"].astype(x.dtype),
                          preferred_element_type=jnp.float32)


def fused_nll_sum(x: jax.Array, embed: jax.Array, targets: jax.Array,
                  chunk_rows: int, weights=None) -> jax.Array:
    """Streamed weight-tied LM cross-entropy: SUM of per-row NLL without
    ever materializing the full [B*S, vocab] logits.  (Callers divide by
    their own token count — the hybrid shard_map step normalizes by the
    GLOBAL count across mesh axes.)  `weights` [B, S] multiplies each
    row's NLL (0 for a position without a target: a second prediction
    head's last positions, `models/joyai.py`); None is 1 everywhere and
    the program it always was.

    Rows are processed in `chunk_rows`-sized chunks under `lax.scan`; each
    chunk computes its logits (activation-dtype matmul, f32 accumulation),
    reduces them to logsumexp + target logit, and is wrapped in
    `jax.checkpoint` so the backward pass recomputes the chunk logits
    instead of saving them.  Meant to run on per-shard (local) inputs —
    build_train_step's shard_map and the hybrid step both satisfy this; a
    GSPMD (jit-sharded) caller whose batch axis is sharded should expect
    the partitioner to move data across shards for the chunked scan.  Peak logits memory drops from O(B*S*V) to
    O(chunk_rows*V) in both passes; the matmul work is unchanged and stays
    MXU-shaped.  (Reference analog: BytePS's whole pitch is removing
    non-compute bottlenecks from the training step — docs/performance.md;
    here the bottleneck is HBM traffic rather than network.)
    """
    B, S, D = x.shape
    N = B * S
    C = min(chunk_rows, N)
    xs = x.reshape(N, D)
    ts = targets.reshape(N)
    pad = (-N) % C
    if pad:
        xs = jnp.concatenate([xs, jnp.zeros((pad, D), xs.dtype)])
        ts = jnp.concatenate([ts, jnp.zeros((pad,), ts.dtype)])
    rows_w = (jnp.ones((N,), jnp.float32) if weights is None
              else weights.reshape(N).astype(jnp.float32))
    w = jnp.concatenate([rows_w, jnp.zeros((pad,), jnp.float32)])
    nc = (N + pad) // C
    emb = embed.astype(x.dtype)

    def chunk_nll_sum(xc, tc, wc):
        logits = jnp.einsum("cd,vd->cv", xc, emb,
                            preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, tc[:, None], axis=1)[:, 0]
        return ((lse - tgt) * wc).sum()

    chunk_nll_sum = jax.checkpoint(chunk_nll_sum)

    def body(acc, args):
        return acc + chunk_nll_sum(*args), None

    total, _ = lax.scan(body, jnp.zeros((), jnp.float32),
                        (xs.reshape(nc, C, D), ts.reshape(nc, C),
                         w.reshape(nc, C)))
    return total


def loss_fn(params: PyTree, batch: Tuple[jax.Array, jax.Array],
            cfg: TransformerConfig, attn_fn=None) -> jax.Array:
    """Cross-entropy LM loss.  batch = (tokens [B,S], targets [B,S]).

    With cfg.ce_chunk_rows > 0 the LM head is streamed (see _fused_lm_loss);
    otherwise the classic full-logits log_softmax path runs.  Both compute
    the same value up to f32 reduction order.
    """
    tokens, targets = batch
    if cfg.ce_chunk_rows:
        x = forward_hidden(params, tokens, cfg, attn_fn=attn_fn)
        with jax.named_scope("transformer.head"):
            return fused_nll_sum(x, params["embed"], targets,
                                 cfg.ce_chunk_rows) / targets.size
    logits = forward(params, tokens, cfg, attn_fn=attn_fn)
    with jax.named_scope("transformer.head"):
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return nll.mean()


def num_params(params: PyTree) -> int:
    return sum(p.size for p in jax.tree.leaves(params))


def flops_per_token(cfg: TransformerConfig) -> float:
    """Approximate training FLOPs/token (6N rule + attention)."""
    qkv_cols = (cfg.num_heads + 2 * cfg.kv_heads) * cfg.head_dim
    mlp_mats = 3 if cfg.act == "swiglu" else 2
    n = (cfg.num_layers * (cfg.d_model * qkv_cols                 # qkv
                           + cfg.num_heads * cfg.head_dim * cfg.d_model
                           + mlp_mats * cfg.d_model * cfg.d_ff)   # mlp
         + cfg.vocab_size * cfg.d_model)
    attn = cfg.num_layers * 2 * cfg.max_seq_len * cfg.d_model
    return 6.0 * (n + attn)


def synthetic_batch(rng: jax.Array, batch_size: int, seq_len: int,
                    cfg: TransformerConfig) -> Tuple[jax.Array, jax.Array]:
    """Random token batch for benchmarking (the reference benchmarks with
    synthetic data too — example/pytorch/benchmark_byteps.py)."""
    toks = jax.random.randint(rng, (batch_size, seq_len + 1), 0,
                              cfg.vocab_size, jnp.int32)
    return toks[:, :-1], toks[:, 1:]
