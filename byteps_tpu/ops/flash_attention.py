"""Flash attention as a Pallas TPU kernel (forward + backward).

The flagship transformer's dense attention materializes the [S, S] logits
in HBM per layer (models/transformer.py dense_attention) — the classic
memory-bound hot spot.  This kernel computes attention blockwise with an
online softmax so nothing bigger than a (block_q, block_k) tile of logits
ever exists, and the backward recomputes probabilities blockwise from the
saved log-sum-exp instead of storing them.

Two execution strategies, auto-selected by VMEM footprint:

  - **resident** (short/medium S): K and V live in VMEM for the whole
    kernel; each q block loops over them with `lax.fori_loop`.  K/V are
    fetched from HBM once per (batch*head), which is what makes the
    kernel beat XLA's fused dense attention (measured 1.6x at S=4096 on
    v5e, docs/performance.md).
  - **streaming** (long S): 3D grid with the contraction axis innermost —
    (bh, q_blocks, k_blocks) forward/dq, (bh, k_blocks, q_blocks) dk/dv —
    carrying running statistics in VMEM scratch across the innermost
    iterations (the matmul k-loop pattern).  Per-program VMEM is
    O(block * d) regardless of S, so the kernel keeps compiling at 32k+
    contexts, at the price of re-streaming K/V once per q block.

Causal grids predicate away upper-triangle blocks (`pl.when` in the
streaming path, a shortened `fori_loop` bound in the resident path) so
masked blocks' matmuls never issue.

This is the compute-path counterpart of the reference's CUDA-side
optimizations: the reference leaves model compute to torch/cudnn (no
attention kernels of its own); a TPU-native framework owns its hot ops
(pallas guide: grid/BlockSpec tiling onto the MXU, f32 accumulation,
custom-VJP pattern).

Layout: q, k, v are [BH, S, D] (batch*heads folded into the grid's first
axis).  The block sizes must divide S; block_q must be a multiple of 128
and block_k a multiple of 64 (`check_blocks` — the chip's lane rule, which
interpret mode does not enforce); D should be a multiple of 8 (128 ideal
for the MXU lane).  A shape that doesn't satisfy the constraints is
refused up front, on every backend: the kernel never degrades to dense
attention, and neither does `models.transformer.flash_attention_fn`.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")

# K+V (resident path) above this many bytes switch to the streaming path;
# ~16MB VMEM/core on current TPUs, leave room for q/o/do tiles + scratch.
RESIDENT_VMEM_BUDGET = 6 * 1024 * 1024


# The Q tile is the LANE dim of the per-row statistics blocks (log-sum-exp
# and delta, shape (1, 1, block_q)), and the TPU lowering takes a block's
# last dim only in multiples of 128.  Mosaic also refuses the resident
# dK/dV kernel's lane-dim slices of those rows unless they are provably
# 128-aligned, so "block_q == S" does not rescue an S that is an odd
# multiple of 64.  K/V tiles only ever sit on a sublane dim.  Both facts
# are from compiling for a described v5e (tests/test_tpu_aot_compile.py).
BLOCK_Q_MULTIPLE = 128
BLOCK_K_MULTIPLE = 64


def check_blocks(s: int, block_q: int, block_k: int) -> None:
    """Raise ValueError unless (block_q, block_k) tile a length-`s`
    sequence in a way the chip's compiler accepts."""
    if (block_q <= 0 or block_k <= 0 or s % block_q or s % block_k
            or block_q % BLOCK_Q_MULTIPLE or block_k % BLOCK_K_MULTIPLE):
        raise ValueError(
            f"flash attention cannot tile seq_len {s} with "
            f"block_q={block_q}, block_k={block_k}: both must divide the "
            f"sequence, block_q must be a multiple of {BLOCK_Q_MULTIPLE} "
            f"and block_k a multiple of {BLOCK_K_MULTIPLE}")


def _use_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def _use_streaming(q, streaming: Optional[bool]) -> bool:
    if streaming is not None:
        return streaming
    _bh, s, d = q.shape
    return 2 * s * d * q.dtype.itemsize > RESIDENT_VMEM_BUDGET


def _causal_mask(s, qi, kb, block_q, block_k, window=None):
    """Mask logits where key position > query position (global indices)
    and, under a sliding `window`, where it is `window` or more behind:
    row i sees the keys i - window < j <= i."""
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + qi * block_q
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + kb * block_k
    keep = rows >= cols
    if window is not None:
        keep = keep & (rows - cols < window)
    return jnp.where(keep, s, NEG_INF)


def _block_live(causal, qi, kb, block_q, block_k, window=None):
    """Whether any (row, col) in this (q block, k block) pair is visible."""
    if not causal:
        return True
    live = (qi + 1) * block_q - 1 >= kb * block_k
    if window is not None:
        # the block's first row still reaches the block's last column
        live = live & (qi * block_q - ((kb + 1) * block_k - 1) < window)
    return live


def _first_kb(qi, block_q, block_k, window):
    """The first k block a q block's rows can see under `window`."""
    return jnp.maximum(qi * block_q - window + 1, 0) // block_k


def _qb_end(ki, block_q, block_k, window, num_qb):
    """One past the last q block that can see k block `ki` under
    `window`: its last column is seen by rows up to window - 1 later."""
    return jnp.minimum(
        num_qb, ((ki + 1) * block_k + window - 2) // block_q + 1)


def _online_step(q_scaled, k, v, carry, qi, kb, causal, block_q, block_k,
                 window=None):
    """One online-softmax accumulation step shared by both forward paths."""
    m, l, acc = carry
    s = jax.lax.dot_general(
        q_scaled, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # (bq, bk)
    if causal:
        s = _causal_mask(s, qi, kb, block_q, block_k, window)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    if window is None:
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
    else:
        # Under a window a row can meet a live block of which it sees
        # nothing before it has seen any key: its running max is still
        # -inf, and exp(-inf - -inf) would be NaN.  (Causal alone never
        # meets this: every row sees column 0 of the first block.)
        m_safe = jnp.where(m_new == NEG_INF, 0.0, m_new)
        alpha = jnp.exp(m - m_safe)
        p = jnp.exp(s - m_safe)
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * alpha + jnp.dot(p, v,
                                    preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _dq_step(q, k, v, do, lse, delta, sm_scale, qi, kb, causal, block_q,
             block_k, window=None):
    s = sm_scale * jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if causal:
        s = _causal_mask(s, qi, kb, block_q, block_k, window)
    p = jnp.exp(s - lse)                                 # (bq, bk)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    return sm_scale * jnp.dot(ds, k, preferred_element_type=jnp.float32)


def _dkv_step(q, k, v, do, lse, delta, sm_scale, qb, ki, causal, block_q,
              block_k, window=None):
    s = sm_scale * jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # (bq, bk)
    if causal:
        s = _causal_mask(s, qb, ki, block_q, block_k, window)
    p = jnp.exp(s - lse)
    dv = jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # (bk, d)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    dk = sm_scale * jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return dk, dv


# ---------------------------------------------------------------------------
# Resident path: K/V whole in VMEM; grid (bh, q_blocks); fori_loop over k.
# ---------------------------------------------------------------------------
def _fwd_kernel_res(q_ref, k_ref, v_ref, o_ref, lse_ref, *, sm_scale,
                    causal, block_q, block_k, seq_len, window=None):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * sm_scale          # (bq, d)
    bq, d = q.shape
    num_kb = seq_len // block_k
    if causal:
        num_kb = jnp.minimum(num_kb,
                             ((qi + 1) * block_q + block_k - 1) // block_k)

    def body(kb, carry):
        k = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        return _online_step(q, k, v, carry, qi, kb, causal, block_q,
                            block_k, window)

    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    first_kb = 0 if window is None else _first_kb(qi, block_q, block_k,
                                                  window)
    m, l, acc = jax.lax.fori_loop(first_kb, num_kb, body, (m0, l0, acc0))
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    # Layout (BH, 1, S): TPU block tiling needs the last two dims to be
    # (1, block) with both tile-divisible or dim-equal.
    lse_ref[0, 0, :] = (m + jnp.log(l))[:, 0]


def _dq_kernel_res(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, sm_scale, causal, block_q, block_k, seq_len,
                   window=None):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, 0, :][:, None]
    delta = delta_ref[0, 0, :][:, None]
    bq, d = q.shape
    num_kb = seq_len // block_k
    if causal:
        num_kb = jnp.minimum(num_kb,
                             ((qi + 1) * block_q + block_k - 1) // block_k)

    def body(kb, dq):
        k = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        return dq + _dq_step(q, k, v, do, lse, delta, sm_scale, qi, kb,
                             causal, block_q, block_k, window)

    first_kb = 0 if window is None else _first_kb(qi, block_q, block_k,
                                                  window)
    dq = jax.lax.fori_loop(first_kb, num_kb, body,
                           jnp.zeros((bq, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _dkv_kernel_res(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, sm_scale, causal, block_q, block_k,
                    seq_len, window=None):
    ki = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)                     # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    bk, d = k.shape
    num_qb = seq_len // block_q
    start_qb = (ki * block_k) // block_q if causal else 0
    if window is not None:
        num_qb = _qb_end(ki, block_q, block_k, window, num_qb)

    def body(qb, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(qb * block_q, block_q)][:, None]
        delta = delta_ref[0, 0, pl.ds(qb * block_q, block_q)][:, None]
        dk_i, dv_i = _dkv_step(q, k, v, do, lse, delta, sm_scale, qb, ki,
                               causal, block_q, block_k, window)
        return dk + dk_i, dv + dv_i

    z = jnp.zeros((bk, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(start_qb, num_qb, body, (z, z))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# Streaming path: 3D grid, contraction axis innermost, scratch carries.
# ---------------------------------------------------------------------------
def _fwd_kernel_str(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                    acc_scr, *, sm_scale, causal, block_q, block_k,
                    window=None):
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    last_kb = pl.num_programs(2) - 1

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(_block_live(causal, qi, kb, block_q, block_k, window))
    def _step():
        q = q_ref[0].astype(jnp.float32) * sm_scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        m, l, acc = _online_step(q, k, v,
                                 (m_scr[:], l_scr[:], acc_scr[:]),
                                 qi, kb, causal, block_q, block_k, window)
        m_scr[:], l_scr[:], acc_scr[:] = m, l, acc

    @pl.when(kb == last_kb)
    def _finish():
        l = l_scr[:]
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0, 0, :] = (m_scr[:] + jnp.log(l))[:, 0]


def _dq_kernel_str(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, sm_scale, causal, block_q, block_k,
                   window=None):
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    last_kb = pl.num_programs(2) - 1

    @pl.when(kb == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(_block_live(causal, qi, kb, block_q, block_k, window))
    def _step():
        dq_scr[:] = dq_scr[:] + _dq_step(
            q_ref[0].astype(jnp.float32),
            k_ref[0].astype(jnp.float32),
            v_ref[0].astype(jnp.float32),
            do_ref[0].astype(jnp.float32),
            lse_ref[0, 0, :][:, None], delta_ref[0, 0, :][:, None],
            sm_scale, qi, kb, causal, block_q, block_k, window)

    @pl.when(kb == last_kb)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel_str(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale, causal,
                    block_q, block_k, window=None):
    ki = pl.program_id(1)
    qb = pl.program_id(2)
    last_qb = pl.num_programs(2) - 1

    @pl.when(qb == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(_block_live(causal, qb, ki, block_q, block_k, window))
    def _step():
        dk_i, dv_i = _dkv_step(
            q_ref[0].astype(jnp.float32),
            k_ref[0].astype(jnp.float32),
            v_ref[0].astype(jnp.float32),
            do_ref[0].astype(jnp.float32),
            lse_ref[0, 0, :][:, None], delta_ref[0, 0, :][:, None],
            sm_scale, qb, ki, causal, block_q, block_k, window)
        dk_scr[:] = dk_scr[:] + dk_i
        dv_scr[:] = dv_scr[:] + dv_i

    @pl.when(qb == last_qb)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call builders
# ---------------------------------------------------------------------------
def _q_spec(block_q, d):
    return pl.BlockSpec((1, block_q, d), lambda b, i, *_: (b, i, 0))


def _lse_spec(block_q):
    return pl.BlockSpec((1, 1, block_q), lambda b, i, *_: (b, 0, i))


def _windowed(window, kind):
    """The extra keywords of a windowed call, for the kernel and for
    `pallas_call`: the window, and a name that says the kind of call and
    the window (`flash_fwd_w2048`), which is how a device trace tells a
    sliding layer's calls from a full layer's.  None for `window=None`,
    which leaves those calls exactly as they were."""
    if window is None:
        return {}, {}
    return {"window": window}, {"name": f"flash_{kind}_w{window}"}


def _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret, streaming,
         window=None):
    bh, s, d = q.shape
    kw, named = _windowed(window, "fwd")
    out_shape = [jax.ShapeDtypeStruct((bh, s, d), q.dtype),
                 jax.ShapeDtypeStruct((bh, 1, s), jnp.float32)]
    if streaming:
        return pl.pallas_call(
            functools.partial(_fwd_kernel_str, sm_scale=sm_scale,
                              causal=causal, block_q=block_q,
                              block_k=block_k, **kw),
            grid=(bh, s // block_q, s // block_k),
            in_specs=[
                _q_spec(block_q, d),
                pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            ],
            out_specs=[_q_spec(block_q, d), _lse_spec(block_q)],
            out_shape=out_shape,
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
                pltpu.VMEM((block_q, 1), jnp.float32),   # running sum l
                pltpu.VMEM((block_q, d), jnp.float32),   # accumulator
            ],
            interpret=interpret, **named,
        )(q, k, v)
    kv_spec = pl.BlockSpec((1, s, d), lambda b, i: (b, 0, 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel_res, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_len=s, **kw),
        grid=(bh, s // block_q),
        in_specs=[_q_spec(block_q, d), kv_spec, kv_spec],
        out_specs=[_q_spec(block_q, d), _lse_spec(block_q)],
        out_shape=out_shape,
        interpret=interpret, **named,
    )(q, k, v)


def _bwd(sm_scale, causal, block_q, block_k, interpret, streaming,
         residuals, g, window=None):
    q, k, v, o, lse = residuals
    do = g
    bh, s, d = q.shape
    kw, dq_named = _windowed(window, "dq")
    dkv_named = _windowed(window, "dkv")[1]
    # delta_i = rowsum(dO_i * O_i): tiny elementwise pass, XLA fuses it.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, None, :]                 # (bh, 1, s)
    if streaming:
        dq = pl.pallas_call(
            functools.partial(_dq_kernel_str, sm_scale=sm_scale,
                              causal=causal, block_q=block_q,
                              block_k=block_k, **kw),
            grid=(bh, s // block_q, s // block_k),
            in_specs=[
                _q_spec(block_q, d),
                pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
                _q_spec(block_q, d),
                _lse_spec(block_q), _lse_spec(block_q),
            ],
            out_specs=_q_spec(block_q, d),
            out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
            interpret=interpret, **dq_named,
        )(q, k, v, do, lse, delta)
        kb_spec = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0))
        qs_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, j, 0))
        ls_spec = pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, j))
        dk, dv = pl.pallas_call(
            functools.partial(_dkv_kernel_str, sm_scale=sm_scale,
                              causal=causal, block_q=block_q,
                              block_k=block_k, **kw),
            grid=(bh, s // block_k, s // block_q),
            in_specs=[qs_spec, kb_spec, kb_spec, qs_spec, ls_spec, ls_spec],
            out_specs=[kb_spec, kb_spec],
            out_shape=[jax.ShapeDtypeStruct((bh, s, d), k.dtype),
                       jax.ShapeDtypeStruct((bh, s, d), v.dtype)],
            scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                            pltpu.VMEM((block_k, d), jnp.float32)],
            interpret=interpret, **dkv_named,
        )(q, k, v, do, lse, delta)
        return dq, dk, dv

    full_spec2 = pl.BlockSpec((1, s, d), lambda b, i: (b, 0, 0))
    full_lse2 = pl.BlockSpec((1, 1, s), lambda b, i: (b, 0, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel_res, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_len=s, **kw),
        grid=(bh, s // block_q),
        in_specs=[_q_spec(block_q, d), full_spec2, full_spec2,
                  _q_spec(block_q, d), _lse_spec(block_q),
                  _lse_spec(block_q)],
        out_specs=_q_spec(block_q, d),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        interpret=interpret, **dq_named,
    )(q, k, v, do, lse, delta)
    kb2 = pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel_res, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_len=s, **kw),
        grid=(bh, s // block_k),
        in_specs=[full_spec2, kb2, kb2, full_spec2, full_lse2, full_lse2],
        out_specs=[kb2, kb2],
        out_shape=[jax.ShapeDtypeStruct((bh, s, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, s, d), v.dtype)],
        interpret=interpret, **dkv_named,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False, sm_scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None,
                    streaming: Optional[bool] = None,
                    window: Optional[int] = None) -> jax.Array:
    """Blockwise (flash) attention.  q, k, v: [BH, S, D] -> [BH, S, D].

    `window` (causal only) is a sliding window: row i attends to the keys
    i - window < j <= i, and the blocks no row of a tile can see are
    skipped in all three kernels, as the blocks above the diagonal are.
    `window=None` is the plain kernel, unchanged.

    sm_scale defaults to 1/sqrt(D).  interpret=None auto-selects the
    Pallas interpreter off-TPU so tests run on the CPU mesh.
    streaming=None auto-selects: K/V-resident kernels while 2*S*D fits the
    VMEM budget (fastest — K/V fetched once per batch*head), 3D-grid
    streaming kernels beyond (O(block*D) VMEM at any S).
    """
    out, _ = _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k,
                        interpret, streaming, window)
    return out


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret,
               streaming, window=None):
    bh, s, d = q.shape
    check_blocks(s, block_q, block_k)
    if window is not None and (not causal or window < 1):
        raise ValueError(f"window={window} needs causal=True and at least "
                         f"one key a row")
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    out, lse = _fwd(q, k, v, scale, causal, block_q, block_k,
                    _use_interpret(interpret), _use_streaming(q, streaming),
                    window)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, sm_scale, block_q, block_k, interpret, streaming,
               window, residuals, g):
    d = residuals[0].shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    return _bwd(scale, causal, block_q, block_k, _use_interpret(interpret),
                _use_streaming(residuals[0], streaming), residuals, g,
                window)


flash_attention.defvjp(_flash_fwd, _flash_bwd)
