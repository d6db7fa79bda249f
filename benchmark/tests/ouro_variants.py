"""The ouro program broken in eight ways, each of which the cell's
`correct` has to catch (ISSUE 64).  A variant is a context manager over a
family: inside it `family.loss` and what `family.reference_loss` asks the
program for (its walks, its exit distribution, its head's rows) are the
broken program's; the reference stays what it is.

Two are built by a field of the model's configuration; six need the
program's code patched, which is done here and nowhere in the program.
Used by the tests at tiny widths (`tests/test_ouro_variants.py`) and by
`tools/reference_check.py` at the published widths on the chip.
"""

import contextlib
import dataclasses
import functools
from unittest import mock

import jax
import jax.numpy as jnp
from jax import lax

from byteps_tpu.models import afmoe, ouro


@contextlib.contextmanager
def _option(family, **changed):
    kept = family.cfg
    family.cfg = dataclasses.replace(kept, **changed)
    try:
        yield family
    finally:
        family.cfg = kept


def three_walks(family):
    """The loop one walk short: the last but one takes what is left."""
    return _option(family, total_ut_steps=family.cfg.total_ut_steps - 1)


def entropy_left_out(family):
    """The expected cross-entropy alone: beta 0."""
    return _option(family, exit_entropy_beta=0.0)


def scan_of_walks(params, tokens, cfg, hand_on_normed=True):
    """`ouro.walks` as a `lax.scan` over the walks round
    `afmoe.run_layers`: the form that was measured and not chosen (PR 64;
    `tests/test_tpu_aot_compile.py` keeps its compiled bytes), the same
    numbers as the program's.  With `hand_on_normed` False walk t + 1
    reads `u_t`, what the layers left, and not `h_t = rms(u_t)`."""
    def walk(x, _):
        u, _ = afmoe.run_layers(params, x, cfg, layer=ouro._layer)
        with jax.named_scope("ouro.exit"):
            h = ouro._rms_norm(u, params["final_ln"], None,
                               eps=cfg.rms_norm_eps)
            return (h if hand_on_normed else u), (h,
                                                  ouro.exit_gate(params, h))
    _, out = lax.scan(walk, ouro._embed(params, tokens, cfg), None,
                      length=cfg.total_ut_steps)
    return out


@contextlib.contextmanager
def next_walk_reads_the_unnormed_state(family):
    """Walk t + 1 reads `u_t` and not `h_t`; the head and the gate still
    read `h_t`."""
    with mock.patch.object(ouro, "walks", functools.partial(
            scan_of_walks, hand_on_normed=False)):
        yield family


@contextlib.contextmanager
def post_norms_left_out(family):
    """A sub-layer's OUTPUT is added as it is: `x = x + f(rms(x))`, the
    layer every other decoder of the package has."""
    layer, norm = ouro._layer, ouro._rms_norm

    def bare(x, lp, *args, **kwargs):
        return layer(x, {**lp, "post_attn_ln": None, "post_mlp_ln": None},
                     *args, **kwargs)

    def norm_or_not(x, scale, bias, eps):
        return x if scale is None else norm(x, scale, bias, eps=eps)
    with mock.patch.object(ouro, "_layer", bare), \
            mock.patch.object(ouro, "_rms_norm", norm_or_not):
        yield family


@contextlib.contextmanager
def last_step_uses_its_gate(family):
    """`p_T = lam_T` times what is left: `p` no longer sums to 1."""
    def exit_distribution(lam):
        lam = lam.astype(jnp.float32)
        reached = jnp.concatenate([jnp.ones_like(lam[:1]),
                                   jnp.cumprod(1.0 - lam[:-1], axis=0)])
        return reached * lam
    with mock.patch.object(ouro, "exit_distribution", exit_distribution):
        yield family


@contextlib.contextmanager
def weights_held_constant(family):
    """`p` a constant of the task term: the gate learns from the entropy
    alone."""
    def loss_fn(params, batch, cfg):
        tokens, targets = batch
        h, lam = ouro.walks(params, tokens, cfg)
        p = ouro.exit_distribution(lam)
        task = ouro.weighted_nll_sum(params, h, targets,
                                     lax.stop_gradient(p), cfg)
        return (task / targets.size
                - cfg.exit_entropy_beta * ouro.exit_entropy(p).mean())
    with mock.patch.object(ouro, "loss_fn", loss_fn):
        yield family


@contextlib.contextmanager
def gate_in_bfloat16(family):
    """The gate's product, sum and sigmoid in bfloat16."""
    def exit_gate(params, h):
        half = jnp.bfloat16
        gate = params["exit_gate"].astype(half)
        logit = (h.astype(half) * gate[:-1]).sum(-1)
        return jax.nn.sigmoid(logit + gate[-1]).astype(jnp.float32)
    with mock.patch.object(ouro, "exit_gate", exit_gate):
        yield family


@contextlib.contextmanager
def logits_in_bfloat16(family):
    """The head's logits, their log-sum-exp and a row's NLL in bfloat16,
    streamed in the program's chunks."""
    half = jnp.bfloat16

    def nll(x, head, targets):
        logits = jnp.einsum("...d,vd->...v", x.astype(half),
                            head.astype(half), preferred_element_type=half)
        tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)
        return (jax.nn.logsumexp(logits, axis=-1) - tgt[..., 0]).astype(
            jnp.float32)

    def fused_nll_sum(x, head, targets, chunk_rows, weights):
        D = x.shape[-1]
        chunks = (-1, min(chunk_rows, targets.size))

        @jax.checkpoint
        def chunk(xc, tc, wc):
            return (nll(xc, head, tc) * wc).sum()
        total, _ = lax.scan(
            lambda acc, args: (acc + chunk(*args), None),
            jnp.zeros((), jnp.float32),
            (x.reshape(*chunks, D), targets.reshape(chunks),
             weights.reshape(chunks)))
        return total

    def head_logits(x, head):
        return jnp.einsum("...d,vd->...v", x.astype(half), head.astype(half),
                          preferred_element_type=half)
    with mock.patch.object(ouro, "fused_nll_sum", fused_nll_sum), \
            mock.patch.object(afmoe, "head_logits", head_logits):
        yield family


VARIANTS = {
    "three_walks": three_walks,
    "next_walk_reads_the_unnormed_state": next_walk_reads_the_unnormed_state,
    "post_norms_left_out": post_norms_left_out,
    "last_step_uses_its_gate": last_step_uses_its_gate,
    "weights_held_constant": weights_held_constant,
    "entropy_left_out": entropy_left_out,
    "gate_in_bfloat16": gate_in_bfloat16,
    "logits_in_bfloat16": logits_in_bfloat16,
}
