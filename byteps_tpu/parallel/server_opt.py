"""Server-resident optimizer training: push gradients, pull *parameters*.

The sum-only PS contract (BytePS §1C) makes every worker pull the full
gradient sum and run the full optimizer redundantly N times, holding N
copies of optimizer state.  This trainer flips the key's publish stage
into parameter mode (CMD_OPT, arXiv 2004.13336 "Automatic Cross-Replica
Sharding of Weight Update"): each partition's ring owner runs the
optimizer step ONCE on the merged sum and publishes the post-update
parameters — workers push gradients exactly as before (codec/EF law
untouched) and adopt pulled parameters instead of sums, skipping the
local optax step entirely.  Partitions spread across the PS ring, so the
weight update is sharded server-by-server for free — the ZeRO-flavored
placement the ROADMAP names.

Two modes, one trainer:

- ``mode="server"`` — the new plane.  ``arm_server_opt`` declares the
  epoch-versioned optimizer config and seeds the initial params; every
  ``step(grads)`` is one push_pull whose pull IS the updated params.
  Per-worker optimizer-state bytes: ~0 (the slots live in the server's
  ``KeyState``; ``bps.get_server_stats()["opt_slot_bytes"]`` is where
  they went).
- ``mode="local"`` — the worker-local optax baseline: pull the sum, run
  the IDENTICAL optax optimizer here.  This is the reference trajectory
  the equivalence law pins: with fixed membership the two modes match
  f32-exactly, round by round, including under compression with EF
  (tests/test_server_opt.py; run the baseline under
  ``jax.disable_jit()`` for the bitwise comparison — eager optax and the
  server's update stage share every f32 op, while jitted XLA's traced
  ``pow`` in Adam's bias correction may differ by ~1 ULP).

The default mode comes from ``BYTEPS_TPU_SERVER_OPT`` (1 = server,
otherwise local), so a launch config can flip a job without touching
trainer code.

Failover: drain and scale-up migrate the optimizer slots byte-equal
(CMD_MIGRATE trailer).  After a SIGKILL failover hands a key range to a
fresh owner, the session re-declares the config and re-seeds params from
this trainer's adopted view (``params_fn``): stateless SGD recovers
bit-identically; momentum/Adam slots cannot be rebuilt from workers and
restart zeroed — see docs/server-optimizer.md "Failover".
"""

from __future__ import annotations

import os
from typing import Any, Optional

import numpy as np

PyTree = Any

#: optimizer-name -> required hyperparams (filled with optax defaults so
#: the canonical kwargs string the server parses is always explicit).
_DEFAULTS = {
    "sgd": {"lr": 0.01},
    "momentum": {"lr": 0.01, "mu": 0.9},
    "adam": {"lr": 0.001, "b1": 0.9, "b2": 0.999, "eps": 1e-8},
}


def _canonical_opt_kwargs(opt_kwargs: dict, grad_scale: float) -> dict:
    kw = {str(k): v for k, v in dict(opt_kwargs).items()}
    name = str(kw.pop("opt", "sgd"))
    if name not in _DEFAULTS:
        raise ValueError(
            f"server-resident optimizer {name!r} not supported "
            f"(have: {sorted(_DEFAULTS)})")
    full = dict(_DEFAULTS[name])
    for k, v in kw.items():
        if k not in full:
            raise ValueError(
                f"unknown hyperparam {k!r} for server optimizer "
                f"{name!r} (have: {sorted(full)})")
        full[k] = float(v)
    full = {k: float(v) for k, v in full.items()}
    full["opt"] = name
    if float(grad_scale) != 1.0:
        full["gscale"] = float(grad_scale)
    return full


class ServerOptTrainer:
    """Sync training whose optimizer step runs on the PS tier.

    Usage::

        trainer = ServerOptTrainer(session, params,
                                   {"opt": "adam", "lr": 1e-3},
                                   name="model", grad_scale=1.0 / N)
        for batch in data:
            grads = grad_fn(trainer.params, batch)
            trainer.step(grads)      # push grads, adopt updated params

    ``grad_scale`` is the factor applied to the merged gradient SUM
    before the optimizer consumes it (1/N for data-parallel averaging;
    default 1.0 = raw-sum semantics).  Applied identically in both
    modes, so local-vs-server trajectories stay comparable.
    """

    def __init__(self, session, params: PyTree, opt_kwargs: dict,
                 name: str = "serveropt",
                 declared_key: Optional[int] = None,
                 mode: Optional[str] = None,
                 grad_scale: float = 1.0,
                 hierarchy=None):
        import jax

        if getattr(session, "server_async", False):
            raise RuntimeError(
                "ServerOptTrainer needs sync rounds; against an async "
                "server there is no merge boundary for the update stage "
                "(use AsyncPSTrainer there)")
        if mode is None:
            mode = ("server"
                    if os.environ.get("BYTEPS_TPU_SERVER_OPT", "0") == "1"
                    else "local")
        if mode not in ("server", "local"):
            raise ValueError(f"mode must be 'server' or 'local', "
                             f"got {mode!r}")
        self._session = session
        self.mode = mode
        # Hierarchical reduction (BYTEPS_TPU_HIERARCHY=1): gradients
        # slice-reduce in-graph, the slice leader pushes the slice sum,
        # and the pulled value — post-update PARAMETERS in server mode —
        # broadcasts back to the slice.  grad_scale semantics are
        # untouched: the server scales the total sum (sum of slice
        # sums == sum over every chip).
        if hierarchy is None:
            from .hierarchy import maybe_reducer
            hierarchy = maybe_reducer(session)
        self._hier = hierarchy
        self._grad_scale = float(grad_scale)
        self._kw = _canonical_opt_kwargs(opt_kwargs, grad_scale)
        self._treedef = jax.tree.structure(params)
        leaves = jax.tree.leaves(params)
        self._shapes = [np.shape(l) for l in leaves]
        self._sizes = [int(np.size(l)) for l in leaves]
        self._dtypes = [np.asarray(l).dtype for l in leaves]
        if declared_key is None:
            from ..core.native import get_core
            declared_key = get_core().declare_tensor(f"ServerOpt.{name}")
        self._key = declared_key
        self._flat = self._flatten(params)
        self._rounds = 0
        if mode == "server":
            # Declare + seed; params_fn hands the session our CURRENT
            # adopted view as the failover re-seed source.  Always
            # effective from round 0: the trainer arms BEFORE its first
            # push, so every pull it ever adopts is parameters — a later
            # effective round would hand back pre-switch gradient SUMS
            # that step() would silently adopt as weights (deferred
            # switches belong to session-level propose_opt, where the
            # caller owns the pull interpretation).
            self._opt_state = None
            session.arm_server_opt(
                declared_key, self._flat, self._kw,
                params_fn=lambda: self._flat,
                effective_round=0)
        else:
            # Worker-local optax baseline — the trajectory the server
            # mode must match f32-exactly.
            self._opt = self._build_optax()
            import jax.numpy as jnp
            self._opt_state = self._opt.init(jnp.asarray(self._flat))

    def _build_optax(self):
        import optax
        kw = self._kw
        name = kw["opt"]
        if name == "sgd":
            return optax.sgd(kw["lr"])
        if name == "momentum":
            return optax.sgd(kw["lr"], momentum=kw["mu"])
        return optax.adam(kw["lr"], b1=kw["b1"], b2=kw["b2"],
                          eps=kw["eps"])

    def _flatten(self, tree: PyTree) -> np.ndarray:
        import jax

        leaves = jax.tree.leaves(tree)
        return np.concatenate(
            [np.asarray(l, np.float32).ravel() for l in leaves])

    def _unflatten(self, flat: np.ndarray) -> PyTree:
        import jax

        out, off = [], 0
        for shape, size, dtype in zip(self._shapes, self._sizes,
                                      self._dtypes):
            out.append(flat[off:off + size].reshape(shape).astype(dtype))
            off += size
        return jax.tree.unflatten(self._treedef, out)

    @property
    def params(self) -> PyTree:
        """The current parameters, as the original pytree."""
        return self._unflatten(self._flat)

    @property
    def rounds(self) -> int:
        return self._rounds

    def opt_state_bytes(self) -> int:
        """Optimizer-state bytes THIS WORKER holds — the redundancy the
        server mode eliminates."""
        if self.mode == "server":
            return 0
        import jax

        return sum(int(np.asarray(l).nbytes)
                   for l in jax.tree.leaves(self._opt_state))

    def step(self, grads: PyTree, timeout: Optional[float] = 300.0
             ) -> PyTree:
        """Push one round's gradients; adopt the post-update params.

        Server mode: the pull IS the updated parameters (the server ran
        the step once, on the key's owner).  Local mode: the pull is the
        gradient sum and the identical optax step runs here."""
        flat_g = self._flatten(grads)
        if self._hier is not None:
            pulled = np.asarray(
                self._hier.push_pull_flat(self._key, flat_g,
                                          timeout=timeout),
                np.float32).ravel()
        else:
            handle = self._session.push_pull_async(self._key, flat_g)
            pulled = np.asarray(handle.wait(timeout), np.float32).ravel()
        if self.mode == "server":
            self._flat = pulled
        else:
            import jax.numpy as jnp
            import optax

            from ..common import devprof

            g = pulled
            if self._grad_scale != 1.0:
                # One weak-f32 scalar multiply, mirrored exactly by the
                # server's gscale leg.
                g = np.float32(self._grad_scale) * g
            # Device-plane hook (common/devprof.py): the local-mode
            # optimizer update is this trainer's on-device work (server
            # mode runs it on the PS tier, so there is nothing to
            # time).  np.asarray below already synchronizes, so the
            # step_end token needs no extra block.
            tok = devprof.step_begin()
            updates, self._opt_state = self._opt.update(
                jnp.asarray(g), self._opt_state,
                jnp.asarray(self._flat))
            self._flat = np.asarray(
                optax.apply_updates(jnp.asarray(self._flat), updates),
                np.float32)
            devprof.step_end(tok)
        self._rounds += 1
        return self.params

    def server_docs(self) -> dict:
        """The authoritative per-partition opt docs (param_version,
        slots_crc, ...) — empty in local mode."""
        if self.mode != "server":
            return {}
        return self._session.fetch_opt_docs(self._key)
