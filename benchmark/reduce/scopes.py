"""The traced step laid over the program's own map of it.

The trace names the instructions that ran; the program says where each
came from: `bps.get_step_scopes()` gives, for every instruction of the
compiled step, the scope the model opened around it (`mellum.moe/grouped`:
a `jax.named_scope`, its parents in the path) and the pass it runs in
(`forward`, `backward`, `recompute`, `optimizer`, `other`).  Joined by the
instruction's name over chip 0's instructions, each with its OWN time
(`intervals.self_times`: a `while` keeps what its body's instructions do
not cover, so nothing is counted twice and nothing is lost), that is time
by pass and by scope, with the devices' own clock, and the passes
partition the chip's busy time.  (`xplane.leaves` would do but for two
things the chip showed, PR 39: it drops an instruction that the next one
overlaps by a few nanoseconds, and the loops' own time, 4% of GPT-2's
step, would be in no pass.)

One join a traced run: the readers under `benchmark/layer_metrics/` are
one module a metric, and each asks `join(ctx)`; the join is kept by the
context's identity, the map by the program.  A program without the map
(an older one, or a job that builds no `build_train_step`) gives None,
and every reader then reads nothing.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re

from benchmark.reduce import intervals, xplane

PASSES = ("forward", "backward", "recompute", "optimizer", "other")


@dataclasses.dataclass(frozen=True)
class Join:
    """Nanoseconds over the whole traced window, chip 0."""
    by_pass: dict       # pass -> ns; the five sum to the busy time
    by_scope: dict      # the whole scope path -> ns (its own, no child's)
    lent: dict          # scope path -> ns of `by_scope` in instructions
                        # whose scope the map LENT them (the compiler's
                        # own kernels, `devprof.parse_step_scopes`)
    scoped_ns: int      # in instructions the map holds AND places, by
                        # their own path or lent
    n_steps: int
    unplaced: dict      # instruction -> ns, those not in `scoped_ns`

    @property
    def busy_ns(self) -> int:
        """Own times sum to the union of every instruction's interval."""
        return sum(self.by_pass.values())

    def under(self, *prefixes: str) -> int:
        """Time in the scopes that are, or lie under, a path that ends a
        component at one of `prefixes` (`"afmoe.moe"` takes
        `afmoe.moe/route` and not `afmoe.moe2`)."""
        return sum(ns for scope, ns in self.by_scope.items()
                   if any(scope == p or scope.startswith(p + "/")
                          for p in prefixes))

    def tops(self) -> set:
        """The first component of every scope that took time."""
        return {scope.split("/", 1)[0] for scope in self.by_scope if scope}

    def ms(self, ns: int) -> float:
        return ns / self.n_steps / 1e6


def reduce(own: dict, scopes: dict, n_steps: int) -> Join:
    """`own` is `{instruction: its own ns}`; `scopes` the program's map.
    An instruction the map does not hold runs in pass "other" with no
    scope."""
    by_pass = dict.fromkeys(PASSES, 0)
    by_scope: dict = {}
    lent: dict = {}
    unplaced: dict = {}
    scoped = 0
    for name, ns in own.items():
        entry = scopes.get(name)
        by_pass[entry["pass"] if entry else "other"] += ns
        if entry and (entry["scope"] or entry["pass"] == "optimizer"):
            by_scope[entry["scope"]] = by_scope.get(entry["scope"], 0) + ns
            if entry.get("lent"):
                lent[entry["scope"]] = lent.get(entry["scope"], 0) + ns
            scoped += ns
        else:
            unplaced[name] = ns
    return Join(by_pass, by_scope, lent, scoped, n_steps, unplaced)


def _write(j: Join, scopes: dict, path: str, top: int = 40) -> None:
    """The join as a file beside the trace, for whoever reads the run
    afterwards: milliseconds a step by pass and by scope, how much of a
    scope's time is in instructions the map lent it (and so how much of
    `step.scoped_share` is), and the instructions no scope holds, longest
    first."""
    rest = sorted(j.unplaced.items(), key=lambda kv: -kv[1])[:top]
    with open(path, "w") as f:
        json.dump({
            "ms_per_step_by_pass": {k: j.ms(v) for k, v in j.by_pass.items()},
            "ms_per_step_by_scope": {
                k: j.ms(v) for k, v in
                sorted(j.by_scope.items(), key=lambda kv: -kv[1])},
            "lent_ms_per_step_by_scope": {
                k: j.ms(v) for k, v in
                sorted(j.lent.items(), key=lambda kv: -kv[1])},
            "busy_ms_per_step": j.ms(j.busy_ns),
            "scoped_share": 100.0 * j.scoped_ns / max(j.busy_ns, 1),
            "lent_share": 100.0 * sum(j.lent.values()) / max(j.busy_ns, 1),
            "unplaced_ms_per_step": [
                [name, j.ms(ns), scopes.get(name, {}).get("op_name")]
                for name, ns in rest]}, f, indent=1)


_kept = None        # (the context, its join)


def join(ctx):
    """The `Join` of a traced run's context, or None where the program
    gives no map or chip 0 ran nothing."""
    global _kept
    if _kept is not None and _kept[0] is ctx:
        return _kept[1]
    import byteps_tpu as bps
    get = getattr(bps, "get_step_scopes", None)
    scopes = get() if get is not None else None
    found = None
    if scopes and ctx.ops(0):
        own = intervals.self_times(
            (xplane.op_name(n), s, e) for n, s, e in ctx.ops(0))
        found = reduce(own, scopes, ctx.n_steps)
        _write(found, scopes, os.path.join(ctx.dir, "scopes.json"))
    _kept = (ctx, found)
    return found


def pass_ms(ctx, which: str):
    j = join(ctx)
    return None if j is None else j.ms(j.by_pass[which])


def scope_ms(ctx, top: str, children=None):
    """Milliseconds a step under the scopes whose first component the
    pattern `top` finds (`r"\\.moe$"`: any family's expert layer); with
    `children`, under those children of them alone.  None where no such
    scope took time: a cell without the layer."""
    j = join(ctx)
    if j is None:
        return None
    tops = [t for t in j.tops() if re.search(top, t)]
    if not tops:
        return None
    if children:
        tops = [f"{t}/{c}" for t in tops for c in children]
    return j.ms(j.under(*tops))


def scoped_share(ctx):
    j = join(ctx)
    return None if j is None or not j.busy_ns else (
        100.0 * j.scoped_ns / j.busy_ns)
