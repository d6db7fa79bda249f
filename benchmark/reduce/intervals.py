"""Arithmetic on time intervals, shared by the readers of per-layer
metrics.  An interval is a `(start, end)` pair in one clock's units; a
list of them need not be sorted or disjoint unless a function says so."""

from __future__ import annotations


def union(intervals) -> list:
    """The same covered time as disjoint, sorted intervals."""
    out = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def total(disjoint) -> float:
    return sum(end - start for start, end in disjoint)


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(disjoint, lo, hi) -> list:
    """What `[lo, hi]` has outside the disjoint, sorted intervals."""
    out, at = [], lo
    for start, end in clip(disjoint, lo, hi):
        if start > at:
            out.append((at, start))
        at = max(at, end)
    if hi > at:
        out.append((at, hi))
    return out


def overlap(a_disjoint, b_disjoint) -> float:
    """Time covered by both of two disjoint, sorted lists."""
    i = j = 0
    both = 0
    while i < len(a_disjoint) and j < len(b_disjoint):
        lo = max(a_disjoint[i][0], b_disjoint[j][0])
        hi = min(a_disjoint[i][1], b_disjoint[j][1])
        if hi > lo:
            both += hi - lo
        if a_disjoint[i][1] <= b_disjoint[j][1]:
            i += 1
        else:
            j += 1
    return both


def exposed(these, others) -> float:
    """Time covered by `these` during which none of `others` runs: for
    collectives against compute, the part of the exchange that the
    backward pass does not hide."""
    mine = union(these)
    return total(mine) - overlap(mine, union(others))


def self_times(events) -> dict:
    """Per name, the time its events cover minus what events nested
    inside them cover.  A device line nests: a `while` holds the ops of
    its body, and summing durations would count the body twice.
    `events` are `(name, start, end)` on one line, where any two either
    nest or do not overlap."""
    out: dict = {}
    stack: list = []          # [name, end, time covered by children]

    def close(until):
        while stack and stack[-1][1] <= until:
            name, end, covered, start = stack.pop()
            out[name] = out.get(name, 0) + (end - start) - covered
            if stack:
                stack[-1][2] += end - start

    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack and end > stack[-1][1]:
            end = stack[-1][1]      # a partial overlap: cut to the parent
        stack.append([name, end, 0, start])
    close(float("inf"))
    return out


def attribute(gap_list, spans, default: str) -> dict:
    """Seconds (or whatever unit) of `gap_list` by the name of the span
    that covers them.  `spans` are `(name, start, end)`; where several
    cover a moment the one that started last wins (the innermost); time
    no span covers goes to `default`."""
    out: dict = {}
    spans = sorted(spans, key=lambda s: s[1])
    for lo, hi in gap_list:
        cuts = sorted({lo, hi, *(t for _, s, e in spans
                                 for t in (s, e) if lo < t < hi)})
        for a, b in zip(cuts, cuts[1:]):
            name = default
            for n, s, e in spans:
                if s <= a and e >= b:
                    name = n        # sorted by start: the last one wins
            out[name] = out.get(name, 0) + (b - a)
    return out
