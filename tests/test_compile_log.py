"""The compile log (`byteps_tpu/utils/compile_cache.py` `CompileLog`,
`bps.get_compile_log()`): one record an outermost span of a stage, the
cache's answer on a COMPILE, where set-up ends, and recompiles after it.
Tiny jits on the CPU; each test has a log of its own behind the
process's one set of listeners."""

import logging
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import byteps_tpu as bps
from byteps_tpu.common import devprof
from byteps_tpu.common.logging import get_logger
from byteps_tpu.utils import compile_cache

from test_step_scopes import cache_in  # noqa: F401  (fixture reuse)


@pytest.fixture
def log(monkeypatch):
    compile_cache.install()         # the listeners and the gauges
    fresh = compile_cache.CompileLog()
    monkeypatch.setattr(compile_cache, "LOG", fresh)
    return fresh


def _records(log, kind, name=None):
    return [r for r in log.snapshot()["records"] if r["kind"] == kind
            and (name is None or r["name"] == name)]


def _recompiles():
    return bps.get_metrics()["bps_recompiles_total"]


def _tiny_step():
    def loss(w, x):
        return jnp.sum(jnp.tanh(x @ w) ** 2)
    mesh = bps.make_mesh(devices=jax.devices()[:1])
    opt = bps.DistributedOptimizer(optax.sgd(0.1))
    w = jnp.ones((16, 16))
    return (bps.build_train_step(loss, opt, mesh, donate=False),
            (w, opt.init(w), np.ones((4, 16), np.float32)))


@pytest.fixture
def settled(log, monkeypatch):
    """A step that has run until set-up ended: `(step, its arguments)`."""
    monkeypatch.setattr(devprof, "_step_record", None)
    monkeypatch.setattr(devprof, "_step_scopes", None)
    step, args = _tiny_step()
    step(*args)
    assert log.steady_at is None        # the first call compiled
    before = time.time()
    step(*args)
    assert before <= log.steady_at <= time.time()
    return step, args


def test_nested_traces_make_one_record(log):
    @jax.jit
    def inner(x):
        return jnp.tanh(x) * 2

    @jax.jit
    def outer(x):
        return inner(x) + inner(x * 3)

    outer(np.ones(3, np.float32))
    trace, = _records(log, "TRACE", "outer")
    assert trace["nested"] > 0
    assert not _records(log, "TRACE", "inner")
    lower, = _records(log, "LOWER", "jit(outer)")
    made, = _records(log, "COMPILE", "jit(outer)")
    assert made["cache"] == "uncached"      # the tests keep no cache
    assert trace["end"] <= lower["start"] and lower["end"] <= made["start"]
    # both clocks: the tracer's is `time.monotonic` in microseconds
    now_us = time.monotonic_ns() / 1e3
    assert 0 <= now_us - made["end_us"] < 60e6
    assert made["end_us"] - made["start_us"] == pytest.approx(
        (made["end"] - made["start"]) * 1e6, abs=2)
    assert made["thread"] == threading.get_ident()
    totals = log.totals()
    assert totals["by_kind"]["TRACE"]["nested"] >= trace["nested"]
    assert totals["by_cache"]["uncached"] >= 1
    assert totals["by_kind"]["COMPILE"]["seconds"] >= (
        made["end"] - made["start"])


def test_a_compile_reads_miss_then_hit_then_small(log, cache_in):
    def f(x):
        return jnp.cos(x) + 41

    x = np.ones(5, np.float32)
    jax.jit(f)(x)
    jax.clear_caches()
    jax.jit(f)(x)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 3600.0)
    jax.jit(lambda x: jnp.sin(x) - 43)(x)
    first, second, third = _records(log, "COMPILE")
    assert first["cache"] == "miss" and "retrieval_s" not in first
    assert second["cache"] == "hit" and second["retrieval_s"] > 0
    assert third["cache"] == "small"
    assert log.totals()["by_cache"] == {"hit": 1, "miss": 1, "small": 1,
                                        "uncached": 0}
    got = bps.get_metrics()
    assert got['bps_compile_programs{cache="hit"}'] == 1
    assert got['bps_compile_seconds{kind="COMPILE"}'] > 0


def test_setup_ends_at_the_first_call_that_compiles_nothing(settled, log):
    step, args = settled
    steady = log.steady_at
    step(*args)
    assert log.steady_at == steady          # set once
    mine = [r for r in log.snapshot()["records"]
            if r["cause"] == "train_step"]
    assert {r["kind"] for r in mine} == {"TRACE", "LOWER", "COMPILE"}
    assert all(r["call"] == 1 and r["end"] <= steady for r in mine)
    assert [r["name"] for r in mine if r["kind"] == "COMPILE"] == [
        "jit(_local_step)"]
    got = bps.get_compile_log()
    assert set(got) == {"process_start", "installed_at", "steady_at",
                        "records", "totals"}
    assert got["steady_at"] == steady
    assert got["process_start"] < got["installed_at"] <= steady


def test_a_new_batch_shape_after_setup_is_a_recompile(settled, log):
    step, (w, state, batch) = settled
    heard = []
    handler = logging.Handler()
    handler.emit = lambda record: heard.append(record.getMessage())
    logger = get_logger()
    level = logger.level
    logger.addHandler(handler)
    logger.setLevel(logging.WARNING)
    before = _recompiles()
    try:
        step(w, state, np.ones((8, 16), np.float32))
    finally:
        logger.removeHandler(handler)
        logger.setLevel(level)
    assert _recompiles() == before + 1 and log.recompiles == 1
    warning, = heard
    assert "jit(_local_step)" in warning and "cache: uncached" in warning
    late = _records(log, "COMPILE")[-1]
    assert late["start"] >= log.steady_at
    assert (late["name"], late["cause"], late["call"]) == (
        "jit(_local_step)", "train_step", 3)


def test_the_scope_map_is_no_recompile(settled, log):
    before, made = _recompiles(), log.made
    brief = log.by_kind["TRACE"]["brief"]
    assert bps.get_step_scopes()
    # JAX hands back the executable the step holds: one trace of length
    # zero, which is counted and no record
    assert log.by_kind["TRACE"]["brief"] == brief + 1 and log.made == made
    assert _recompiles() == before and log.recompiles == 0
    # and where its lowering does have to compile, that is no recompile
    with compile_cache.caused_by("scope_map"):
        jax.jit(lambda x: x * 7 - 1)(np.ones(3, np.float32))
    assert _recompiles() == before
    assert _records(log, "COMPILE")[-1]["cause"] == "scope_map"


def test_a_traced_call_ends_no_setup(log):
    """`build_train_step`'s callable inside somebody else's jit sends
    nested events only: that call is no sign that set-up has ended."""
    step, args = _tiny_step()
    for _ in range(2):
        jax.jit(lambda w, s, x: step(w, s, x)[2])(*args)
    assert log.steady_at is None


def test_the_cap_holds_and_the_totals_go_on():
    log = compile_cache.CompileLog()
    extra = 10
    for i in range(compile_cache.MAX_RECORDS + extra):
        log.enter("TRACE")
        log.enter("TRACE")              # a nested one
        log.leave("TRACE", i + 0.5, i + 0.75, "inner")
        log.leave("TRACE", float(i), i + 1.0, "f")
        log.enter("TRACE")              # a jaxpr JAX already held
        log.leave("TRACE", i + 1.0, i + 1.0, "add")
    got = log.snapshot()
    assert len(got["records"]) == compile_cache.MAX_RECORDS
    totals = got["totals"]
    assert totals["records"] == compile_cache.MAX_RECORDS + extra
    assert totals["kept"] == compile_cache.MAX_RECORDS
    assert totals["by_kind"]["TRACE"] == {
        "records": compile_cache.MAX_RECORDS + extra,
        "nested": compile_cache.MAX_RECORDS + extra,
        "seconds": float(compile_cache.MAX_RECORDS + extra),
        "brief": compile_cache.MAX_RECORDS + extra}


def test_threads_lose_no_record():
    """More threads than cores entering and leaving at once: every
    outermost span is a record, none is left open, and a clean call of an
    entry point is only found once all have left."""
    import sys
    log = compile_cache.CompileLog()
    threads, each = 16, 300
    began = log.call_begin()
    go = threading.Event()

    def work():
        go.wait(10)
        for i in range(each):
            log.enter("COMPILE")
            log.cache_said("asked")
            log.enter("COMPILE")
            log.leave("COMPILE", 1.0, 2.0, "inner")
            log.leave("COMPILE", float(i), i + 1.0, "f")

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        pool = [threading.Thread(target=work) for _ in range(threads)]
        for t in pool:
            t.start()
        go.set()
        for t in pool:
            t.join(60)
        assert not any(t.is_alive() for t in pool)
    finally:
        sys.setswitchinterval(interval)
    log.call_end(began)
    assert log.steady_at is None            # records were made meanwhile
    log.call_end(log.call_begin())
    assert log.steady_at is not None
    totals = log.totals()
    assert log.open == 0 and totals["records"] == threads * each
    assert totals["by_kind"]["COMPILE"]["nested"] == threads * each
    assert totals["by_cache"]["uncached"] == threads * each
    assert sorted(r["seq"] for r in log.records) == list(
        range(compile_cache.MAX_RECORDS))


def test_a_nested_span_costs_microseconds(log):
    """What set-up pays for the log: JAX's three calls a span (entry,
    duration, exit) through the registered listeners, nested in an open
    trace as a model's thousands are.  About 1.3 us a span in the sandbox
    and 2.2 on the chip's host; the bound would still catch a lock, a
    record or a clock read on the nested path."""
    from jax._src import monitoring
    event = "/jax/core/compile/jaxpr_trace_duration"
    monitoring.record_scalar(event, 1.0, fun_name="outer")
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        monitoring.record_scalar(event, 1.0, fun_name="f")
        monitoring.record_event_duration_secs(event, 0.5, fun_name="f")
        monitoring.record_event_time_span(event, 1.0, 1.5, fun_name="f")
    per_span_us = (time.perf_counter() - t0) / n * 1e6
    monitoring.record_event_time_span(event, 1.0, 3.0, fun_name="outer")
    record, = _records(log, "TRACE", "outer")
    assert record["nested"] == n and log.made == 1
    assert per_span_us < 50, f"a nested span cost {per_span_us:.1f} us"
