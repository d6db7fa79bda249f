"""The floor probe of the PS wire (byteps_tpu/server/wire_floor.py,
docs/performance.md "The floor"): against its own child over both
families a session dials, and what it does when it cannot be made."""

import json
import subprocess
import sys

import pytest

from byteps_tpu.common import telemetry
from byteps_tpu.server import wire_floor

_MB = 1 << 20


@pytest.fixture
def peers(monkeypatch):
    """Every peer the test's probes start."""
    started = []
    start = wire_floor._start_peer

    def recording(*args):
        started.append(start(*args))
        return started[-1]

    monkeypatch.setattr(wire_floor, "_start_peer", recording)
    yield started
    assert started
    for peer in started:
        assert peer.poll() is not None, "a peer outlived its probe"


def _off_the_accelerator(pid: int) -> bool:
    """No accelerator runtime, and no jax at all, mapped into `pid`."""
    with open(f"/proc/{pid}/maps") as f:
        maps = f.read()
    return not any(lib in maps for lib in ("libtpu", "jaxlib", "xla"))


@pytest.mark.parametrize("transport", ["tcp", "uds"])
def test_probe_against_its_child(transport, peers, monkeypatch):
    seen = []
    phase = wire_floor._phase

    def watching(lanes, *args):
        seen.append(_off_the_accelerator(peers[-1].pid))
        return phase(lanes, *args)

    monkeypatch.setattr(wire_floor, "_phase", watching)
    # Rates at a few MB on a busy host swing: the order of the three
    # has to hold in one of a few probes, the rest in every one.
    ordered = []
    for _ in range(4):
        got = wire_floor.probe(transport=transport, lanes=3,
                               frame_bytes=_MB, sock_buf_kb=256,
                               bytes_out=8 * _MB, bytes_in=5 * _MB)
        assert (got["transport"], got["lanes"], got["frame_bytes"],
                got["sock_buf_kb"]) == (transport, 3, _MB, 256)
        # whole frames a lane: 3 x 3 MB out, 3 x 2 MB in
        assert got["out"]["bytes"] == 9 * _MB
        assert got["in"]["bytes"] == 6 * _MB
        assert got["duplex"]["bytes"] == 15 * _MB
        for name in ("out", "in", "duplex"):
            assert got[name]["seconds"] > 0
            assert got[name]["GB_per_s"] == pytest.approx(
                got[name]["bytes"] / got[name]["seconds"] / 1e9)
        ordered.append(got["duplex"]["GB_per_s"] >= min(
            got["out"]["GB_per_s"], got["in"]["GB_per_s"]))
        if ordered[-1]:
            break
    assert any(ordered)
    assert seen and all(seen)
    assert len(peers) == len(ordered)


def test_a_missing_child_leaves_no_file_and_no_exception(
        monkeypatch, tmp_path):
    monkeypatch.setattr(sys, "executable", str(tmp_path / "no-python"))
    monkeypatch.setattr(wire_floor, "_probed", False)
    assert wire_floor.probe(bytes_out=_MB, bytes_in=_MB) is None

    class Session:
        partition_bytes, sock_buf_kb = _MB, 0
        _data_conns = [[type("Conn", (), {"transport": "tcp"})()] * 2]
        spans = type("Spans", (), {"last": None})()

    assert wire_floor.probe_at_shutdown(Session(), str(tmp_path)) is None
    assert not list(tmp_path.iterdir())


def test_a_silent_or_slow_peer_is_given_up_and_reaped(peers):
    # a deadline that passes mid-probe fails the lanes' calls
    assert wire_floor.probe(bytes_out=4096 * _MB, bytes_in=4096 * _MB,
                            timeout_s=0.05) is None
    # a child that never says where it listens
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(wire_floor, "START_TIMEOUT_S", 0.3)
        patch.setattr(wire_floor.subprocess, "Popen", _sleeper)
        assert wire_floor.probe(bytes_out=_MB, bytes_in=_MB) is None
    assert len(peers) == 2


def _sleeper(argv, start=subprocess.Popen, **kw):
    return start(["sleep", "60"], **kw)


def test_shutdown_probes_once_a_process(peers, monkeypatch, tmp_path):
    monkeypatch.setattr(wire_floor, "_probed", False)

    class Session:
        partition_bytes, sock_buf_kb = _MB // 4, 0
        _data_conns = [[type("Conn", (), {"transport": "uds"})()] * 2]
        spans = type("Spans", (), {
            "last": {"bytes_out": 3 * _MB, "bytes_in": 2 * _MB}})()

    got = wire_floor.probe_at_shutdown(Session(), str(tmp_path))
    with open(tmp_path / "wire_floor.json") as f:
        assert json.load(f) == got
    assert (got["transport"], got["lanes"], got["frame_bytes"]) == (
        "uds", 2, _MB // 4)
    # the last round's bytes, where they are under MIN_BYTES
    assert got["out"]["bytes"] == 3 * _MB and got["in"]["bytes"] == 2 * _MB
    text = telemetry.get_registry().render_prometheus()
    for name in ("out", "in", "duplex"):
        assert f'bps_wire_floor_gbps{{dir="{name}"}} ' \
            f'{got[name]["GB_per_s"]!r}' in text
    (tmp_path / "wire_floor.json").unlink()
    assert wire_floor.probe_at_shutdown(Session(), str(tmp_path)) is None
    assert len(peers) == 1 and not list(tmp_path.iterdir())
