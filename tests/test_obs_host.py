"""``obs/host.py``: the two parsers on texts recorded from ``/proc``,
on a missing file and on malformed lines; the begin / end pair."""

import gc
import os

import pytest

from routest_tpu.obs import host


class _Span:
    """What the pair asks of a span."""

    def __init__(self, sampled=True):
        self.sampled, self.attrs = sampled, {}

    def set_attr(self, key, value):
        self.attrs[key] = value


def _end(start):
    span = _Span()
    host.end(span, start)
    return span.attrs


@pytest.fixture(autouse=True)
def reopened(monkeypatch):
    """Each test opens the files it points the reader at; the process's
    own descriptors are put back after."""
    monkeypatch.setattr(host, "_fds", None)
    yield
    for fd in (host._fds or {}).values():
        os.close(fd)

PRESSURE_CPU = """\
some avg10=5.62 avg60=2.00 avg300=1.41 total=3868270128
full avg10=0.00 avg60=0.00 avg300=0.00 total=0
"""
# a kernel before 5.13 writes no ``full`` line for the CPU
PRESSURE_OLD = "some avg10=0.00 avg60=0.00 avg300=0.00 total=1500\n"
STAT = """\
cpu  4938051 0 391982 113728701 44867 0 31632 206739 0 0
cpu0 603194 0 63331 14154904 18153 0 10006 32355 0 0
intr 1 2 3
ctxt 12345
"""


@pytest.mark.parametrize("text,want", [
    (PRESSURE_CPU, 3868270.128), (PRESSURE_OLD, 1.5),
    ("", None), ("full avg10=0.00 total=7\n", None),
    ("some avg10=0.00 avg60=0.00\n", None),         # no total
    ("some avg10=0.00 total=many\n", None),         # not a number
    ("some\n", None), ("garbage without fields", None)])
def test_pressure_is_the_some_lines_total_in_milliseconds(text, want):
    assert host.parse_pressure(text) == want


@pytest.mark.parametrize("text,want", [
    (STAT, (2067390.0, 448670.0)),                  # steal, iowait at 100 Hz
    ("cpu0 1 2 3 4 5 6 7 8 9 10\n", None),          # no aggregate line
    ("cpu  1 2 3 4 5 6 7\n", None),                 # a line too short
    ("cpu  1 2 3 4 x 6 7 8\n", None), ("", None)])
def test_stat_is_the_aggregate_lines_steal_and_iowait(text, want):
    assert host.parse_stat(text, 100.0) == want


def test_ticks_follow_the_clock_rate():
    assert host.parse_stat(STAT, 250.0) == (206739 * 4.0, 44867 * 4.0)


def test_a_pair_gives_the_growth_of_what_both_ends_could_read(tmp_path,
                                                              monkeypatch):
    cpu, stat = tmp_path / "cpu", tmp_path / "stat"
    cpu.write_text(PRESSURE_CPU)
    stat.write_text(STAT)
    monkeypatch.setattr(host, "PRESSURE", {
        "psi_cpu_ms": str(cpu), "psi_io_ms": str(tmp_path / "absent"),
        "psi_mem_ms": str(tmp_path)})               # a directory: OSError
    monkeypatch.setattr(host, "STAT", str(stat))
    start = host.begin(_Span())
    cpu.write_text(PRESSURE_CPU.replace("3868270128", "3868282128"))
    stat.write_text(STAT.replace("31632 206739", "31632 206742"))
    sum(i * i for i in range(20000))                # some CPU time
    got = _end(start)
    assert got["psi_cpu_ms"] == 12.0
    assert got["steal_ms"] > 0.0 and got["iowait_ms"] == 0.0
    assert "psi_io_ms" not in got and "psi_mem_ms" not in got
    assert got["cpu_ms"] > 0.0 and got["gc_ms"] >= 0.0
    assert got["nivcsw"] >= 0 and got["majflt"] >= 0


def test_a_source_that_goes_away_or_turns_malformed_is_left_out(
        tmp_path, monkeypatch):
    cpu, stat = tmp_path / "cpu", tmp_path / "stat"
    cpu.write_text(PRESSURE_CPU)
    stat.write_text(STAT)
    monkeypatch.setattr(host, "PRESSURE", {"psi_cpu_ms": str(cpu)})
    monkeypatch.setattr(host, "STAT", str(stat))
    start = host.begin(_Span())
    os.close(host._fds.pop("psi_cpu_ms"))
    host._fds["psi_cpu_ms"] = -1                    # a read that fails
    stat.write_text("cpu  1 2\n")
    got = _end(start)
    host._fds.pop("psi_cpu_ms")
    assert not {"psi_cpu_ms", "steal_ms", "iowait_ms"} & set(got)
    assert "cpu_ms" in got


def test_with_no_proc_at_all_nothing_raises(tmp_path, monkeypatch):
    monkeypatch.setattr(host, "PRESSURE", {
        k: str(tmp_path / k) for k in host.PRESSURE})
    monkeypatch.setattr(host, "STAT", str(tmp_path / "stat"))
    monkeypatch.setattr(host, "resource", None)
    assert set(_end(host.begin(_Span()))) == {"cpu_ms", "gc_ms"}
    assert host._fds == {}                          # and looks no more


def test_the_files_are_opened_once_a_process(tmp_path, monkeypatch):
    stat = tmp_path / "stat"
    stat.write_text(STAT)
    monkeypatch.setattr(host, "PRESSURE", {})
    monkeypatch.setattr(host, "STAT", str(stat))
    host.begin(_Span())
    fds = dict(host._fds)
    assert set(fds) == {"stat"}
    stat.unlink()                                   # the descriptor stays
    assert _end(host.begin(_Span()))["steal_ms"] == 0.0
    assert host._fds == fds


def test_the_collectors_pauses_are_counted_once_hooked():
    start = host.begin(_Span())
    assert gc.callbacks.count(host._on_gc) == 1
    junk = [[i] for i in range(50000)]
    junk.append(junk)
    del junk
    gc.collect()
    host.begin(_Span())                                    # hooks no second time
    assert gc.callbacks.count(host._on_gc) == 1
    assert _end(start)["gc_ms"] > 0.0


def test_a_span_that_is_not_recorded_reads_nothing(monkeypatch):
    monkeypatch.setattr(host, "_now", lambda: pytest.fail("read"))
    span = _Span(sampled=False)
    start = host.begin(span)
    assert start is None and host._fds is None
    host.end(span, start)
    assert span.attrs == {}
