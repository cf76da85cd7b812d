"""Who kept the chip waiting (``vlog_tpu/obs/hostwait.py``), on the CPU:
the wait helper's record, the process's GC recorder, the stall book, the
two counters, and ``obs/profiler.py::summarize`` booking a device gap
under a collection or the engine's thread."""

import gc
import time

import numpy as np
import pytest

from vlog_tpu.obs import hostwait
from vlog_tpu.obs.hostwait import GcRecorder, WaitBook, pull


@pytest.fixture
def recorder():
    """The process's recorder, installed and empty; as it was after."""
    was = hostwait.GC._installed
    hostwait.GC.reset()
    hostwait.GC.install()
    yield hostwait.GC
    hostwait.GC.reset()
    if was:
        hostwait.GC.install()


class Flipping:
    """A device array stand-in: ready at the ``n``-th ``is_ready()``;
    ``on_call`` runs before the answer of the call it is keyed by."""

    def __init__(self, n, on_call=None):
        self.n = n
        self.calls = 0
        self.on_call = on_call or {}
        self.value = np.arange(4, dtype=np.int32)

    def is_ready(self):
        self.calls += 1
        if self.calls in self.on_call:
            self.on_call[self.calls]()
        return self.calls >= self.n

    def __array__(self, dtype=None, copy=None):
        return self.value


def _garbage_collect():
    junk = [[i] for i in range(20000)]
    for a, b in zip(junk, junk[1:]):
        a.append(b)
        b.append(a)
    del junk
    gc.collect()


def test_the_wait_record_counts_polls_and_sees_a_pause_and_a_collection(
        recorder):
    arr = Flipping(6, {2: lambda: time.sleep(0.15), 4: _garbage_collect,
                       5: lambda: time.sleep(0.05)})
    t0 = time.monotonic()
    (host,), wait = pull((arr,), poll_s=1e-4)
    assert np.array_equal(host, arr.value)
    assert wait["polls"] == arr.calls == 6
    # the thread was away 0.15 s between two polls: the widest stretch
    assert 0.15 <= wait["gap_max_s"] < wait["wait_s"]
    # the collection inside the wait, as the recorder measured it
    start, seconds, gen = recorder.entries()[-1]
    assert gen == 2 and seconds > 0 and start > t0
    assert wait["gc_s"] == pytest.approx(sum(
        s for at, s, _g in recorder.entries() if at >= t0))
    assert wait["gc_s"] < wait["wait_s"] - 0.15
    assert 0.0 <= wait["cpu_s"] <= wait["wait_s"] + 0.05
    # the longest is_ready() call, and the copy, apart from the gaps
    assert 0.15 <= wait["ready_max_s"] < wait["gap_max_s"] + 1e-9
    assert 0.0 <= wait["copy_s"] < 0.05
    assert set(wait) == {"polls", "gap_max_s", "cpu_s", "gc_s", "wait_s",
                         "ready_max_s", "copy_s"}


def test_a_ready_result_costs_one_poll_and_a_stand_in_none(recorder):
    (a, b), wait = pull((Flipping(1), np.ones(3)), poll_s=1e-3)
    assert wait["polls"] == 2 and wait["gc_s"] == 0.0
    assert wait["gap_max_s"] < 0.1 and b.tolist() == [1.0, 1.0, 1.0]


def test_the_ring_stays_bounded_and_reset_removes_the_callback():
    rec = GcRecorder(size=8)
    for i in range(20):
        rec._callback("start", {"generation": i % 3})
        rec._callback("stop", {"generation": i % 3})
    assert len(rec.entries()) == 8 and sum(rec.counts) == 20
    assert rec.counts == [7, 7, 6] and all(s >= 0 for s in rec.seconds)
    starts = [e[0] for e in rec.entries()]
    assert starts == sorted(starts)
    rec.install()
    rec.install()                       # once a process
    assert gc.callbacks.count(rec._callback) == 1
    gc.collect()
    assert rec.counts[2] == 7
    rec.reset()
    assert rec._callback not in gc.callbacks and not rec._installed
    assert rec.entries() == [] and rec.counts == [0, 0, 0]
    gc.collect()
    assert rec.counts == [0, 0, 0]


def test_seconds_between_counts_only_the_overlap():
    rec = GcRecorder(size=4)
    for start, seconds in ((1.0, 0.5), (2.0, 0.1), (3.0, 1.0),
                           (10.0, 0.2), (11.0, 0.3)):
        rec._ring[rec._n % rec.size] = (start, seconds, 0)
        rec._n += 1
    # (1.0, 0.5) fell out of the ring of four
    assert rec.seconds_between(0.0, 20.0) == pytest.approx(1.6)
    assert rec.seconds_between(2.05, 3.5) == pytest.approx(0.05 + 0.5)
    assert rec.seconds_between(4.5, 9.0) == 0.0
    assert rec.seconds_between(10.1, 11.1) == pytest.approx(0.2)


def test_only_generations_one_and_two_open_an_annotation(monkeypatch):
    opened = []

    class Note:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(("enter", self.name))

        def __exit__(self, *exc):
            opened.append(("exit", self.name))

    monkeypatch.setattr(hostwait, "_annotation",
                        lambda name, attrs: Note(name))
    rec = GcRecorder()
    for gen in (0, 1, 2):
        rec._callback("start", {"generation": gen})
        rec._callback("stop", {"generation": gen})
    assert opened == [("enter", "gc.gen1"), ("exit", "gc.gen1"),
                      ("enter", "gc.gen2"), ("exit", "gc.gen2")]


def _wait(wait_s, gc_s=0.0, gap_max_s=0.001, ready_max_s=1e-4,
          copy_s=1e-3):
    return {"polls": 3, "gap_max_s": gap_max_s, "cpu_s": 0.0,
            "gc_s": gc_s, "wait_s": wait_s, "ready_max_s": ready_max_s,
            "copy_s": copy_s}


@pytest.mark.parametrize("late,cause", [
    (_wait(0.9, gc_s=0.5), "gc"),               # a collection held it
    (_wait(0.9, gap_max_s=0.5), "host"),        # the thread was away
    (_wait(0.9, gc_s=0.1, gap_max_s=0.1), "runtime"),   # "not ready"
    # the long gap held a blocking is_ready() call, or ended in the copy
    (_wait(0.9, gap_max_s=0.5, ready_max_s=0.45), "runtime"),
    (_wait(0.9, gap_max_s=0.5, copy_s=0.48), "runtime"),
    # a collection inside the call still books to the collection
    (_wait(0.9, gc_s=0.45, gap_max_s=0.5, ready_max_s=0.5), "gc"),
    (_wait(0.2, gc_s=0.2, gap_max_s=0.2), None),        # within 0.2 s
])
def test_a_stall_is_booked_to_its_cause(late, cause):
    book = WaitBook()
    for i in range(10):
        assert book.add(_wait(0.05 + 0.001 * i), key=0, seq=i) is None
    assert book.add(late, key=0, seq=10) == cause
    stats = book.stats()
    assert stats["count"] == 11
    assert stats["stalls"] == {c: int(c == cause)
                               for c in ("gc", "host", "runtime")}
    top = stats["longest"][0]
    assert top["seq"] == 10 and top["cause"] == cause and top["key"] == 0
    assert top["excess_s"] == pytest.approx(late["wait_s"] - 0.0545)


def test_waits_are_judged_against_their_own_key_and_five_are_kept():
    book = WaitBook()
    assert book.add(_wait(5.0), key=2048, seq=0) is None   # no history
    for i in range(1, 9):
        assert book.add(_wait(0.02), key=0, seq=i) is None
        assert book.add(_wait(0.8), key=2048, seq=100 + i) is None
    assert book.add(_wait(1.05, gap_max_s=0.3), key=2048, seq=200) == "host"
    stats = book.stats()
    assert stats["stalls"]["host"] == 1
    assert [w["wait_s"] for w in stats["longest"]] == [5.0, 1.05, 0.8,
                                                       0.8, 0.8]
    assert len(book._recent[0]) == 8


def test_the_history_is_the_last_64():
    book = WaitBook()
    for i in range(100):
        book.add(_wait(1.0 if i < 40 else 0.01), key=None, seq=i)
    assert len(book._recent[None]) == 64
    # the median is the recent 0.01, so 0.25 is a stall
    assert book.add(_wait(0.25, gc_s=0.2), key=None, seq=100) == "gc"


def test_the_two_counters_are_documented_and_exported(recorder):
    from vlog_tpu.analysis import registry
    from vlog_tpu.obs.metrics import runtime

    registry.assert_metric_families(("vlog_gc_pause_seconds_total",
                                     "vlog_engine_stalls_total"))
    gc.collect()
    runtime().engine_stalls.labels("lm", "gc").inc()
    text = runtime().render_text()
    line = next(ln for ln in text.splitlines()
                if ln.startswith('vlog_gc_pause_seconds_total{generation="2"}'))
    assert float(line.split()[-1]) == pytest.approx(recorder.seconds[2])
    assert float(line.split()[-1]) > 0
    assert 'vlog_engine_stalls_total{cause="gc",plane="lm"}' in text


# ---------------------------------------------------------------------------
# obs/profiler.py::summarize: who a device gap is booked to
# ---------------------------------------------------------------------------

def _planes(host_lines):
    """One device with ops at [0, 1000) and [9000, 10000) us: one gap
    whose middle is at 5,000 us; host lines as given."""
    us = 1000
    return [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [(0, 1000 * us, "lm.moe.experts"),
                                       (9000 * us, 10000 * us, "lm.attn")]},
        {"name": "XLA Modules", "events": [
            (0, 10000 * us, "jit_lm_step_c0(123)")]}]},
        {"name": "/host:CPU", "lines": [
            {"name": name, "events": [(s * us, e * us, n)
                                      for s, e, n in events]}
            for name, events in host_lines]}]


ENGINE = ("python3", [(0, 10000, "lm.step"), (1000, 9500,
                                              "lm.step.device_wait")])
BYSTANDER = ("python3", [(4000, 6000, "digest.job.prompt")])


@pytest.mark.parametrize("host_lines,owner", [
    # the engine's own span over a shorter one of another thread
    ([ENGINE, BYSTANDER], "lm.step.device_wait"),
    # a collection stops every thread: it wins wherever it covers
    ([ENGINE, BYSTANDER, ("python3", [(4500, 5500, "gc.gen2")])],
     "gc.gen2"),
    ([ENGINE, ("python3", [(4900, 5100, "gc.gen1")])], "gc.gen1"),
    # the Whisper engine's thread: the line that holds its tick
    ([("python3", [(0, 10000, "asr.tick"),
                   (1000, 9000, "asr.generate.device_wait")]),
      BYSTANDER], "asr.generate.device_wait"),
    # no engine thread: the innermost of any thread, as before
    ([("python3", [(0, 10000, "bench.job")]), BYSTANDER],
     "digest.job.prompt"),
    ([BYSTANDER[:1] + ([(0, 100, "x")],)], "no_program_span"),
])
def test_summarize_books_a_gap_to_a_collection_then_the_engine(
        host_lines, owner):
    from vlog_tpu.obs.profiler import summarize_planes

    got = summarize_planes(_planes(host_lines))
    assert got["idle_by_span"] == {owner: pytest.approx(0.008)}
    assert got["busy_s"] == pytest.approx(0.002)
    assert got["by_scope"] == {"lm.moe.experts": pytest.approx(0.001),
                               "lm.attn": pytest.approx(0.001)}
