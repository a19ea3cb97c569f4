"""The readers of the engine's own spans: ``sample_ms`` and
``device_wait_ms`` over a hand-filled recorder and a synthetic record."""

import sys

import pytest

from bench import run
from repro.serving import telemetry

READERS = [("sample_ms.chat", "engine.sample"), ("device_wait_ms.chat", "engine.fetch")]


class Clock:
    """Reads the time it is set to."""
    t = 0.0

    def __call__(self):
        return self.t


def tick(rec, clock, start, *, work="engine.decode", sample=0.0, fetch=0.0):
    """One ``engine.step`` from ``start``: an optional chunk or decode
    step, then a fetch and a draw of the given seconds, then 1 s more."""
    clock.t = start
    with rec.span("engine.step"):
        if work:
            with rec.span(work):
                clock.t += 0.5
        if fetch:
            with rec.span("engine.fetch"):
                clock.t += fetch
        if sample:
            with rec.span("engine.sample"):
                clock.t += sample
        clock.t += 1.0


@pytest.fixture
def recorder(monkeypatch):
    clock = Clock()
    rec = telemetry.Recorder(clock=clock)
    monkeypatch.setattr(telemetry, "RECORDER", rec)
    return rec, clock


@pytest.mark.parametrize("metric,span", READERS)
def test_mean_over_working_ticks_in_the_window(recorder, metric, span):
    rec, clock = recorder
    key = "sample" if span == "engine.sample" else "fetch"
    tick(rec, clock, 0.0, **{key: 9.0})                    # before the window
    tick(rec, clock, 10.0, **{key: 0.2})
    tick(rec, clock, 20.0, work="engine.prefill", **{key: 0.4})
    tick(rec, clock, 30.0, work=None, **{key: 5.0})        # no chunk, no decode
    tick(rec, clock, 40.0, work=None)
    tick(rec, clock, 99.0, **{key: 9.0})                   # after the window
    got = run.reader(metric).read({"host_window": (5.0, 50.0)})
    assert got == pytest.approx(300.0)                     # (0.2 + 0.4) / 2 s


@pytest.mark.parametrize("metric,span", READERS)
def test_spans_nested_deeper_count_toward_their_tick(recorder, metric, span):
    rec, clock = recorder
    clock.t = 10.0
    with rec.span("engine.step"):
        with rec.span("engine.prefill"):
            with rec.span(span):
                clock.t += 0.25
        with rec.span(span):
            clock.t += 0.5
    assert run.reader(metric).read({"host_window": (0.0, 50.0)}) == pytest.approx(750.0)


@pytest.mark.parametrize("metric,span", READERS)
def test_nothing_to_read(recorder, monkeypatch, metric, span):
    rec, clock = recorder
    reader = run.reader(metric)
    assert reader.read({"host_window": (0.0, 50.0)}) is None          # no spans
    tick(rec, clock, 10.0, work=None, sample=0.1, fetch=0.1)
    assert reader.read({"host_window": (0.0, 50.0)}) is None          # no working tick
    tick(rec, clock, 20.0, sample=0.1, fetch=0.1)
    assert reader.read({"host_window": (0.0, 50.0)}) is not None
    # a program without the recorder: the reader finds nothing, and raises nothing
    monkeypatch.setitem(sys.modules, "repro.serving.telemetry", None)
    monkeypatch.delattr(sys.modules["repro.serving"], "telemetry")
    assert reader.read({"host_window": (0.0, 50.0)}) is None
