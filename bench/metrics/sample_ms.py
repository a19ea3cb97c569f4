"""Mean host time of an ``Engine.step()`` that did work (ran a prefill
chunk or a decode step) spent drawing tokens: the ``engine.sample`` spans
under each ``engine.step`` span of the program's own recorder
(``repro.serving.telemetry``), over the window less its traced slice.
A program without that recorder reads nothing."""

WORK = ("engine.prefill", "engine.decode")


def per_tick_ms(rec, name: str):
    """Mean ms, over the working ticks that start in ``rec["host_window"]``,
    of the summed ``name`` spans under each tick; None without spans."""
    try:
        from repro.serving import telemetry
    except ImportError:
        return None
    spans = telemetry.RECORDER.spans()
    by_id = {s.id: s for s in spans}
    lo, hi = rec["host_window"]
    ticks = {s.id: 0.0 for s in spans if s.name == "engine.step" and lo <= s.start < hi}
    worked = {s.parent for s in spans if s.name in WORK and s.parent in ticks}

    def tick_of(s):
        while s is not None and s.name != "engine.step":
            s = by_id.get(s.parent)
        return s.id if s is not None else None

    for s in spans:
        if s.name == name:
            t = tick_of(s)
            if t in worked:
                ticks[t] += s.end - s.start
    return sum(ticks[t] for t in worked) / len(worked) * 1e3 if worked else None


def read(rec):
    return per_tick_ms(rec, "engine.sample")
