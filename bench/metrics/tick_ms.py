"""Mean host time of an ``Engine.step()`` that did work (ran a prefill
chunk or a decode step): the harness's span around each call, over the
window less its traced slice."""


def read(rec):
    lo, hi = rec["host_window"]
    spans = [t.end - t.start for t in rec.get("ticks", [])
             if (t.decode or t.prefill) and lo <= t.start < hi]
    return sum(spans) / len(spans) * 1e3 if spans else None
