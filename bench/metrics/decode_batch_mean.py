"""Decode tokens emitted per decode step the engine ran (its
``decode_steps`` counter), over the window less its traced slice: how full
the decode batch runs."""


def read(rec):
    lo, hi = rec["host_window"]
    steps = sum(t.decode for t in rec.get("ticks", []) if lo <= t.end < hi)
    tokens = sum(lo <= t < hi for s in rec["served"].values() for t in s.times[1:])
    return tokens / steps if steps else None
