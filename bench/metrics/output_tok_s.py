"""Output tokens that reached the host inside the window, per second of
the window."""


def read(rec):
    t_open, t_close = rec["t_open"], rec["t_close"]
    n = sum(t_open <= t < t_close for s in rec["served"].values() for t in s.times)
    return n / (t_close - t_open)
