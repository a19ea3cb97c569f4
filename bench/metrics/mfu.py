"""Whole-step share of the chip's bf16 peak, in percent: model operations of
the work done in the window over time and peak.

Each output token reaching the host counts one forward token at its
context, and a first token counts its whole prompt.  The time is the wall
time of the engine steps that did work for the open-loop chat mix, and the
window otherwise; both leave out the traced slice of the window."""

from bench import work


def read(rec):
    cell = rec["cell"]
    t_open, t_close = rec["host_window"]
    dims = cell.dims
    peak = work.peaks(cell.device.device_kind)["bf16_flops"]
    flops = 0.0
    for srv in rec["served"].values():
        plen = srv.req.prompt.size
        for i, t in enumerate(srv.times):
            if not t_open <= t < t_close:
                continue
            if i == 0:
                flops += work.model_flops(dims, plen, plen * (plen + 1) // 2)
            else:
                flops += work.model_flops(dims, 1, plen + i)
    if rec["kind"] == "serve_open":
        busy = sum(t.end - t.start for t in rec["ticks"]
                   if (t.decode or t.prefill) and t_open <= t.start < t_close)
    else:
        busy = t_close - t_open
    return 100.0 * flops / busy / peak if busy else None
