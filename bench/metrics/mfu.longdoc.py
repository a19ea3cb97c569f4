"""Whole-step share of the chip's bf16 peak, in percent, for the MLA + MoE
family: model operations of the work done in the window over the window's
time and the peak.

Each output token reaching the host counts one forward token at its
context, and a first token counts its whole prompt, through the weights
every token multiplies and the expanded attention; the routed experts
count by the pairs the program's ``moe_routed`` counter saw in the same
interval (bench/work_mla_moe.py).  The interval is the window less its
traced slice.  A program without the counter reads nothing."""

from bench import weights_mla_moe as W
from bench import work, work_mla_moe


def read(rec):
    cell = rec["cell"]
    lo, hi = rec["host_window"]
    counts = work_mla_moe.counts_between(rec.get("moe", []), lo, hi)
    if counts is None or hi <= lo:
        return None
    dims = W.Dims(cell.conf)
    tokens = context = 0
    for srv in rec["served"].values():
        plen = srv.req.prompt.size
        for i, t in enumerate(srv.times):
            if lo <= t < hi:
                n = plen if i == 0 else 1
                tokens += n
                context += plen * (plen + 1) // 2 if i == 0 else plen + i
    flops = work_mla_moe.model_flops(dims, tokens, context, counts[0])
    return 100.0 * flops / (hi - lo) / work.peaks(cell.device.device_kind)["bf16_flops"]
