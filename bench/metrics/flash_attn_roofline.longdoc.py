"""Share of its roofline, in percent, that the flash-attention prefill
kernel reaches over the traced slice, for the expanded latent attention of
the MLA family: per head a query width of nope + rope and a value width of
v (bench/work_mla_moe.py).  One kernel event is one layer of one prefill
chunk; the chunks are those of the engine steps that ran inside the traced
slice, each at the prompt position it started at; their mean least time a
layer, times the kernel's events, over the kernel's summed time."""

from bench import weights_mla_moe as W
from bench import work, work_mla_moe


def read(rec):
    tr = rec.get("trace")
    k = tr and tr["kernels"].get("flash_attention")
    if not k or not k["seconds"]:
        return None
    cell = rec["cell"]
    dims = W.Dims(cell.conf)
    peak = work.peaks(cell.device.device_kind)
    c = cell.mix["engine"]["prefill_chunk"]
    lo, hi = cell.trace_window
    times = [work.least_time(*work_mla_moe.attention_work(dims, c, t.chunk_offset), peak)
             for t in rec["ticks"] if t.prefill and lo <= t.start and t.end <= hi]
    if not times:
        return None
    return 100.0 * k["count"] * (sum(times) / len(times)) / k["seconds"]
