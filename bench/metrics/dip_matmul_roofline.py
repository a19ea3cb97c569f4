"""Share of its roofline, in percent, that ``pallas_dip`` reaches over the
traced slice: the least time of the work its calls did over their summed
time.  Each call is matched by its storage shapes to the model's
projection it computes (bench/work.py) and counted at that projection's
sizes, on the rows its program runs: the engine's slots in a decode step,
the chunk's rows in a prefill chunk.  Bound by the bf16 peak or by HBM
bandwidth, operands and weights at the bf16 compute width."""

from bench import work


def read(rec):
    events = (rec.get("trace") or {}).get("kernel_events", {}).get("pallas_dip")
    if not events:
        return None
    cell = rec["cell"]
    rows = {"decode": cell.mix["engine"]["slots"],
            "prefill": cell.mix["engine"]["prefill_chunk"]}
    peak = work.peaks(cell.device.device_kind)
    least = 0.0
    for name, _, program in events:
        proj = work.projection_of(cell.dims, *work.kernel_call_shape(name))
        if proj is None or program not in rows:
            return None
        _, k, n, weights = proj
        least += work.least_time(*work.matmul_work(rows[program], k, n, weights), peak)
    return 100.0 * least / sum(sec for _, sec, _ in events)
