"""Share, in percent, of the expert rows the program computed that carry a
(token, slot) pair routed to an expert held here: its ``moe_routed`` over
its ``moe_rows`` counter, over the window less its traced slice.  A capacity
buffer computes rows for every expert and slot it could hold; a dropless
grouped matmul only the routed ones.  A program without the counters reads
nothing."""

from bench.work_mla_moe import counts_between


def read(rec):
    got = counts_between(rec.get("moe", []), *rec["host_window"])
    return 100.0 * got[0] / got[1] if got and got[1] else None
