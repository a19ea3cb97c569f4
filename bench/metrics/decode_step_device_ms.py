"""Device time of one call of the engine's paged-decode program, from the
trace: the modules-line executions in which only ``pallas_dip`` kernels ran."""


def read(rec):
    p = rec.get("trace", {}).get("programs", {}).get("decode")
    return p["seconds"] / p["count"] * 1e3 if p and p["count"] else None
