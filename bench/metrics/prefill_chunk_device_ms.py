"""Device time of one call of the engine's chunked-prefill program, from the
trace: the modules-line executions in which the flash-attention kernel ran."""


def read(rec):
    p = rec.get("trace", {}).get("programs", {}).get("prefill")
    return p["seconds"] / p["count"] * 1e3 if p and p["count"] else None
