"""Reduction of a JAX profiler trace to device busy time, program and
kernel times, and idle gaps attributed to the harness's host spans.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into plain
event tuples; ``summarize`` works on those alone, so a test can feed it a
small recorded trace.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
from typing import Dict, List, Tuple

__all__ = ["load", "summarize", "reduce", "union", "gaps"]

Event = Tuple[str, str, int, int]        # (line, name, start_ns, duration_ns)


def load(trace_dir) -> Dict[str, List[Event]]:
    """{"device": op and program events of the first TPU, "host": every
    host-thread event}."""
    import jax

    paths = sorted(glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    out: Dict[str, List[Event]] = {"device": [], "host": []}
    for plane in data.planes:
        if plane.name == "/device:TPU:0":
            side, lines = "device", (OPS_LINE, MODULES_LINE)
        elif plane.name.startswith("/host:"):
            side, lines = "host", None
        else:
            continue
        for line in plane.lines:
            if lines is None or line.name in lines:
                out[side].extend((line.name, ev.name, int(ev.start_ns), int(ev.duration_ns))
                                 for ev in line.events)
    return out


def union(intervals) -> List[Tuple[int, int]]:
    """Merged (start, end) intervals."""
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(busy, start: int, end: int) -> List[Tuple[int, int]]:
    """The idle intervals of [start, end) outside ``busy`` (merged)."""
    out, t = [], start
    for s, e in busy:
        if s > t:
            out.append((t, min(s, end)))
        t = max(t, e)
    if t < end:
        out.append((t, end))
    return [(s, e) for s, e in out if e > s]


# kernels by the names the trace gives their ops today: a Pallas kernel's
# custom call is named after the function that calls pallas_call
KERNELS = {"pallas_dip": "%dip_matmul_pallas", "flash_attention": "%flash_attention_pallas"}
# ops that only contain others (a scan's loop): left out of the op breakdown
CONTAINERS = ("while", "conditional", "call")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def kernel_of(op_name: str):
    for label, prefix in KERNELS.items():
        if op_name.startswith(prefix + "."):
            return label
    return None


def op_kind(op_name: str) -> str:
    """An op's name without its HLO text: "%fusion.12 = bf16[...] ..." ->
    "fusion"; a Pallas kernel -> its label."""
    label = kernel_of(op_name)
    if label:
        return label
    head = op_name.split(" = ", 1)[0].lstrip("%")
    base, _, tail = head.rpartition(".")
    return base if tail.isdigit() and base else head


def summarize(events: Dict[str, List], window_s: float) -> dict:
    """Busy time, per-program and per-kernel device time (and each kernel
    call's op name, seconds and program), and idle gaps.

    ``events["device"]`` holds (line, name, start_ns, duration_ns) tuples of
    the ops and modules lines, ``events["host"]`` those of the host threads.
    A program execution (an event of the modules line) is named by the
    kernels that ran inside it: ``prefill`` if flash attention did,
    ``decode`` if only ``pallas_dip`` did; of the rest, the engine's KV
    import (``jit_imp``) is ``import`` and any other program ``other``."""
    ops = [e for e in events["device"] if e[0] == OPS_LINE]
    modules = sorted((e for e in events["device"] if e[0] == MODULES_LINE),
                     key=lambda e: e[2])
    busy = union((e[2], e[2] + e[3]) for e in ops)
    busy_ns = sum(e - s for s, e in busy)

    kernels: Dict[str, Dict[str, float]] = collections.defaultdict(
        lambda: {"count": 0, "seconds": 0.0})
    by_op: Dict[str, float] = collections.defaultdict(float)
    calls = []                                  # (start, kernel, op name, seconds)
    for e in ops:
        kind = op_kind(e[1])
        if kind not in CONTAINERS:
            by_op[kind] += e[3] / 1e9
        if kind in KERNELS:
            kernels[kind]["count"] += 1
            kernels[kind]["seconds"] += e[3] / 1e9
            calls.append((e[2], kind, e[1], e[3] / 1e9))
    calls.sort(key=lambda c: c[0])

    programs: Dict[str, Dict[str, float]] = collections.defaultdict(
        lambda: {"count": 0, "seconds": 0.0})
    starts = [c[0] for c in calls]
    program_of = [None] * len(calls)
    for m in modules:
        lo = bisect.bisect_left(starts, m[2])
        hi = bisect.bisect_right(starts, m[2] + m[3])
        inside = {c[1] for c in calls[lo:hi]}
        kind = ("prefill" if "flash_attention" in inside else
                "decode" if "pallas_dip" in inside else
                "import" if m[1].startswith("jit_imp(") else "other")
        programs[kind]["count"] += 1
        programs[kind]["seconds"] += m[3] / 1e9
        program_of[lo:hi] = [kind] * (hi - lo)
    kernel_events: Dict[str, list] = collections.defaultdict(list)
    for (_, kind, name, sec), prog in zip(calls, program_of):
        kernel_events[kind].append((name, sec, prog))

    everything = [(e[2], e[2] + e[3]) for side in events.values() for e in side]
    t0 = min(s for s, _ in everything) if everything else 0
    t1 = max(e for _, e in everything) if everything else 0
    host = sorted(events["host"], key=lambda e: e[3])
    idle = []
    for s, e in sorted(gaps(busy, t0, t1), key=lambda g: g[0] - g[1])[:10]:
        # the innermost host event under the gap's middle says what the
        # host was doing while the device waited
        mid = (s + e) // 2
        label = next((h[1] for h in host if h[2] <= mid <= h[2] + h[3]), "no host event")
        idle.append((label, (e - s) / 1e9))
    return {
        "window_s": window_s, "busy_s": busy_ns / 1e9,
        "programs": {k: dict(v) for k, v in programs.items()},
        "kernels": {k: dict(v) for k, v in kernels.items()},
        "kernel_events": dict(kernel_events),
        "breakdown": {
            "device_ops": [[n, s] for n, s in sorted(by_op.items(), key=lambda x: -x[1])[:10]],
            "idle_gaps": [[n, s] for n, s in idle],
        },
    }


def reduce(trace_dir, window) -> dict:
    """``summarize`` of the trace in ``trace_dir``; ``window`` is the host
    clock's (start, stop) of the traced slice."""
    return summarize(load(trace_dir), window[1] - window[0])
