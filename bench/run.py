#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload yi_9b.chat_poisson --seed 7 --seconds 51 --trace 0

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration,
``bench/configs/<config>.json``, and a traffic mix,
``bench/traffic/<traffic>.json``; the mix's ``kind``, up to its first
underscore, names the module that runs it (``serve_open`` ->
``bench/serve.py``).  Each metric is read by ``bench/metrics/<name>.py``,
or, where that file is missing, by the reader of the name's part before its
first dot.  ``--trace 0`` prints the cell's
end-to-end metrics; ``--trace 1`` traces a slice of the window with the JAX
profiler and prints the per-layer metrics.

Without a TPU, or with fewer chips than the cell asks for, the run exits
with code 3 and prints no result.  The last line of standard output is one
JSON object; the numbers compared for ``correct`` are also the last lines of
standard error.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):                 # the harness; the program
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def reader(name: str, root: Path = ROOT):
    """The reader module of a metric, found by its name."""
    for stem in (name, name.split(".")[0]):
        path = root / "bench" / "metrics" / f"{stem}.py"
        if path.is_file():
            spec = importlib.util.spec_from_file_location(f"bench_metric_{stem}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    raise FileNotFoundError(f"no reader for metric {name!r} under bench/metrics/")


def cell_metrics(bench: dict, workload: str, trace: bool) -> list:
    """The metric entries this cell reports in this kind of run."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads", [workload] if m["moves"] in moved else [])]


class Cell:
    """Everything the serving module needs about the run, and the tracing hooks."""

    def __init__(self, *, workload, conf, mix, seed, seconds, trace, trace_dir, device):
        import jax

        from bench import program, weights

        self.name, self.conf, self.mix = workload, conf, mix
        self.seed, self.seconds = seed, seconds
        self.dims = weights.Dims({k: v for k, v in conf.items()
                                  if isinstance(v, (int, float, bool))})
        self.cfg = program.arch_config(conf)
        self.key = weights.seed_key(seed)
        self.device = device
        self.tracing = trace
        self.trace_dir = trace_dir
        self.trace_s = min(seconds, float(mix.get("trace_s", 10)))
        self.trace_window = None              # host clock (start, stop)
        self._tracing_now = False
        self._jax = jax

    def span(self, name: str):
        """A host span in the profile while the trace is on."""
        if self._tracing_now:
            return self._jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def window_opened(self, now: float) -> None:
        self.t_open = now
        self.setup_s = now - T_START
        if self.tracing:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            # the Python tracer stays on: it names what the host does in
            # each idle gap (host-clock metrics leave the traced slice out)
            opts = self._jax.profiler.ProfileOptions()
            opts.enable_hlo_proto = False
            self._jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
            self._tracing_now = True
            self.trace_window = (time.monotonic(), None)

    def window_tick(self, now: float) -> None:
        if self._tracing_now and now >= self.trace_window[0] + self.trace_s:
            self._stop_trace()

    def window_closed(self) -> None:
        if self._tracing_now:
            self._stop_trace()

    def _stop_trace(self) -> None:
        stop = time.monotonic()
        self._jax.profiler.stop_trace()
        self._tracing_now = False
        self.trace_window = (self.trace_window[0], stop)

    def memory_peak(self) -> int:
        stats = self.device.memory_stats() or {}
        return int(stats.get("peak_bytes_in_use", 0))


def require_chips(n: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n:
        log(f"bench: needs {n} TPU chip(s); JAX found {len(devices)} "
            f"{devices[0].platform} device(s)")
        sys.exit(3)
    return devices


def resolve(workload: str, trace: bool, root: Path = ROOT):
    """The cell's entry, configuration, traffic mix and metric readers, all
    found by the names in ``BENCHMARK.json``."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    wl = cells[workload]
    conf = load_json(root / "bench" / "configs" / f"{wl['config']}.json")
    mix = load_json(root / "bench" / "traffic" / f"{wl['traffic']}.json")
    metrics = cell_metrics(bench, workload, trace)
    return wl, conf, mix, metrics, {m["name"]: reader(m["name"], root) for m in metrics}


def verdict(conf: dict, mix: dict, rec: dict):
    """(correct, {name: {value, limit}}): every number compared is at or
    under its limit, the limits being the configuration's for this kind of
    traffic."""
    limits = conf["check"][mix["kind"].split("_")[0]]
    checks = {name: {"value": rec["check"][name], "limit": limit}
              for name, limit in limits.items()}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks


def report(rec: dict, metrics: list, readers: dict) -> dict:
    out = {}
    for m in metrics:
        value = readers[m["name"]].read(rec)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        wl, conf, mix, metrics, readers = resolve(args.workload, bool(args.trace))
    except (KeyError, FileNotFoundError) as e:
        log(f"bench: {e}")
        return 2

    devices = require_chips(wl["chips"])
    import jax

    from bench import trace as tr
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **_: compiles.append(time.monotonic())
        if event == "/jax/core/compile/backend_compile_duration" else None)

    cell = Cell(workload=args.workload, conf=conf, mix=mix, seed=args.seed,
                seconds=args.seconds, trace=bool(args.trace),
                trace_dir=ROOT / ".bench_trace", device=devices[0])
    log(f"bench: {args.workload} seed {args.seed} on {len(devices)} x "
        f"{devices[0].device_kind}; compile cache {cache}")
    runner = importlib.import_module(f"bench.{mix['kind'].split('_')[0]}")
    rec = runner.run(cell, time.monotonic)
    rec.update(cell=cell, setup_s=cell.setup_s, compiles_in_window=sum(
        cell.t_open <= t < rec["t_close"] for t in compiles),
        host_window=(cell.trace_window[1] if cell.trace_window else cell.t_open,
                     rec["t_close"]))
    if args.trace:
        rec["trace"] = tr.reduce(cell.trace_dir, cell.trace_window)
        shutil.rmtree(cell.trace_dir, ignore_errors=True)

    out = report(rec, metrics, readers)
    correct, checks = verdict(conf, mix, rec)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": wl["chips"], "memory_peak_bytes": rec["memory_peak"]}
    result = {"correct": correct, "attempted": rec["attempted"], "failed": rec["failed"],
              "metrics": out, "device": device}
    if args.trace:
        device.update(busy_s=rec["trace"]["busy_s"], window_s=rec["trace"]["window_s"])
        result["breakdown"] = rec["trace"]["breakdown"]
    log(f"bench: setup_s {cell.setup_s}; compiles in window "
        f"{rec['compiles_in_window']}; {json.dumps(rec.get('notes', {}))}")
    result["check"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
