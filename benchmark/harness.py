"""What every cell shares: finding a cell's files by name, the device check,
the profiler window, the record a run leaves, and the last line it prints.

A cell is an entry of BENCHMARK.json's `workloads`. Its files are found by
name: cells/<cell>.json, configs/<config>.json, traffic/<traffic>.json, the
loop loops/<loop>.py that the cell's file names, the adapter
models/<model>.py that the configuration's file names, and one reader
metrics/<metric>.py for every metric BENCHMARK.json lists for the cell.
Adding a cell, a configuration, a mix or a metric adds files and entries and
edits none.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import shutil
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py, found by its file name (a metric's name
    may hold '.' or '-', which an import statement could not spell)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind}/{name}.py in the benchmark")
    if name.isidentifier():
        return importlib.import_module(f"benchmark.{kind}.{name}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}._{abs(hash(name))}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def quantile(values, q: float):
    """Linear interpolation between order statistics; None of nothing."""
    return float(np.quantile(values, q)) if len(values) else None


class Cell:
    """A cell's entry in BENCHMARK.json and the files it names."""

    def __init__(self, name: str):
        bench = load_json(os.pardir, "BENCHMARK.json")
        entry = next((w for w in bench["workloads"] if w["name"] == name), None)
        if entry is None:
            raise SystemExit(f"BENCHMARK.json has no workload {name!r}")
        self.name, self.chips = name, int(entry["chips"])
        self.spec = load_json("cells", name + ".json")
        self.config = load_json("configs", entry["config"] + ".json")
        from . import traffic
        self.traffic = traffic.load(entry["traffic"])
        self.adapter = load_module("models", self.config["model"])
        self.loop = load_module("loops", self.spec["loop"])
        self.metrics = {}
        for group in ("end_to_end", "per_layer"):
            self.metrics[group] = [
                m for m in bench[group]
                if "workloads" not in m or name in m["workloads"]]


def device_facts(chips: int) -> dict:
    """The accelerator as JAX reports it, or exit: a cell never runs smaller
    on a CPU, and never on fewer chips than it asks for. This is the
    process's first touch of the device: `jax.devices()` starts the TPU
    runtime, and one small array put on every chip of the cell brings up its
    transfer path, so that `start_run` can time the runtime's start apart
    from what the program sets up."""
    import jax
    devs = jax.devices()
    jax.block_until_ready([jax.device_put(np.zeros(8, np.float32), d)
                           for d in devs[:chips]])
    kind = devs[0].device_kind
    peaks = load_json("peaks.json")
    if devs[0].platform != "tpu":
        raise SystemExit(f"benchmark: JAX found platform {devs[0].platform!r} "
                         f"({kind}); a cell runs on a TPU and nowhere else")
    if len(devs) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    if kind not in peaks:
        raise SystemExit(f"benchmark: device_kind {kind!r} is not in "
                         f"benchmark/peaks.json; add it with its source")
    return {"platform": devs[0].platform, "kind": kind, "count": chips,
            "devices": devs[:chips], "peaks": peaks[kind]}


def memory_peak_bytes(devices) -> int:
    """Peak of device memory on the fullest chip. The TPU runtime keeps two
    books: `peak_bytes_in_use` for arrays (weights, optimizer state, caches,
    feeds) and `peak_bytes_reserved` for the scratch memory compiled programs
    reserve (activations, temporaries). They do not overlap:
    largest_free_block_bytes = bytes_limit - peak_bytes_in_use -
    bytes_reserved on the chip. The peak is their sum."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


class CompileCounter:
    """Counts XLA compilations (a cache hit that loads an executable counts
    too: inside the window there should be neither)."""

    def __init__(self):
        import jax.monitoring
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


class Profiler:
    """The profiler around a traced phase that FOLLOWS the measured window
    (starting and stopping a trace stalls the host for seconds, which inside
    the window would be charged to steps and requests). Host annotations on,
    Python call tracing off: it slows the host loop severalfold and nothing
    here reads it. While it is open the program's spans are TraceAnnotations
    on the profiler's clock."""

    def __init__(self):
        self.dir = os.path.join(ROOT, ".bench_trace")

    def __enter__(self):
        import jax
        from paddle_tpu.observability import tracing
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        tracing.annotation_factory = jax.profiler.TraceAnnotation
        return self

    def __exit__(self, *exc):
        import jax
        from paddle_tpu.observability import tracing
        tracing.annotation_factory = None
        jax.profiler.stop_trace()
        return False

    def result(self):
        """The reduced trace; None where it holds no TPU plane (a CPU)."""
        from . import xplane
        try:
            trace = xplane.Trace(xplane.find_xplane(self.dir))
            return trace if trace.devices else None
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


class Run:
    """What a loop hands the metric readers. Loops fill what they have; a
    reader that finds nothing to read returns None."""

    def __init__(self, cell: Cell, seed: int, seconds: float, device: dict):
        self.cell, self.seed, self.seconds, self.device = \
            cell, seed, seconds, device
        self.t0 = None            # the process's start, on perf_counter
        self.setup_s = None       # process start to the window, less runtime_start
        self.setup_parts = {}     # import / runtime_start / build / init /
                                  # compile_or_load / warm, in seconds
        self.steps = []           # (dispatched, loss on the host, tokens)
        self.batches = []         # the ring (training)
        self.requests = []        # dicts with due/submitted/.../done (serving)
        self.counters = {}        # program counters read after the window
        self.spans = []           # the program's spans recorded in the window
        self.trace = None         # xplane.Trace of the traced part, if any
        self.compiles_in_window = 0
        self.attempted = 0
        self.failed = 0
        self.correct = False
        self.checks = {}          # what `correct` compared: name -> (value, limit)
        self.window_clock = {}    # wall_s and thread_cpu_s of the measured loop
        self.notes = {}

    def open_window(self, t_open: float):
        """The window opens at `t_open`: set-up is what came before it, less
        the TPU runtime's start."""
        self.setup_s = t_open - self.t0 - self.setup_parts["runtime_start"]

    def span_ms(self, name: str):
        return [s.duration_ms for s in self.spans if s.name == name]


def start_run(cell: Cell, args, t0: float) -> Run:
    """What every loop does first: the imports' seconds since the process's
    start `t0`, then the first touch of the device, timed apart as
    `runtime_start`: the TPU runtime's start reads 6 to 22 s on one machine
    and one tree (PERF.md, section 6, PR 29 and PR 31) and no statement of
    the program shortens it, so `setup_s` leaves it out (metrics/setup_s.py)."""
    parts = {"import": time.perf_counter() - t0}    # jax, paddle_tpu, manifest
    t = time.perf_counter()
    device = device_facts(cell.chips)
    parts["runtime_start"] = time.perf_counter() - t
    run = Run(cell, args.seed, args.seconds, device)
    run.t0, run.setup_parts = t0, parts
    return run


def read_metrics(run: Run, group: str) -> dict:
    out = {}
    for m in run.cell.metrics[group]:
        reader = load_module("metrics", m["name"])
        for field in ("unit", "source"):
            if getattr(reader, field.upper()) != m[field]:
                raise SystemExit(f"metrics/{m['name']}.py and BENCHMARK.json "
                                 f"disagree on {field}")
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def emit(run: Run, traced: bool):
    """Facts for a reader of the log, then the one JSON line the driver reads."""
    dev = run.device
    info = {"cell": run.cell.name, "seed": run.seed,
            "device": f"{dev['platform']} {dev['kind']} x{dev['count']}",
            "compilations_in_window": run.compiles_in_window,
            "counted": run.attempted, "failed": run.failed,
            "setup_s": run.setup_s,
            "setup_parts": {k: round(v, 3) for k, v in run.setup_parts.items()},
            "notes": run.notes,
            "memory_stats": {k: v for k, v in
                             (dev["devices"][0].memory_stats() or {}).items()
                             if "bytes" in k}}
    print("benchmark: " + json.dumps(info), flush=True)
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"],
              "memory_peak_bytes": memory_peak_bytes(dev["devices"])}
    line = {"correct": bool(run.correct) and run.compiles_in_window == 0,
            "attempted": run.attempted, "failed": run.failed,
            "metrics": read_metrics(run, "per_layer" if traced else "end_to_end"),
            "device": device}
    if traced and run.trace is not None:
        from . import xplane
        lo, hi = run.trace.window()
        device["busy_s"] = run.trace.busy_seconds(lo, hi)
        device["window_s"] = hi - lo
        line["breakdown"] = {
            "device_ops": xplane.top(run.trace.op_seconds()),
            "idle_gaps": xplane.top(run.trace.idle_gaps_by_host_span())}
    # each number `correct` compared beside its limit: last in the line, and
    # the last lines of standard error
    checks = dict(run.checks, compilations_in_window=(run.compiles_in_window, 0),
                  failed=(run.failed, 0))
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in checks.items()}
    print(json.dumps(line), flush=True)
    for k, (v, lim) in checks.items():
        print(f"check {k}: {v} (limit {lim})", file=sys.stderr, flush=True)
