"""The one command: run one cell once and print its result line.

``python -m benchmarks.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>``

One process, which holds the chip. Everything that belongs to one
configuration, one traffic mix or one per-layer metric is a file of its own,
found by the name ``BENCHMARK.json`` gives it:

- ``configs/<config>.json``: the deployment, its sizes and its driver;
- ``traffic/<traffic>.json``: the mix's parameters, read by one generator (``gen.py``);
- ``drivers/<driver>.py``: set-up, window and comparison for one kind of deployment;
- ``layers/<metric>.py``: ``read(run) -> number or None`` for one per-layer metric;
- ``programs/*.json``: device program name prefixes -> layer, for the trace reduction.

``--check`` validates the manifest and exits. ``--rehearse`` shrinks the sizes by
the configuration's factor and runs on the CPU, for the sandbox only: its output
says ``"platform": "cpu"`` and none of its numbers is a device number.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # process start, as near as Python lets us read it

import argparse  # noqa: E402
import contextlib  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
MANIFEST = "BENCHMARK.json"

from benchmarks import manifest_check, trace_reduce  # noqa: E402


class CompileMeter:
    """XLA compilations, from jax's own monitoring events. A persistent-cache
    hit counts as one, so a warm run shows the same count."""

    BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax

        self.compilations = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event: str, _seconds: float, **_kw) -> None:
        if event == self.BACKEND_COMPILE:
            self.compilations += 1


class CompileNames(logging.Handler):
    """Which programs compiled inside the window, and for which shapes: jax
    says so on its own logger while ``jax_log_compiles`` is on."""

    LOGGER = "jax._src.interpreters.pxla"

    def __init__(self) -> None:
        super().__init__(level=logging.DEBUG)
        self.names: list = []

    def emit(self, record: logging.LogRecord) -> None:
        message = record.getMessage()
        if message.startswith("Compiling"):
            self.names.append(message.split(". Argument mapping")[0][:400])


class Run:
    """What a driver is handed, and what the per-layer readers read afterwards."""

    def __init__(self, cell: dict, config: dict, traffic: dict, seed: int,
                 seconds: float, trace: bool, rehearse: bool) -> None:
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds = seed, seconds
        self.trace, self.rehearse = trace, rehearse
        factor = config.get("rehearse_factor", 1) if rehearse else 1
        #: the configuration's sizes as run (divided by its factor in a rehearsal)
        keep = config.get("rehearse_keep", ())
        self.sizes = {k: int(v) if k in keep else max(int(v) // factor, 1)
                      for k, v in config["sizes"].items()}
        self.meter: CompileMeter | None = None
        self.device: dict = {}
        self.keep_events: str | None = None
        self.setup_s: float | None = None
        self.window_compilations: int | None = None
        self.memory_peak_bytes = 0
        self.spans: list = []  # (name, start_s, end_s) on perf_counter
        self.counters: dict = {}  # filled by the driver: deltas over the window
        self.facts: dict = {}  # filled by the driver: sizes and counts as run
        self.trace_dir = os.path.join(ROOT, ".bench_trace")
        self.traced: dict | None = None  # the reduced trace, once read
        self._tracing = None  # the traced window's annotation, while it is open
        self._compiles_at_open = 0
        self.compile_names = CompileNames()

    # -- spans: on the host clock, and in the profiler's trace under the same name
    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                self.spans.append((name, t0, time.perf_counter()))

    def span_seconds(self, name: str, since: float = 0.0) -> float:
        return sum(e - s for n, s, e in self.spans if n == name and s >= since)

    # -- the window's edges
    def window_opens(self) -> float:
        """Set-up ends here. Returns the window's start on perf_counter."""
        import jax

        jax.config.update("jax_log_compiles", True)
        logging.getLogger(CompileNames.LOGGER).addHandler(self.compile_names)
        now = time.perf_counter()
        self.setup_s = now - T_START
        self._compiles_at_open = self.meter.compilations
        return now

    def window_closed(self) -> None:
        """Read what must be read before the reference runs."""
        import jax

        self.window_compilations = self.meter.compilations - self._compiles_at_open
        jax.config.update("jax_log_compiles", False)
        logging.getLogger(CompileNames.LOGGER).removeHandler(self.compile_names)
        peak = 0
        for d in jax.local_devices():
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        self.memory_peak_bytes = peak

    # -- the profiler, for a few seconds of a --trace 1 run
    def start_trace(self) -> None:
        import jax

        if not self.trace or self._tracing is not None:
            return
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        # the traced window, as a span of its own on the trace's clock
        self._tracing = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
        self._tracing.__enter__()

    def stop_trace(self) -> None:
        import jax

        if self._tracing is not None:
            self._tracing.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self._tracing = None


def _load_json(*parts) -> dict:
    with open(os.path.join(*parts), encoding="utf-8") as f:
        return json.load(f)


def load_cell(name: str, manifest: str = MANIFEST):
    """The manifest (a path from the root of the checkout), the cell's entry
    in it, and the cell's configuration and traffic files."""
    man = _load_json(ROOT, manifest)
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise SystemExit(f"no cell {name!r} in {manifest}; cells: {sorted(cells)}")
    cell = cells[name]
    config_entry = next(c for c in man["configs"] if c["name"] == cell["config"])
    config = _load_json(ROOT, config_entry["file"])
    traffic = _load_json(HERE, "traffic", f"{cell['traffic']}.json")
    return man, cell, config, traffic


def load_reader(metric: str):
    """``layers/<metric>.py``: a metric's name may hold dots, so load by path."""
    path = os.path.join(HERE, "layers", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks_layer_" + metric.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def reports(metric: dict, cell: str, man: dict) -> bool:
    """Whether ``cell`` reports ``metric``: by its ``workloads`` key, else (a
    per-layer metric) wherever the end-to-end metric it moves is reported."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        moved = next(m for m in man["end_to_end"] if m["name"] == metric["moves"])
        return reports(moved, cell, man)
    return True


def ensure_native() -> None:
    """Build ``csrc/`` where the native libraries are missing; a library that
    then does not load is a failure, not a slower path taken in silence."""
    build = os.path.join(ROOT, "csrc", "build")
    if not glob.glob(os.path.join(build, "*.so")):
        if shutil.which("g++") is None:
            raise SystemExit("no native libraries and no g++ to build them")
        subprocess.run(["sh", os.path.join(ROOT, "csrc", "build.sh")], check=True,
                       timeout=600, stdout=subprocess.DEVNULL)
    from surge_tpu.log import native_gate, segment
    from surge_tpu.store import native as store_native

    live = {"store": store_native.native_available(),
            "segment": segment.native_codec_available(),
            "txn": native_gate.available()}
    if not all(live.values()):
        raise SystemExit(f"native library failed to load: {live}")


def prepare(cell: dict, config: dict, traffic: dict, seed: int, seconds: float,
            trace: bool, rehearse: bool) -> "Run | None":
    """Find the chips the cell asks for, place the compile cache, build the
    native libraries. None (and a word on stderr) where the cell cannot run."""
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        import jax

        import surge_tpu  # noqa: F401 — the system under test
    except ImportError as e:
        print(f"cannot import the system under test: {e}", file=sys.stderr)
        return None
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"JAX found no device: {e}", file=sys.stderr)
        return None
    want = "cpu" if rehearse else "tpu"
    if devices[0].platform != want or len(devices) < cell["chips"]:
        print(f"cell {cell['name']} needs {cell['chips']} x {want}; JAX found "
              f"{len(devices)} x {devices[0].platform} "
              f"({devices[0].device_kind})", file=sys.stderr)
        return None
    # every program, however quick to compile, goes into the persistent cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from surge_tpu.replay.engine import ensure_compile_cache

    ensure_compile_cache()
    ensure_native()
    run = Run(cell, config, traffic, seed, seconds, trace, rehearse)
    run.meter = CompileMeter()
    run.device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices)}
    return run


def emit(run: Run, man: dict, outcome: dict, device: dict) -> int:
    """Per-layer readers, the compared numbers on stderr, the result line."""
    cell = run.cell["name"]
    values = dict(outcome["metrics"])
    values["setup_s"] = run.setup_s
    metrics = {}
    breakdown = None
    if run.trace:
        pb = trace_reduce.find_pb(run.trace_dir)
        if pb is not None:
            events = trace_reduce.read_events(pb, {n for n, _s, _e in run.spans})
            if run.keep_events:
                with open(run.keep_events, "w", encoding="utf-8") as f:
                    json.dump(events, f)
            run.traced = trace_reduce.reduce(events, trace_reduce.load_programs(
                os.path.join(HERE, "programs")))
        shutil.rmtree(run.trace_dir, ignore_errors=True)
        if run.traced is not None:
            device["busy_s"] = run.traced["busy_s"]
            device["window_s"] = run.traced["window_s"]
            breakdown = {"device_ops": run.traced["device_ops"][:10],
                         "idle_gaps": run.traced["idle_gaps"][:10]}
        for m in man["per_layer"]:
            if reports(m, cell, man):
                value = load_reader(m["name"])(run)
                if value is not None:
                    metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        for m in man["end_to_end"]:
            if reports(m, cell, man):
                metrics[m["name"]] = {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}
    compared = {name: {"value": value, "limit": limit}
                for name, value, limit in outcome["compared"]}
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    line = {"correct": correct, "attempted": outcome["attempted"],
            "failed": outcome["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
        line["unmapped_programs"] = run.traced["unmapped"]
    line["window_compilations"] = run.window_compilations
    line["compared"] = compared
    print(f"window_compilations: {run.window_compilations}", flush=True)
    for name in run.compile_names.names:
        print(f"in-window compile: {name}", flush=True)
    sys.stdout.flush()
    for note in outcome.get("notes", []):
        print(f"note: {note}", file=sys.stderr)
    for name, c in compared.items():
        print(f"compared {name}={c['value']} limit={c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m benchmarks.run")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="sandbox only: the configuration's sizes divided by "
                         "its rehearse factor, on the CPU backend")
    ap.add_argument("--keep-trace-events", metavar="FILE",
                    help="with --trace 1: also write the few trace events the "
                         "reduction read, as JSON (how fixtures/ was recorded)")
    ap.add_argument("--check", action="store_true",
                    help="validate the manifest and the files it names, exit")
    ap.add_argument("--manifest", default=MANIFEST, metavar="FILE",
                    help="the manifest to read in place of BENCHMARK.json, as "
                         "a path from the root: a builder's, for cells that "
                         "wait under benchmarks/staged/")
    args = ap.parse_args(argv)

    errors = manifest_check.check(ROOT, args.manifest)
    if errors:
        for e in errors:
            print(f"{args.manifest}: {e}", file=sys.stderr)
        return 2
    if args.check:
        print(f"{args.manifest}: ok")
        return 0
    if not args.workload:
        ap.error("--workload is required")
    man, cell, config, traffic = load_cell(args.workload, args.manifest)
    seconds = args.seconds if args.seconds is not None else man["run_seconds"]

    run = prepare(cell, config, traffic, args.seed, seconds, bool(args.trace),
                  args.rehearse)
    if run is None:
        return 3
    run.keep_events = args.keep_trace_events
    device = dict(run.device)
    try:
        outcome = importlib.import_module(
            f"benchmarks.drivers.{config['driver']}").run(run)
    finally:
        run.stop_trace()
    device["memory_peak_bytes"] = run.memory_peak_bytes
    return emit(run, man, outcome, device)


if __name__ == "__main__":
    sys.exit(main())
