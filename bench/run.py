"""Chip benchmark of the region-discharge mincut/maxflow solver.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the accelerator it is started on:
generates the cell's instances from the seed, warms every program the
window uses, drives the cell's load loop for ``--seconds`` (the window ends
when the last request started inside it finishes), checks every answer
against the plain reference in ``bench/reference``, and prints one JSON
line last on standard output.  With ``--trace 1`` it profiles a slice of
the window and reports the cell's per-layer metrics instead of its
end-to-end ones.

Everything is found by name: the configuration in
``bench/configs/<config>.json`` (whose ``family`` names its generator,
``bench/generators/<family>.py``, and whose ``partition.splits`` set the
grid's dimension), the traffic in ``bench/traffic/<traffic>.json`` (whose
``loop`` names a load loop of ``bench/loops.py``), and each metric's reader
in ``bench/metrics/<metric>.py``.  In a traced run the program's own spans
(``repro.core.spans``, where the program has them) are recorded over the
window and handed to the readers as ``Run.program_spans``.  Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero before
measuring.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if sys.path and Path(sys.path[0]).resolve() == BENCH:
    sys.path[0] = str(ROOT)     # bench/trace.py must not shadow stdlib trace
SPAN_NAMES = ("prepare", "update", "solve", "solve_many")
# the traced slice: requests that start after this share of the window,
# until at least TRACE_MIN_S seconds and TRACE_MIN_REQUESTS have been traced
TRACE_FROM = 0.25
TRACE_MIN_S = 4.0
TRACE_MIN_REQUESTS = 2


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_metric(name: str, bench: Path = BENCH):
    """The reader of metric ``name``: ``bench/metrics/<name>.py``'s
    ``read(run) -> float | None``."""
    path = bench / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(benchmark: dict, cell: str, traced: bool) -> list[dict]:
    """The metrics this cell reports: the end-to-end ones untraced, the
    per-layer ones traced; a metric with ``workloads`` only in those."""
    group = benchmark["per_layer" if traced else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


class Spans:
    """Host-clock spans around the calls into the program's layers; in a
    traced run each is also a profiler ``TraceAnnotation``."""

    @dataclasses.dataclass
    class Span:
        seconds: float = 0.0

    def __init__(self):
        self.annotate = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax

        span = Spans.Span()
        t0 = time.perf_counter()
        if self.annotate:
            with jax.profiler.TraceAnnotation(name):
                yield span
        else:
            yield span
        span.seconds = time.perf_counter() - t0


class CompileCounter:
    """Counts, by function name, the programs built (compiled, or loaded
    from the persistent cache) before the window, and those compiled and
    loaded in it.

    A program compiled in the window under a name that set-up never built
    is a shape that set-up failed to warm.  One that set-up did build,
    compiled again, is the program making a new ``jax.jit`` per call: a
    cost its users pay, which the window keeps."""

    def __init__(self, jax):
        self.on = False             # in the window
        self.setup_names: set = set()
        self.compiles: dict = {}    # name -> compiles in the window
        self.cache_loads = 0        # programs the cache served, window
        self._hit = False
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, fun_name="?", **kw):
        if event != "/jax/core/compile/backend_compile_duration":
            return
        hit, self._hit = self._hit, False
        if not self.on:
            self.setup_names.add(fun_name)
        elif hit:
            self.cache_loads += 1
        else:
            self.compiles[fun_name] = self.compiles.get(fun_name, 0) + 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self._hit = True        # the backend-compile event follows

    @property
    def unwarmed(self) -> int:
        """Compiles in the window of programs that set-up never built."""
        return sum(n for name, n in self.compiles.items()
                   if name not in self.setup_names)


@dataclasses.dataclass
class Context:
    """What a load loop gets: the configuration, traffic, seed, the
    session ``Solver``, the span recorder and the checkout's ``bench``
    directory, where the generators are found."""

    jax: object
    config: dict
    traffic: dict
    seed: int
    solver: object
    spans: Spans
    bench: Path = BENCH


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read about one run."""

    cell: str
    config: dict
    setup_s: float
    window_s: float
    requests: list                  # loops.Request of the window
    compiles: int                   # XLA compiles inside the window
    cache_misses: int               # Solver.cache_info().misses, window
    instances: dict                 # answer key -> instance dict
    peaks: object                   # peaks.ChipPeaks of the device
    trace: dict | None = None       # trace.reduce() of the traced slice
    traced: list = dataclasses.field(default_factory=list)  # its requests
    # repro.core.spans records inside the window, in a traced run
    program_spans: list = dataclasses.field(default_factory=list)

    def part(self, inst):
        from bench import families

        return families.partition(self.config, inst)


def _process_start() -> float:
    """Wall-clock time at which this process started (interpreter start
    included), or the import of this module where that is unknown."""
    try:
        import psutil

        return min(psutil.Process().create_time(), T_START)
    except (ImportError, OSError):
        return T_START


def is_tpu(devices) -> bool:
    return all(d.platform == "tpu" for d in devices)


def _setup_jax(root: Path):
    """JAX with its persistent compilation cache at a fixed path inside
    the checkout, and JAX's own rules for what the cache keeps, as the
    program's entry points leave them (``repro/launch/cache.py``)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      str(root / "bench" / ".jax_cache"))
    return jax


def _close_cache(jax):
    """From here on the persistent cache neither serves nor keeps a
    program.  The window runs on what set-up built; a program that the
    solver builds anew on every call compiles on every call, in every run
    alike.  (JAX keeps a program only once one of its compiles has taken a
    second, so with the cache open a program that compiles in about half
    of one would be compiled in some checkouts and loaded in others.)"""
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()


def check_answers(answers, instances) -> dict:
    """Compare every answer with the reference: counts of flow values and
    cuts that differ, and of answers that could not be checked."""
    from bench.reference import min_cut

    ref_cache: dict = {}
    flow_bad = cut_bad = 0
    for flow, source, key in answers:
        if key not in ref_cache:
            ref_cache[key] = min_cut(instances[key])
        ref_flow, ref_source = ref_cache[key]
        flow_bad += int(flow != ref_flow)
        cut_bad += int(source.shape != ref_source.shape
                       or bool((source != ref_source).any()))
    return dict(flow_mismatches=flow_bad, cut_mismatches=cut_bad)


class Tracer:
    """Profiles one slice of the window: the requests that start after
    ``TRACE_FROM`` of it, until the slice holds ``TRACE_MIN_REQUESTS`` and
    spans ``TRACE_MIN_S``.  Each traced request is wrapped in a
    ``request`` annotation, which ``bench/trace.py`` reads as the slice."""

    def __init__(self, jax, spans):
        self.jax, self.spans = jax, spans
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.state = "before"       # before -> tracing -> done

    def wants(self, elapsed, seconds, traced) -> bool:
        if self.state == "before" and elapsed >= TRACE_FROM * seconds:
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            self.jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.state = "tracing"
            self.spans.annotate = True
        if self.state == "tracing" and len(traced) >= TRACE_MIN_REQUESTS \
                and traced[-1].t1 - traced[0].t0 >= TRACE_MIN_S:
            self.stop()
        return self.state == "tracing"

    def stop(self):
        if self.state == "tracing":
            self.jax.profiler.stop_trace()
            self.state = "done"
            self.spans.annotate = False

    def reduce(self, span_names) -> dict:
        from bench import trace

        try:
            return trace.reduce(trace.find_xplane(self.dir), span_names)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def _request(loop, i, tracer, err):
    """One request of the window; a request that raises is a failed one."""
    import jax

    t0 = time.perf_counter()
    try:
        if tracer is None:
            return loop.request(i)
        with jax.profiler.TraceAnnotation("request"):
            return loop.request(i)
    except Exception:               # the run goes on; the failure counts
        traceback.print_exc(file=err)
        return loop.failed(t0, time.perf_counter())


def run(argv=None, *, accept_devices=is_tpu, root: Path = ROOT,
        out=sys.stdout, err=sys.stderr) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    benchmark = load_benchmark(root)
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}; cells: {sorted(cells)}",
              file=err)
        return 2
    cell = cells[args.workload]
    bench = root / "bench"
    config = load_json(bench / "configs" / f"{cell['config']}.json")
    traffic = load_json(bench / "traffic" / f"{cell['traffic']}.json")
    metrics = [(m, load_metric(m["name"], bench))
               for m in cell_metrics(benchmark, cell["name"], bool(args.trace))]

    jax = _setup_jax(root)
    devices = jax.devices()
    if not accept_devices(devices) or len(devices) < cell["chips"]:
        print(f"no accelerator for this cell: JAX found {len(devices)} "
              f"{devices[0].platform} device(s), the cell needs "
              f"{cell['chips']} TPU chip(s)", file=err)
        return 3
    sys.path.insert(0, str(ROOT / "src"))      # the program under test
    from bench import loops, peaks
    from repro.core import Solver, SolverOptions
    try:
        from repro.core import spans as program_spans
    except ImportError:             # a program without spans records none
        program_spans = None

    dev = devices[0]
    chip_peaks = peaks.peaks_for(dev.device_kind) \
        if dev.platform == "tpu" else None
    counter = CompileCounter(jax)
    spans = Spans()
    solver = Solver(SolverOptions(**config["solver"]))
    ctx = Context(jax, config, traffic, args.seed, solver, spans, bench)
    loop = loops.LOOPS[traffic["loop"]](ctx)
    setup_s = time.time() - _process_start()

    requests: list = []
    traced: list = []
    misses0 = solver.cache_info().misses
    tracer = Tracer(jax, spans) if args.trace else None
    recording = bool(tracer and program_spans)
    _close_cache(jax)
    with (program_spans.recording() if recording
          else contextlib.nullcontext([])) as records:
        counter.on = True
        t0 = time.perf_counter()
        try:
            while not requests or time.perf_counter() - t0 < args.seconds:
                if tracer and tracer.wants(time.perf_counter() - t0,
                                           args.seconds, traced):
                    traced.append(_request(loop, len(requests), tracer, err))
                    requests.append(traced[-1])
                else:
                    tracer and tracer.stop()
                    requests.append(_request(loop, len(requests), None, err))
        finally:
            counter.on = False
            tracer and tracer.stop()
        if tracer and not traced:   # no request started inside the slice:
            tracer.state = "before"  # trace one more, after the window
            tracer.wants(args.seconds, args.seconds, traced)
            traced.append(_request(loop, len(requests), tracer, err))
            tracer.stop()
    window_s = requests[-1].t1 - t0
    cache_misses = solver.cache_info().misses - misses0
    stats = [d.memory_stats() or {} for d in devices[:cell["chips"]]]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)

    lo, hi = requests[0].t0, requests[-1].t1
    in_window = [r for r in records
                 if r.start_ns * 1e-9 >= lo and r.end_ns * 1e-9 <= hi]
    trace = tracer.reduce(SPAN_NAMES + tuple(sorted(
        {r.name for r in records}))) if tracer else None

    warm_answers, instances = loop.expected()
    del loop, solver, ctx
    done = requests + [r for r in traced if r not in requests]
    answers = warm_answers + [a for r in done for a in r.answers]
    attempted = sum(r.cuts for r in requests)
    failed = sum(r.failed for r in done)
    checks = check_answers(answers, instances)
    checks["unwarmed_compiles"] = counter.unwarmed
    checks["failed"] = failed
    compiles = sum(counter.compiles.values())
    limits = {k: 0 for k in checks}
    correct = all(checks[k] <= limits[k] for k in checks)

    record = Run(cell["name"], config, setup_s, window_s, requests,
                 compiles, cache_misses, instances, chip_peaks, trace, traced,
                 in_window)
    values = {}
    for m, read in metrics:
        v = read(record)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell["chips"], "memory_peak_bytes": peak}
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": values, "device": device}
    if trace is not None:
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    line["compiles_in_window"] = counter.compiles
    print(json.dumps({"cuts_checked": len(answers),
                      "window_requests": len(requests),
                      "compiles_in_window": counter.compiles,
                      "program_spans": program_spans.summary(in_window)
                      if recording else None,
                      "cache_loads_in_window": counter.cache_loads,
                      "moved_share": _moved_share(requests),
                      "trace_lines": trace and trace["lines"]}), file=err)
    line["checks"] = {k: {"value": checks[k], "limit": limits[k]}
                      for k in checks}
    for k in checks:
        print(f"check {k} {checks[k]} limit {limits[k]}", file=err)
    print(json.dumps(line), file=out, flush=True)
    return 0


def _moved_share(requests) -> float:
    """Share of the window's cuts that took at least one sweep."""
    cuts = [s for r in requests for s in r.sweeps]
    return sum(s > 0 for s in cuts) / max(1, len(cuts))


if __name__ == "__main__":
    sys.exit(run())
