"""One run of one benchmark cell: set-up, window, check, result line.

Everything that belongs to one configuration, traffic mix, metric, kernel,
deployment layout or reference sits in a file of its own under this
directory, found by the name ``BENCHMARK.json`` gives it:

    configs/<config>.json     sizes and guarantees of a deployment
    deploy/<layout>.py        stands the deployment up on the chips
    reference/<name>.py       its plain reference
    traffic/<mix>.json        parameters of a traffic mix
    loops/<loop>.py           the client loop a mix names
    metrics/<metric>.py       reads one metric from the run
    kernels/<kernel>.py       a kernel's trace name and the work it does
    peaks.json                the chips' peaks, by device kind

From the program the benchmark takes the system under test (its index
build, ``GeneSearchService`` and ``AsyncScheduler``) and what it records:
counters, spans and the names its kernels carry in a device trace.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import pathlib
import queue
import shutil
import sys
import threading
import time
from typing import Optional

import numpy as np

from bench import synth

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench" / "trace"
ANSWER_WAIT_S = 60.0       # an answer later than this past the close is lost
WINDOW_MARK = "bench.window"
TRACE_S = 8.0              # a traced run profiles the window's last seconds
PROFILER_LEAD_S = 1.0      # the profiler starts this long before its mark


class Refused(Exception):
    """The run cannot measure: no result is printed, the exit code is 2."""


def load_json(path: pathlib.Path):
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    path = BENCH / kind / f"{name}.py"
    if not path.exists():
        raise Refused(f"no {kind} named {name!r} ({path} is missing)")
    key = f"bench_{kind}_{name}".replace(".", "_").replace("-", "_")
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def cell_of(spec: dict, name: str, rehearse: bool = False) -> Cell:
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise Refused(f"no workload {name!r} in BENCHMARK.json "
                      f"(have {sorted(work)})")
    w = work[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = load_json(ROOT / conf["file"])
    traffic = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    if rehearse:
        config = {**config, **config.get("rehearsal", {})}
        traffic = {**traffic, **traffic.get("rehearsal", {})}

    def here(metric, e2e_names=None):
        cells = metric.get("workloads")
        if cells is not None:
            return name in cells
        return e2e_names is None or metric["moves"] in e2e_names

    e2e = [m for m in spec["end_to_end"] if here(m)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"] if here(m, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=layer)


class CompileClock:
    """Counts JAX's compile events: programs the backend built or loaded
    from the persistent cache (JAX reports both as a backend compile),
    the loads among them, and traces of a Python function."""

    EVENTS = {"/jax/core/compile/backend_compile_duration": "programs",
              "/jax/compilation_cache/cache_retrieval_time_sec":
                  "cache_loads",
              "/jax/core/compile/jaxpr_trace_duration": "traces"}

    def __init__(self):
        import jax
        self.counts = {v: 0 for v in self.EVENTS.values()}
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        kind = self.EVENTS.get(event)
        if kind is not None:
            self.counts[kind] += 1
            if kind == "programs":
                self.compile_s += duration

    @staticmethod
    def line(counts: dict) -> str:
        return (f"{counts['programs'] - counts['cache_loads']} compiles, "
                f"{counts['cache_loads']} programs loaded from the cache, "
                f"{counts['traces']} traces")


class WindowTrace:
    """Profiles the window's last ``TRACE_S`` seconds with ``jax.profiler``.

    A thread of its own starts the profiler ``PROFILER_LEAD_S`` before the
    traced span, marks the span with a ``TraceAnnotation`` named
    ``WINDOW_MARK`` and records its bounds on the host clock, so that the
    client's loop is never held up by the profiler's start. The profiler
    stops in ``stop``, once every answer of the window is in."""

    def __init__(self, seconds: float):
        self.length = min(TRACE_S, seconds)
        self.offset = seconds - self.length
        self.span: Optional[tuple] = None
        self._thread: Optional[threading.Thread] = None

    def start(self, t_open: float) -> None:
        self._thread = threading.Thread(target=self._run, args=(t_open,),
                                        name="bench-trace", daemon=True)
        self._thread.start()

    def _run(self, t_open: float) -> None:
        import jax
        t_mark = t_open + self.offset
        _sleep_until(t_mark - PROFILER_LEAD_S)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        # the host's runtime events only: the Python tracer would record
        # every call of the client, the scheduler and the planner, which
        # slows the host the window measures and fills the trace
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=options)
        _sleep_until(t_mark)
        with jax.profiler.TraceAnnotation(WINDOW_MARK):
            t0 = time.monotonic()
            _sleep_until(t_mark + self.length)
            self.span = (t0, time.monotonic())

    def stop(self) -> None:
        import jax
        self._thread.join()
        jax.profiler.stop_trace()


def _sleep_until(t: float) -> None:
    wait = t - time.monotonic()
    if wait > 0:
        time.sleep(wait)


@dataclasses.dataclass
class Window:
    """What the client saw between the window's open and close."""

    clock: CompileClock
    registry: object
    trace: Optional[WindowTrace] = None
    t_open: Optional[float] = None
    t_close: Optional[float] = None
    requests: list = dataclasses.field(default_factory=list)
    batches: list = dataclasses.field(default_factory=list)
    counters: tuple = ({}, {})
    compiles: dict = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        self.batch_events: queue.SimpleQueue = queue.SimpleQueue()
        self._completer = threading.local()

    def open(self, t: float) -> None:
        self.t_open = t
        self._c0 = dict(self.clock.counts)
        self._s0 = self.registry.snapshot()
        if self.trace is not None:
            self.trace.start(t)

    def close(self, t: float) -> None:
        self.t_close = t
        self.counters = (self._s0, self.registry.snapshot())
        self.compiles = {k: v - self._c0[k]
                         for k, v in self.clock.counts.items()}

    def on_batch(self, stats, now: float) -> None:
        """The scheduler's hook, called on its completer thread for each
        batch just before the batch's futures are resolved: notes the
        batch's completion for the loop and its dispatch time for the
        requests it answers."""
        self._completer.dispatched = now - stats.wall_ms * 1e-3
        self.batch_events.put((now, stats.n_requests))

    def resolved(self, req, done: queue.SimpleQueue, fut) -> None:
        req.done = time.monotonic()
        # set on the completer thread; a future resolved before its
        # callback was added runs this on the client's thread instead
        req.dispatched = getattr(self._completer, "dispatched", None)
        try:
            req.answer = fut.result().file_ids
        except Exception as e:  # noqa: BLE001 - a failed request is recorded
            req.error = repr(e)
        done.put(req)


@dataclasses.dataclass
class Context:
    """What a metric's reader reads."""

    cell: Cell
    window: Window
    setup_s: float
    peak: dict
    trace: Optional[object] = None       # xplane.Reduced, with --trace 1

    def counter(self, name: str, **where) -> float:
        from repro.obs import metrics
        s0, s1 = self.window.counters
        return (metrics.counter_total(s1, name, where or None)
                - metrics.counter_total(s0, name, where or None))

    def kernel(self, name: str):
        return load_module("kernels", name)


def check_devices(chips: int, allow_cpu: bool):
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not allow_cpu:
        raise Refused(f"found no TPU: JAX's devices are {platform} devices")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found "
                      f"{len(devices)}")
    peaks = load_json(BENCH / "peaks.json")
    kind = devices[0].device_kind
    if kind not in peaks and not allow_cpu:
        raise Refused(f"device kind {kind!r} is not in peaks.json")
    return devices[:chips], peaks.get(kind, {})


def warm(svc, cfg: dict, traffic: dict, genomes: np.ndarray,
         seed: int) -> int:
    """Serve, through the synchronous path that shares every compiled
    step with the scheduler, a batch of every shape the traffic uses:
    each kmer bucket its read lengths fall in, at the batch fills the
    mix names, with the shortest and the longest reads of the bucket.
    Returns the batches served."""
    k = cfg["k"]
    lengths = synth.read_lengths(traffic["read_lengths"])
    by_bucket: dict = {}
    for n in lengths.tolist():
        by_bucket.setdefault(svc.bucket_for(n - k + 1), []).append(n)
    stream = synth.ReadStream(genomes, traffic, seed, stream=1)
    served = 0
    for bucket in sorted(by_bucket):
        for fill in traffic["warm_fills"]:
            for n in sorted({min(by_bucket[bucket]),
                             max(by_bucket[bucket])}):
                reads = [r.read for r in stream.take(fill)]
                reads = [np.resize(r, n) for r in reads]
                svc.search(reads)
                served += 1
    return served


def prepare(cell: Cell, seed: int, allow_cpu: bool = False):
    """Set-up: the chips, the compile cache, the archive made from the
    seed, the deployment built on the chips and every shape warmed.
    Returns ``(devices, peak, clock, genomes, service)``."""
    cfg, traffic = cell.config, cell.traffic
    devices, peak = check_devices(cell.chips, allow_cpu)

    import jax
    from repro.launch.compile_cache import use_compile_cache

    cache = use_compile_cache()
    # every program goes to the cache, however fast it compiled, so that
    # only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    clock = CompileClock()
    shown = {k: v for k, v in traffic.items() if k != "rehearsal"}
    print(f"cell {cell.name}: config {cfg['name']}, traffic "
          f"{json.dumps(shown)}; {len(devices)} x "
          f"{devices[0].device_kind}; compile cache {cache}",
          file=sys.stderr, flush=True)

    genomes = synth.archive(cfg["n_files"], cfg["genome_len"], seed)
    t0 = time.monotonic()
    svc = load_module("deploy", cfg["deploy"]).build(
        cfg, genomes, devices, traffic["service"])
    build_s = time.monotonic() - t0
    n_warm = warm(svc, cfg, traffic, genomes, seed)
    print(f"set-up: index built in {build_s:.3f} s; {n_warm} warm-up "
          f"batches; {clock.line(clock.counts)} ({clock.compile_s:.3f} s "
          f"in the backend)", file=sys.stderr, flush=True)
    return devices, peak, clock, genomes, svc


def measure(args, t_start: float, *, allow_cpu: bool = False,
            rehearse: bool = False, fault=None) -> dict:
    """One run of ``args.workload``; returns the result line's object."""
    spec = load_json(ROOT / "BENCHMARK.json")
    cell = cell_of(spec, args.workload, rehearse)
    cfg, traffic = cell.config, cell.traffic
    seed = args.seed % (1 << 64)
    devices, peak, clock, genomes, svc = prepare(cell, seed, allow_cpu)

    from repro.obs import metrics as obs_metrics
    from repro.serving.scheduler import AsyncScheduler, SchedulerConfig

    tracing = bool(args.trace)
    window = Window(clock=clock, registry=obs_metrics.DEFAULT,
                    trace=WindowTrace(args.seconds) if tracing else None)
    sched = AsyncScheduler(svc, SchedulerConfig(**traffic["scheduler"]),
                           on_batch=window.on_batch)
    if fault is not None:
        sched = fault(sched, cfg)
    stream = synth.ReadStream(genomes, traffic, seed)
    load_module("loops", traffic["loop"]).run(
        sched, stream, traffic, args.seconds, window, seed)
    setup_s = window.t_open - t_start

    # every request that was sent is due; wait for each, a minute past the
    # close at most
    deadline = window.t_close + ANSWER_WAIT_S
    for req in window.requests:
        while req.done is None and time.monotonic() < deadline:
            time.sleep(0.005)
        if req.done is None:
            req.done = time.monotonic()
            req.error = "no answer within a minute of the window's close"
    if tracing:
        window.trace.stop()
    print(f"window: {window.t_close - window.t_open:.6f} s; in the window "
          f"{clock.line(window.compiles)}", flush=True)
    stats = [d.memory_stats() or {} for d in devices]
    memory_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    sched.close()
    del sched, svc
    gc.collect()

    ctx = Context(cell=cell, window=window, setup_s=setup_s, peak=peak)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    breakdown = None
    if tracing:
        from bench import xplane
        path = xplane.find(TRACE_DIR)
        if args.keep_trace:
            dest = pathlib.Path(args.keep_trace)
            dest.mkdir(parents=True, exist_ok=True)
            shutil.copy(path, dest / path.name)
        ctx.trace = xplane.reduce(path, WINDOW_MARK,
                                  [d.id for d in devices])
        ctx.trace.span = window.trace.span
        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = ctx.trace.window_s
        breakdown = ctx.trace.breakdown()
        metrics_spec = cell.per_layer
        # what tracing costs: the end-to-end numbers of this traced run,
        # which are not reported, and the p95 inside and before the span
        for m in cell.end_to_end:
            value = load_module("metrics", m["name"]).read(ctx)
            print(f"traced run, not reported: {m['name']} {value}",
                  file=sys.stderr)
        before = _p95(window.requests, window.t_open, ctx.trace.span[0])
        print(f"traced run, not reported: latency p95 ms before the traced "
              f"span {before}, inside it "
              f"{_p95(window.requests, *ctx.trace.span)}", file=sys.stderr)
    else:
        metrics_spec = cell.end_to_end
    metrics = {}
    for m in metrics_spec:
        value = load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    check = compare(cell, genomes, window.requests, seed,
                    control=getattr(args, "control", False))
    failed = sum(1 for r in window.requests if r.error is not None)
    correct = all(v["value"] <= v["limit"] for v in check.values())
    result = dict(correct=correct, attempted=len(window.requests),
                  failed=failed, metrics=metrics, device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = check
    return result


def _p95(requests: list, t0: float, t1: float):
    """p95 of the latency of the requests sent in ``[t0, t1)``, from their
    scheduled arrival where they have one."""
    lat = [(r.done - (r.scheduled or r.sent)) * 1e3 for r in requests
           if t0 <= r.sent < t1]
    return float(np.percentile(lat, 95)) if lat else None


def compare(cell: Cell, genomes: np.ndarray, requests: list, seed: int, *,
            control: bool = False) -> dict:
    """Hold a sample of the served answers against the plain reference.

    The sample is drawn from the seed among every request that was due.
    ``control`` puts the reference, with the configuration's control break,
    in the program's place."""
    cfg = cell.config
    ref = load_module("reference", cfg["reference"])
    n = min(int(cell.traffic["check_sample"]), len(requests))
    pick = np.random.default_rng([seed, 4]).choice(len(requests), n,
                                                   replace=False)
    sample = [requests[i] for i in sorted(pick)]
    t0 = time.monotonic()
    archive = ref.Archive(cfg, genomes)
    want = archive.answers([r.read for r in sample], cfg["theta"])
    if control:
        got = archive.answers([r.read for r in sample],
                              cfg["control"]["theta"])
    else:
        got = [r.answer for r in sample]
    mismatched = missing = 0
    for g, w in zip(got, want):
        if g is None:
            missing += 1
        else:
            mismatched += len(set(g) ^ set(w))
    print(f"reference: {n} sampled requests of {len(requests)} checked in "
          f"{time.monotonic() - t0:.3f} s; {sum(len(w) > 0 for w in want)} "
          f"answered by some file", file=sys.stderr)
    return {"mismatched_answers": {"value": mismatched, "limit": 0},
            "missing_answers": {"value": missing, "limit": 0}}


def run(args, t_start: float) -> int:
    try:
        result = measure(args, t_start)
    except Refused as e:
        print(f"bench: refused: {e}", file=sys.stderr, flush=True)
        return 2
    check = result["check"]
    for name, v in check.items():
        print(f"{name}: {v['value']} (limit {v['limit']})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
