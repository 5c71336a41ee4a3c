"""Benchmark entry: one cell, one run, one result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run is one process.  It reads the cell from ``BENCHMARK.json``, finds
the cell's configuration (``bench/configs/<config>.json``), traffic mix
(``bench/traffic/<traffic>.json``) and per-layer metric readers
(``bench/metrics/<metric>.py``) by name (``bench/plugins.py``), and
hands them to the driver the configuration names
(``bench/drivers/<driver>.py``).  Then it

1. sets up: imports, the traffic pool from ``--seed``, the compile cache
   at a fixed path in the checkout, compilation and warm-up of exactly
   the shapes the window uses (``setup_s`` runs from process start to
   here);
2. measures for ``--seconds``: calls run back to back and the window
   closes when the first call completes at or after ``--seconds``, so
   every call in it is whole; compilations inside it are counted;
3. reads the device's peak memory, frees the program's state, and checks
   a sample of the window's answers against the plain reference;
4. prints the numbers compared, each beside its limit, as the last lines
   of standard error, and one JSON object as the last line of standard
   output.

With ``--trace 1`` the profiler records whole calls of the window, from
its first call until the first call that ends ``TRACE_MIN_S`` or more
after the recording began, and the result carries the cell's per-layer
metrics, ``device.busy_s`` / ``device.window_s`` (those calls', from the
first one's start to the last one's end, gaps between them included) and
a ``breakdown``; otherwise its end-to-end metrics.  The run refuses,
printing no result, when JAX finds no TPU, fewer chips than the cell
asks for, or Pallas kernels in interpret mode.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

from bench import plugins  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
#: JAX's persistent compilation cache: one fixed path in the checkout
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")
#: profiler output of ``--trace 1`` runs
TRACE_DIR = os.path.join(ROOT, ".bench_cache", "trace")
#: the profiler records whole calls, from the window's first until the
#: first that ends TRACE_MIN_S or more after the recording began
TRACE_MIN_S = 0.5


class Refused(Exception):
    """The run cannot be measured here; no result is printed."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def cell_plan(workload: str, bench: dict | None = None) -> dict:
    """Everything the harness needs for one cell, found by name."""
    bench = bench or load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(os.path.join(ROOT, conf["file"]))
    mix = load_json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))

    def reports(m):
        return "workloads" not in m or workload in m["workloads"]

    return {"cell": cell, "config": cfg,
            "mix": mix,
            "end_to_end": [m for m in bench["end_to_end"] if reports(m)],
            "per_layer": [m for m in bench["per_layer"] if reports(m)]}


def require_chips(n: int):
    """Refuse unless JAX sees at least ``n`` TPU chips and Pallas kernels
    compile (no interpret mode)."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < n:
        raise Refused(f"the cell needs {n} chips, JAX sees {len(devs)}")
    from repro.kernels.dispatch import resolve_interpret
    if resolve_interpret():
        raise Refused("Pallas kernels would run in interpret mode")


class Spans:
    """Host spans of the window: ``(name, start_s, end_s)``.  Under the
    profiler each span is also a ``TraceAnnotation`` named
    ``bench:<name>``, so the trace reduction can label idle gaps."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.spans: list[tuple[str, float, float]] = []

    def __call__(self, name: str):
        return _Span(self, name)


class _Span:
    def __init__(self, owner: Spans, name: str):
        self.owner, self.name, self.ann = owner, name, None

    def __enter__(self):
        if self.owner.annotate:
            import jax
            self.ann = jax.profiler.TraceAnnotation("bench:" + self.name)
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        self.owner.spans.append((self.name, self.t0, t1))
        return False


class CompileCounter:
    """Counts jaxpr traces and backend compilations while ``on``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.on = False
        self.counts = {e: 0 for e in self.EVENTS}
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, duration, **kw):
        if self.on and event in self.counts:
            self.counts[event] += 1

    @property
    def traces(self) -> int:
        return self.counts[self.EVENTS[0]]

    @property
    def compiles(self) -> int:
        return self.counts[self.EVENTS[1]]


def configure_jax():
    """Compile cache at the checkout's fixed path, every program kept."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # no eviction: the cache holds only this checkout's few programs, and
    # an eviction limit set in the environment made writes fail
    jax.config.update("jax_compilation_cache_max_size", -1)


def device_info(n_chips: int) -> dict:
    import jax
    devs = jax.devices()[:n_chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def read_layer_metrics(per_layer: list[dict], ctx: dict) -> dict:
    """Run each metric's reader; a reader that finds nothing returns
    None and the metric is left out of the line."""
    out = {}
    for m in per_layer:
        v = plugins.load("metrics", m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def reduce_trace(log_dir: str) -> dict:
    """Device busy time, slice length and breakdown of the traced calls.

    The slice runs from the start of the first host span in the trace to
    the end of the last: the traced calls, whole, with the host's work
    between their device programs.  Where the profiler dropped records
    (a call too long for its buffers), the slice ends at the last device
    operation it kept and is marked incomplete: the readers that need
    whole calls then find nothing to read."""
    from bench import trace as tr
    device_ops, spans, dropped = tr.load(log_dir)
    if not device_ops:
        raise RuntimeError("the trace holds no device operations")
    if not spans:
        raise RuntimeError("the trace holds no span of the benchmark")
    lo = min(s for s, _, _ in spans)
    hi = max(s + d for s, d, _ in spans)
    if dropped:
        hi = min(hi, max(float(o.end.max()) for o in device_ops.values()))
    busy = [tr.busy_ns(o, lo, hi) for o in device_ops.values()]
    tot: dict[str, float] = {}
    for o in device_ops.values():
        for k, v in tr.op_totals(o, lo, hi).items():
            tot[k] = tot.get(k, 0.0) + v / len(device_ops)
    first = next(iter(device_ops.values()))
    return {
        "busy_ns": sum(busy) / len(busy),
        "window_ns": hi - lo,
        "spans": spans,
        "device_ops": device_ops,
        "lo": lo, "hi": hi,
        "complete": not dropped,
        "breakdown": {
            "device_ops": [[k, v * 1e-9] for k, v in
                           sorted(tot.items(), key=lambda x: -x[1])[:10]],
            "idle_gaps": [[k, v * 1e-9] for k, v in
                          tr.idle_gaps(first, spans, lo, hi)],
        },
    }


class TracedCalls:
    """Starts the profiler before the window's first call and stops it
    after the first call that ends ``TRACE_MIN_S`` or more later, so the
    trace holds whole calls and the gaps between them."""

    def __init__(self, log_dir: str):
        import shutil
        shutil.rmtree(log_dir, ignore_errors=True)
        self.log_dir, self.t0, self.done = log_dir, None, False

    def before_call(self):
        if self.t0 is None:
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1      # the benchmark's annotations
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            self.t0 = time.perf_counter()

    def after_call(self):
        if not self.done and time.perf_counter() - self.t0 >= TRACE_MIN_S:
            self.stop()

    def stop(self):
        if self.t0 is not None and not self.done:
            import jax
            jax.profiler.stop_trace()
            self.done = True


def verdict(checks: dict) -> bool:
    """``correct``: there are numbers compared, and each is within its
    limit."""
    return bool(checks) and all(c["ok"] for c in checks.values())


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        plan: dict | None = None, check_chips: bool = True,
        driver_cls=None, log=print) -> dict:
    """One run of one cell; returns the result object.  ``driver_cls``
    puts another driver in the configuration's place (the control)."""
    plan = plan or cell_plan(workload)
    cell, cfg = plan["cell"], plan["config"]
    n_chips = int(cell["chips"])
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    configure_jax()
    if check_chips:
        require_chips(n_chips)
    log(f"set-up: jax and {n_chips} device(s) ready at "
        f"{time.perf_counter() - T_START:.2f} s", file=sys.stderr)
    driver = (driver_cls or plugins.load("drivers", cfg["driver"]).Driver)(
        cfg, plan["mix"], seed, n_chips, log=log)
    counter = CompileCounter()
    driver.setup()
    setup_s = time.perf_counter() - T_START
    log(f"set-up: done at {setup_s:.2f} s", file=sys.stderr)

    spans = Spans(annotate=trace)
    traced = TracedCalls(TRACE_DIR) if trace else None
    counter.on = True
    t_open = time.perf_counter()
    calls = events = 0
    while True:
        if traced:
            traced.before_call()
        events += driver.call(spans, split=trace)
        calls += 1
        if traced:
            traced.after_call()
        elapsed = time.perf_counter() - t_open
        if elapsed >= seconds:
            break
    counter.on = False
    if traced:
        traced.stop()
    log(f"window: {calls} calls, {events} events, {elapsed:.3f} s, "
        f"{counter.traces} traces and {counter.compiles} compilations "
        f"inside it", file=sys.stderr)

    device = device_info(n_chips)
    metrics, breakdown = {}, None
    if trace:
        try:
            red = reduce_trace(TRACE_DIR)
        except RuntimeError:
            if check_chips:
                raise
            red = None      # a CPU rehearsal: no device plane to read
        if red is not None:
            device["busy_s"] = red["busy_ns"] * 1e-9
            device["window_s"] = red["window_ns"] * 1e-9
            breakdown = red["breakdown"]
        ctx = {"spans": spans.spans, "trace": red,
               **driver.layer_context()}
        metrics = read_layer_metrics(plan["per_layer"], ctx)
    else:
        e2e = driver.end_to_end(events, elapsed)
        e2e["setup_s"] = setup_s
        for m in plan["end_to_end"]:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}

    driver.release()
    checks = driver.check()
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r}) "
            f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    result = {"correct": verdict(checks),
              "attempted": driver.attempted(), "failed": driver.failed(),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["window"] = {"seconds": elapsed, "events": events,
                        "traces": counter.traces,
                        "compiles": counter.compiles}
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    try:
        res = run(a.workload, a.seed, a.seconds, bool(a.trace))
    except Refused as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
