"""The benchmark harness: one cell, one process, one result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads ``BENCHMARK.json`` at the checkout's root and finds by name what the
cell names: its configuration (``bench/configs/<config>.json``), its
traffic mix (``bench/traffic/<mix>.json``), the entry the mix drives
(``bench/entries/<entry>.py``) and, with ``--trace 1``, a reader per
per-layer metric (``bench/metrics/<metric>.py``).  Adding a cell, a mix or
a metric therefore adds files and edits none.

A run checks for the chips the cell asks for and fails without them, sets
up (keys, traffic pool, sessions, every compile), runs a closed loop for
``--seconds`` (the next batch goes in when the previous call returns),
reads peak device memory, frees the program's state, and compares what
the timed path produced with the plain reference.  The last line of
standard output is one JSON object; the numbers compared also end standard
error, each beside its limit.

The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set, else
``.jax_cache/`` in the checkout.  Traces go to ``bench/traces/``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# libtpu would otherwise log to a fixed directory under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

import tracing  # noqa: E402


def log(**fields) -> None:
    print(json.dumps(fields), file=sys.stderr, flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def chip_devices(chips: int):
    """The TPU devices, or exit non-zero when fewer than ``chips``."""
    import jax
    try:
        devices = jax.devices("tpu")
    except RuntimeError as e:
        raise SystemExit(f"no TPU: {e}")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} TPU chips; JAX found "
                         f"{len(devices)}")
    return jax.devices()


def use_compile_cache() -> None:
    import jax
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts the compiles and traces JAX reports while ``active``, and
    sums the seconds of every compile-side event by name."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax.monitoring
        self.active = False
        self.count = 0
        self.seconds = collections.defaultdict(float)

        def listener(event, duration, **kwargs):
            if "compil" in event or "jaxpr" in event:
                self.seconds[event.rsplit("/", 1)[-1]] += duration
            if self.active and event in self.EVENTS:
                self.count += 1
        jax.monitoring.register_event_duration_secs_listener(listener)


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


def quantile(values, q: float) -> float:
    """``q`` in (0, 1) by ``statistics.quantiles`` (exclusive method)."""
    if len(values) == 1:
        return values[0]
    return statistics.quantiles(values, n=100)[round(q * 100) - 1]


def make_entry(config: dict, mix: dict, seed: int, spans):
    """The entry module the mix drives, and its entry object."""
    mod = load_module("entries", mix["entry"])
    cls = getattr(mod, mix["entry"].capitalize() + "Entry")
    return mod, cls(config, mix, seed, spans)


def set_up(entry) -> dict:
    """Run the entry's set-up; returns seconds by phase."""
    phases = {}

    @contextlib.contextmanager
    def phase(name):
        t0 = time.perf_counter()
        yield
        phases[name] = time.perf_counter() - t0

    entry.setup(phase)
    return phases


def measure(entry, spans, seconds: float, counter: "CompileCounter"):
    """The closed loop: calls completed inside ``seconds`` (latencies in
    seconds), the operations they carried, and compiles seen meanwhile."""
    entry.begin_window()
    counter.active = True
    latencies, ops = [], 0
    end = time.perf_counter() + seconds
    with spans.span(tracing.WINDOW_SPAN):
        while True:
            t0 = time.perf_counter()
            if t0 >= end:
                break
            with spans.span("bench.batch"):
                n = entry.call()
            t1 = time.perf_counter()
            if t1 <= end:
                latencies.append(t1 - t0)
                ops += n
    counter.active = False
    return latencies, ops, counter.count


def run_cell(spec: dict, cell: dict, config: dict, mix: dict, *, seed: int,
             seconds: float, trace: bool, devices) -> dict:
    """Set up, measure, check; returns the result object."""
    import jax
    spans = tracing.Spans(annotate=trace)
    entry_mod, entry = make_entry(config, mix, seed, spans)
    counter = CompileCounter()
    phases = set_up(entry)
    trace_dir = os.path.join(HERE, "traces", cell["name"])
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # host TraceMe spans only
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    setup_s = time.perf_counter() - T_START
    log(setup_phases_s=phases, setup_s=setup_s,
        setup_compile_s=dict(counter.seconds))

    latencies, ops, compiles = measure(entry, spans, seconds, counter)
    if trace:
        jax.profiler.stop_trace()
    peak = memory_peak(devices)
    log(window_batches=len(latencies), window_compiles=compiles,
        window_retunes=len(getattr(entry, "retunes", [])))

    # ---- correctness, after the window ----------------------------------
    entry.release()
    t_ref = time.perf_counter()
    numbers = entry.check(entry.reference())
    sampled = numbers.pop("_sampled", {})
    limits = entry_mod.LIMITS
    correct = all(numbers[k] <= limits[k] for k in limits)
    log(reference_s=time.perf_counter() - t_ref, sampled=sampled)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    # a call that raises ends the run, so every completed call succeeded
    result = {"correct": correct, "attempted": len(latencies), "failed": 0,
              "device": device}
    if trace:
        metrics, breakdown = per_layer(spec, cell, spans, trace_dir, device)
        result["metrics"] = metrics
        if breakdown:
            result["breakdown"] = breakdown
    else:
        ms = sorted(1e3 * x for x in latencies)
        log(latency_samples=len(ms))
        result["metrics"] = {
            "ops_per_s": {"value": ops / seconds, "unit": "ops/s"},
            "batch_ms_p50": {"value": quantile(ms, 0.50), "unit": "ms"},
            "batch_ms_p95": {"value": quantile(ms, 0.95), "unit": "ms"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    result["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                        for k in limits}
    for k in limits:
        print(f"check {k} {numbers[k]!r} limit {limits[k]!r}",
              file=sys.stderr)
    return result


def per_layer(spec, cell, spans, trace_dir, device):
    """Each per-layer metric this cell reports, from its own reader."""
    path = tracing.newest_xplane(trace_dir)
    trace = None
    if path is not None:
        trace = tracing.reduce_trace(path, ["bench.batch", "locate",
                                            "profile", "price"])
        device["busy_s"] = trace.busy_s()
        device["window_s"] = trace.window_s
    peaks = load_json(HERE, "peaks.json")
    ctx = {"spans": spans, "trace": trace, "device": device, "peaks": peaks}
    metrics = {}
    for m in spec["per_layer"]:
        if cell["name"] not in m.get("workloads", [cell["name"]]):
            continue
        value = load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    breakdown = None
    if trace is not None:
        top = sorted(trace.op_seconds().items(), key=lambda kv: -kv[1])
        gaps = sorted(trace.idle_by_span().items(), key=lambda kv: -kv[1])
        breakdown = {"device_ops": [list(kv) for kv in top[:10]],
                     "idle_gaps": [list(kv) for kv in gaps[:10]]}
    return metrics, breakdown


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = load_json(ROOT, "BENCHMARK.json")
    cells = {c["name"]: c for c in spec["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"unknown workload {args.workload!r}; "
                         f"one of {sorted(cells)}")
    cell = cells[args.workload]
    devices = chip_devices(cell["chips"])
    use_compile_cache()
    config = load_json(HERE, "configs", cell["config"] + ".json")
    mix = load_json(HERE, "traffic", cell["traffic"] + ".json")
    result = run_cell(spec, cell, config, mix, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      devices=devices)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
