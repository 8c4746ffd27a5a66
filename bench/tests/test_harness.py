"""The harness end to end on the CPU, and the pieces it is made of."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import roofline
import run
import tracing
import traffic
from conftest import BENCH


def rehearse(tiny, seed=2**31 + 7, seconds=2.0, trace=False):
    spec, cell, config, mix = tiny
    return run.run_cell(spec, cell, config, mix, seed=seed, seconds=seconds,
                        trace=trace, devices=jax.devices())


def test_tiny_run_is_correct(tiny):
    res = rehearse(tiny)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"ops_per_s", "batch_ms_p50",
                                   "batch_ms_p95", "setup_s"}
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks"


def test_tiny_traced_run_reports_host_spans(tiny):
    res = rehearse(tiny, trace=True)
    assert res["correct"], res["checks"]
    # no TPU in the trace: the device metrics find nothing and stay out
    assert set(res["metrics"]) == {"locate_ms", "profile_ms", "price_ms"}
    assert res["device"]["window_s"] > 0


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "books_rmi.w4_drift", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_traffic_is_a_function_of_the_seed(tiny):
    _, _, config, mix = tiny
    keys = traffic.make_keys("books", 50_000, 0)
    a = traffic.make_pool(mix, keys, 2**31 + 11)["point"]
    b = traffic.make_pool(mix, keys, 2**31 + 11)["point"]
    c = traffic.make_pool(mix, keys, 12)["point"]
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not all(np.array_equal(x, y) for x, y in zip(a, c))
    # every seed gives the same sizes
    assert [x.shape for x in a] == [x.shape for x in c]
    assert np.array_equal(traffic.make_keys("books", 10_000, 3),
                          traffic.make_keys("books", 10_000, 3))


def test_unknown_device_kind_raises():
    trace = tracing.Trace((0, 10), {0: [("price_grid", 0, 5)]}, [])
    ctx = {"trace": trace, "peaks": run.load_json(run.HERE, "peaks.json"),
           "device": {"kind": "TPU v99"}}
    with pytest.raises(KeyError, match="TPU v99"):
        roofline.share(ctx, "price_grid", 1000)


def test_trace_reduction_on_a_recorded_window():
    # device 0: ops at [10, 30), [20, 40) and [60, 70); window [0, 100)
    ops = {0: [("fusion.1", 10, 30), ("_price_kernel", 20, 40),
               ("_price_kernel", 60, 70), ("late", 95, 120)]}
    host = [(tracing.WINDOW_SPAN, 0, 100), ("bench.batch", 0, 100),
            ("price", 50, 80)]
    trace = tracing.Trace((0, 100), ops, host)
    # busy: [10, 40) + [60, 70) + [95, 100) = 45 ns
    assert trace.busy_s() == pytest.approx(45e-9)
    assert trace.window_s == pytest.approx(100e-9)
    assert trace.kernel_seconds("_price_kernel") == pytest.approx(30e-9)
    assert trace.op_seconds()["late"] == pytest.approx(5e-9)
    idle = trace.idle_by_span()
    # gaps [0,10), [40,60) (mid 50: in price), [70,95) (mid 82: batch)
    assert idle == {"bench.batch": pytest.approx(35e-9),
                    "price": pytest.approx(20e-9)}
    ctx = {"trace": trace, "peaks": {"k": {"hbm_bytes_per_s": 1e9}},
           "device": {"kind": "k"}}
    # 3 bytes at 1 GB/s = 3 ns, over 30 ns of kernel
    assert roofline.share(ctx, "_price_kernel", 3) == pytest.approx(10.0)
    assert roofline.share(ctx, "absent", 3) is None


def test_reduce_trace_reads_a_profiler_file(tmp_path):
    spans = tracing.Spans(annotate=True)
    jax.profiler.start_trace(str(tmp_path))
    with spans.span(tracing.WINDOW_SPAN):
        with spans.span("price"):
            jax.numpy.ones(8).block_until_ready()
    jax.profiler.stop_trace()
    trace = tracing.reduce_trace(tracing.newest_xplane(str(tmp_path)),
                                 ["price"])
    assert trace.window[1] > trace.window[0]
    assert [s[0] for s in trace.host_spans].count("price") == 1
