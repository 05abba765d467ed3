"""Spans and build counters (runtime/telemetry.py, DESIGN.md §9): off by
default at the cost of a flag test, recorded per name under
``tracing()``, summarized by ``runtime_report()["spans"]``, and placed by
``execute_network`` around its memo lookup, its jitted call and its
builds."""
import glob
import os
import sys
import threading

import jax
import pytest

from repro.core import chain, network
from repro.kernels.policy import KernelPolicy
from repro.runtime import telemetry

RAW = KernelPolicy(impl="xla", on_failure="raise")
GUARDED = KernelPolicy(impl="xla")   # on_failure="degrade": the executor


@pytest.fixture(autouse=True)
def _clean():
    telemetry.reset_runtime_telemetry()
    network.clear_network_cache()
    yield
    telemetry.reset_runtime_telemetry()
    network.clear_network_cache()


def _tiny():
    net = network.NetworkSpec(name="tiny", c_in=8, blocks=(
        chain.separable_block_spec(16, stride=1),
        chain.inverted_residual_spec(16, 16, expand=2, stride=2),
    ))
    params = network.init_network(jax.random.PRNGKey(0), net)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 8, 8))
    return net, params, x


def test_span_off_is_the_shared_null_context():
    a, b = telemetry.span("x"), telemetry.span("y")
    assert a is b
    with a:
        pass
    assert telemetry.runtime_report()["spans"] == {}


def test_span_on_records_per_name():
    with telemetry.tracing():
        for _ in range(3):
            with telemetry.span("a"):
                pass
        with telemetry.span("b"):
            pass
    with telemetry.span("a"):   # off again after the block
        pass
    spans = telemetry.runtime_report()["spans"]
    assert {k: v["count"] for k, v in spans.items()} == {"a": 3, "b": 1}
    assert 0 <= spans["a"]["median_us"] <= spans["a"]["p95_us"]
    telemetry.reset_runtime_telemetry()
    assert telemetry.runtime_report()["spans"] == {}


def test_tracing_nests_and_restores():
    with telemetry.tracing():
        with telemetry.tracing():
            pass
        assert telemetry.span("a") is not telemetry.span("b")
    assert telemetry.span("a") is telemetry.span("b")


def test_spans_are_bounded(monkeypatch):
    monkeypatch.setattr(telemetry, "MAX_SPANS", 4)
    with telemetry.tracing():
        for _ in range(10):
            with telemetry.span("a"):
                pass
    assert telemetry.runtime_report()["spans"]["a"]["count"] == 4


def test_spans_from_many_threads_are_all_kept():
    n_threads, n_spans = 2 * (os.cpu_count() or 1) + 2, 200
    names = ("a", "b", "c")

    def work():
        for i in range(n_spans):
            with telemetry.span(names[i % 3]):
                pass

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with telemetry.tracing():
            threads = [threading.Thread(target=work)
                       for _ in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    counts = {k: v["count"]
              for k, v in telemetry.runtime_report()["spans"].items()}
    assert sum(counts.values()) == n_threads * n_spans
    assert set(counts) == set(names)


def test_span_budget_is_at_least_two_to_the_sixteen():
    assert telemetry.MAX_SPANS >= 1 << 16


def test_span_lands_in_a_profiler_trace(tmp_path):
    from jax.profiler import ProfileData
    with telemetry.tracing():
        jax.profiler.start_trace(str(tmp_path))
        try:
            with telemetry.span("network.memo"):
                pass
        finally:
            jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    names = {e.name for p in ProfileData.from_file(path).planes
             if p.name.startswith("/host:")
             for ln in p.lines for e in ln.events}
    assert "network.memo" in names


def test_span_annotates_only_under_a_profiler(monkeypatch, tmp_path):
    made = []

    class Note(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            made.append(name)
            super().__init__(name, **kw)

    with telemetry.tracing():
        monkeypatch.setattr(telemetry, "_annotation", Note)
        with telemetry.span("a"):
            pass
        assert made == []
        jax.profiler.start_trace(str(tmp_path))
        try:
            with telemetry.span("b"):
                pass
        finally:
            jax.profiler.stop_trace()
    assert made == ["b"]
    counts = {k: v["count"]
              for k, v in telemetry.runtime_report()["spans"].items()}
    assert counts == {"a": 1, "b": 1}


@pytest.mark.parametrize("policy", [RAW, GUARDED], ids=["raw", "guarded"])
def test_memo_hits_record_memo_and_call_spans(policy):
    net, params, x = _tiny()
    network.execute_network(net, params, x, policy=policy)   # the build
    n = 5
    with telemetry.tracing():
        for _ in range(n):
            network.execute_network(net, params, x, policy=policy)
    rep = telemetry.runtime_report()
    counts = {k: v["count"] for k, v in rep["spans"].items()}
    assert counts == {"network.memo": n, "network.call": n}
    # steady state: the window built nothing
    assert rep["counters"]["network.builds"] == 1


def test_a_miss_counts_one_build_and_its_time():
    net, params, x = _tiny()
    with telemetry.tracing():
        network.execute_network(net, params, x, policy=RAW)
    rep = telemetry.runtime_report()
    assert rep["counters"]["network.builds"] == 1
    assert rep["counters"]["network.build_ns"] > 0
    assert rep["spans"]["network.build"]["count"] == 1
    assert rep["spans"]["network.memo"]["count"] == 1
    assert "network.call" not in rep["spans"]
    # another input shape is another build
    network.execute_network(net, params, x[:, :4, :4], policy=RAW)
    assert telemetry.runtime_report()["counters"]["network.builds"] == 2


def test_build_counters_are_on_without_tracing():
    net, params, x = _tiny()
    network.execute_network(net, params, x, policy=RAW)
    network.execute_network(net, params, x, policy=RAW)
    rep = telemetry.runtime_report()
    assert rep["counters"]["network.builds"] == 1
    assert rep["spans"] == {}


def test_block_and_segment_scopes_reach_the_hlo():
    net, params, x = _tiny()
    nplan = network.plan_network(net, x.shape, policy=RAW)
    fn = jax.jit(network.build_network_fn(net, nplan, RAW))
    text = fn.lower(params, x).as_text(debug_info=True)
    for scope in ("b00/fused2", "b01/fused3"):
        assert scope in text, scope
