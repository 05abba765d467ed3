"""The reduction from a profiler trace to the per-layer numbers: idle
share, Pallas kernels against other device operations, and idle gaps
shared out among the harness's host spans."""
import gzip
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import reduce_trace  # noqa: E402

#: Two calls, hand-made: host spans, two program runs, four device ops.
EVENTS = {
    "host": [("window", 0, 1000), ("dispatch", 0, 10), ("sync", 10, 100),
             ("next_input", 100, 110), ("dispatch", 110, 120),
             ("sync", 120, 300), ("next_input", 300, 310)],
    "modules": [("jit_run", 20, 90), ("jit_run", 130, 290),
                ("jit_run", 995, 1010)],   # ends after the window: left out
    "ops": [("separable_fused_pallas.1", 20, 50), ("pad.2", 55, 90),
            ("separable_fused_pallas.1", 130, 200), ("fusion.3", 210, 290),
            ("separable_fused_pallas.1", 995, 1010)],
}


def test_reduce_by_hand():
    r = reduce_trace.reduce(EVENTS, ["%separable_fused_pallas.1"])
    ns = 1e-9
    # the window holds whole calls only: first run's start to last's end
    assert r["calls"] == 2
    assert r["window_s"] == pytest.approx(270 * ns)
    assert r["busy_s"] == pytest.approx((30 + 35 + 70 + 80) * ns)
    assert r["kernel_s"] == pytest.approx(100 * ns)
    assert r["op_s"] == pytest.approx(215 * ns)
    # gaps 50-55 (sync), 90-130 (sync, next_input, dispatch, sync),
    # 200-210 (sync)
    assert dict(r["idle_by_host"]) == pytest.approx(
        {"sync": 35 * ns, "next_input": 10 * ns, "dispatch": 10 * ns})
    assert r["top_ops"][0] == ["separable_fused_pallas.1",
                               pytest.approx(100 * ns)]


def test_gap_outside_every_span_is_other():
    ev = dict(EVENTS, host=[("window", 0, 1000)])
    r = reduce_trace.reduce(ev, [])
    assert dict(r["idle_by_host"]) == pytest.approx({"other": 55e-9})
    assert r["kernel_s"] == 0


def test_nothing_to_read_gives_none():
    assert reduce_trace.reduce(dict(EVENTS, host=[]), []) is None
    assert reduce_trace.reduce(
        dict(EVENTS, host=[("window", 2000, 3000)]), []) is None


def test_union_merges_overlaps():
    assert reduce_trace.union([(5, 8), (0, 3), (2, 4), (8, 9)]) == [
        (0, 4), (5, 9)]


def test_short_name():
    assert reduce_trace.short_name(
        "%separable_fused_pallas.18 = bf16[1,56,56,24]{3,2,1,0} custom-call("
        "bf16[1,113,113,16] %pad.2), custom_call_target=\"tpu_custom_call\""
    ) == "separable_fused_pallas.18"
    assert reduce_trace.short_name("fusion.3") == "fusion.3"


#: A trace recorded on one TPU v5e (jax 0.9.0, libtpu 0.0.34): two
#: batch-1 calls of the MobileNetV2 body, bf16 stream, under the
#: harness's spans.  Its compiled program's 17 Pallas kernels:
FIXTURE = os.path.join(HERE, "fixtures", "v2_b1_2calls.xplane.pb.gz")
FIXTURE_KERNELS = [f"separable_fused_pallas.{i}" for i in range(17, 34)]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "v2_b1.xplane.pb"
    with gzip.open(FIXTURE) as f:
        path.write_bytes(f.read())
    return reduce_trace.load(str(path))


def test_recorded_trace_loads(recorded):
    assert len(recorded["modules"]) == 2
    assert all(n.startswith("jit_run") for n, _, _ in recorded["modules"])
    assert len(recorded["ops"]) == 292
    names = [n for n, _, _ in recorded["host"]]
    assert names.count("window") == 1
    assert names.count("dispatch") == names.count("sync") == 2
    # ops carry their HLO instruction's name, not its whole text
    kernels = {n for n, _, _ in recorded["ops"]
               if n.startswith("separable_fused_pallas")}
    assert kernels == set(FIXTURE_KERNELS)


def test_recorded_trace_reduces(recorded):
    r = reduce_trace.reduce(recorded, FIXTURE_KERNELS)
    assert r["calls"] == 2 and r["n_ops"] == 292
    # the window: first program run's start to the last one's end
    (_, s0, _), (_, _, e1) = recorded["modules"]
    assert r["window_s"] == pytest.approx((e1 - s0) * 1e-9)
    assert r["busy_s"] == pytest.approx(200.795e-6)
    assert r["kernel_s"] == pytest.approx(174.508e-6)
    assert r["op_s"] == pytest.approx(r["busy_s"])   # ops never overlap
    idle = r["window_s"] - r["busy_s"]
    assert idle / r["window_s"] > 0.8                # batch 1: mostly idle
    assert sum(v for _, v in r["idle_by_host"]) == pytest.approx(idle)
    # the device waits most on the host's dispatch and on the sync
    by = dict(r["idle_by_host"])
    assert by["dispatch"] > by["next_input"] and by["sync"] > by["next_input"]
    assert r["top_ops"][0][0] in FIXTURE_KERNELS


def test_readers_on_the_recorded_trace(recorded):
    import body
    r = reduce_trace.reduce(recorded, FIXTURE_KERNELS)
    ideal = 8.0e-6
    ctx = {"trace": r, "host": {}, "ideal_s_per_call": ideal}

    def read(name):
        return body.load_module(os.path.join(HERE, "metrics",
                                             f"{name}.py")).read(ctx)

    busy, window = 200.795e-6, r["window_s"]
    assert read("idle_share.latency") == pytest.approx(
        100 * (1 - busy / window))
    assert read("glue_share.latency") == pytest.approx(
        100 * (busy - 174.508e-6) / busy)
    assert read("kernel_roofline.latency") == pytest.approx(
        100 * ideal * 2 / busy)
    assert read("mfu_roofline.latency") == pytest.approx(
        100 * ideal * 2 / window)
    # the host metrics read nothing from a trace: they return nothing
    assert read("host_call_us.latency") is None
    assert read("latency_ms_p95") is None
    ctx["trace"] = None
    assert read("idle_share.throughput") is None
