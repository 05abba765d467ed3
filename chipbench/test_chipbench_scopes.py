"""The compiled program's scopes (``scopes.py``), the per-block and
per-span breakdown (``breakdown.py``), the ``network_build_s`` reader, and
the trace reduction's values on the recorded fixture, pinned."""
import dataclasses
import gzip
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import body  # noqa: E402
import breakdown  # noqa: E402
import reduce_trace  # noqa: E402
import run  # noqa: E402
import scopes  # noqa: E402

#: Lines of a compiled program (the first MobileNetV2 blocks, bf16, for a
#: TPU v5e; jax 0.9.0, libtpu 0.0.34), their backend configs cut off.
HLO = r"""
  %copy = bf16[1,112,112,32]{3,2,1,0:T(8,128)(2,1)S(1)} copy(%x.1), sharding={replicated}, frontend_attributes={xla.sdy.sharding="#sdy.sharding<@empty_mesh, [{}, {}, {}, {}]>"}, metadata={op_name="x"}
  %pad.0 = bf16[1,114,114,32]{3,2,1,0:T(8,128)(2,1)S(1)} pad(%copy, %constant), padding=0_0x1_1x1_1x0_0, metadata={op_name="jit(run)/b00/fused2/same_pad/jit(_pad)/pad" stack_frame_id=5}
  %copy-done.3 = bf16[32,16]{0,1:T(8,128)(2,1)S(1)} copy-done(%copy-start.3)
  %copy.1 = bf16[32,16]{1,0:T(8,128)(2,1)S(1)} copy(%copy-done.3), sharding={replicated}, frontend_attributes={xla.sdy.sharding="#sdy.sharding<@empty_mesh, [{}, {}]>"}, metadata={op_name="params[0][1][\'w\']"}
  %fused2.1 = bf16[1,112,112,16]{3,2,1,0:T(8,128)(2,1)S(1)} custom-call(%pad.0, %copy-done.9, %copy.1), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[1,114,114,32]{3,2,1,0}, bf16[3,3,32]{2,1,0}, bf16[32,16]{1,0}}, frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(run)/b00/fused2/jit(separable_fused_pallas)/fused2/pallas_call" stack_frame_id=7}, backend_config={...}
  %pad.2 = bf16[1,113,113,16]{3,2,1,0:T(8,128)(2,1)S(1)} pad(%fused2.1, %constant), padding=0_0x0_1x0_1x0_0, metadata={op_name="jit(run)/b01/fused3/same_pad/jit(_pad)/pad" stack_frame_id=5}
  %fused3.3 = bf16[1,56,56,24]{3,2,1,0:T(8,128)(2,1)S(1)} custom-call(%pad.2, %copy-done.8, %copy-done.10, %copy.2), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[1,113,113,16]{3,2,1,0}, bf16[16,96]{1,0}, bf16[3,3,96]{2,1,0}, bf16[96,24]{1,0}}, frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(run)/b01/fused3/jit(separable_fused_pallas)/fused3/pallas_call" stack_frame_id=7}, backend_config={...}
  ROOT %fused3.5 = bf16[1,28,28,32]{3,2,1,0:T(8,128)(2,1)} custom-call(%pad.6, %copy-done.5, %copy-done.7, %copy.4), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[1,57,57,24]{3,2,1,0}, bf16[24,144]{1,0}, bf16[3,3,144]{2,1,0}, bf16[144,32]{1,0}}, frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(run)/b03/fused3/jit(separable_fused_pallas)/fused3/pallas_call" stack_frame_id=7}, backend_config={...}
"""


def test_scope_map_of_recorded_hlo():
    assert scopes.scope_map(HLO) == {
        "copy": ("unscoped", ()),
        "pad.0": ("b00", ("fused2", "same_pad")),
        "copy.1": ("unscoped", ()),
        "fused2.1": ("b00", ("fused2", "fused2")),
        "pad.2": ("b01", ("fused3", "same_pad")),
        "fused3.3": ("b01", ("fused3", "fused3")),
        "fused3.5": ("b03", ("fused3", "fused3")),
    }
    assert scopes.kernels(HLO) == ["fused2.1", "fused3.3", "fused3.5"]


@pytest.mark.parametrize("op_name,want", [
    ("jit(run)/b12/pw/jit(pwconv_pallas)/pwconv/pallas_call",
     ("b12", ("pw", "pwconv"))),
    ("jit(run)/jit(separable_fused_pallas)/pallas_call", ("unscoped", ())),
    ("jit(f)/same_pad/jit(_pad)/pad", ("unscoped", ("same_pad",))),
    ("x", ("unscoped", ())),
])
def test_parse_op_name(op_name, want):
    assert scopes.parse_op_name(op_name) == want


def test_block_key():
    assert scopes.block_key(("b01", ("fused3", "same_pad"))) == "b01.fused3"
    assert scopes.block_key(("unscoped", ("same_pad",))) == "unscoped"
    assert scopes.block_key(("b01", ())) == "b01"


#: The same hand-made calls as test_chipbench_trace.EVENTS.
EVENTS = {
    "host": [("window", 0, 1000), ("dispatch", 0, 10), ("sync", 10, 100),
             ("next_input", 100, 110), ("dispatch", 110, 120),
             ("sync", 120, 300), ("next_input", 300, 310)],
    "modules": [("jit_run", 20, 90), ("jit_run", 130, 290),
                ("jit_run", 995, 1010)],
    "ops": [("fused3.1", 20, 50), ("pad.2", 55, 90),
            ("fused3.1", 130, 200), ("copy", 210, 290),
            ("fused3.1", 995, 1010)],
}
SMAP = {"fused3.1": ("b00", ("fused3", "fused3")),
        "pad.2": ("b00", ("fused3", "same_pad")),
        "copy": ("unscoped", ())}


def test_op_seconds_and_blocks_by_hand():
    # the window is cut to whole calls, 20-290: the third call is out
    op_s = dict(reduce_trace.reduce(EVENTS, [], top=None)["top_ops"])
    assert op_s == pytest.approx({"fused3.1": 100e-9, "pad.2": 35e-9,
                                  "copy": 80e-9})
    assert dict(breakdown.device_blocks(op_s, SMAP)) == pytest.approx(
        {"b00.fused3": 135e-9, "unscoped": 80e-9})
    assert breakdown.same_pad_share(op_s, SMAP) == pytest.approx(
        100 * 35 / 215)
    # an op the compiled text does not name is not charged to a block
    assert dict(breakdown.device_blocks(op_s, {}))["unmapped"] == \
        pytest.approx(215e-9)


def test_same_pad_share_needs_block_scopes():
    op_s = {"pad.2": 1.0, "fused3.1": 1.0}
    assert breakdown.same_pad_share(op_s, {
        "pad.2": ("unscoped", ()), "fused3.1": ("unscoped", ())}) is None
    assert breakdown.same_pad_share({}, SMAP) is None


def test_idle_by_program_by_hand():
    # gaps: 50-55 (sync), 90-130 (sync 90-100, next_input 100-110,
    # dispatch 110-120 holding network.memo 110-113 and network.call
    # 113-119, sync 120-130), 200-210 (sync)
    program = [("network.memo", 110, 113), ("network.call", 113, 119)]
    got = dict(breakdown.idle_by_program(EVENTS, program, ["fused3.1"]))
    ns = 1e-9
    assert got == pytest.approx({"sync": 35 * ns, "next_input": 10 * ns,
                                 "network.memo": 3 * ns,
                                 "network.call": 6 * ns,
                                 "dispatch": 1 * ns})
    # without program spans it reads as the harness's own attribution
    r = reduce_trace.reduce(EVENTS, ["fused3.1"])
    assert dict(breakdown.idle_by_program(EVENTS, [], ["fused3.1"])) == \
        pytest.approx(dict(r["idle_by_host"]))


FIXTURE = os.path.join(HERE, "fixtures", "v2_b1_2calls.xplane.pb.gz")
FIXTURE_KERNELS = [f"separable_fused_pallas.{i}" for i in range(17, 34)]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "v2_b1.xplane.pb"
    with gzip.open(FIXTURE) as f:
        path.write_bytes(f.read())
    return str(path)


def test_reduce_on_the_recorded_trace_is_pinned(recorded):
    """Every key of the reduction, as the accepted benchmark read it."""
    r = reduce_trace.reduce(reduce_trace.load(recorded), FIXTURE_KERNELS)
    assert set(r) == {"window_s", "busy_s", "calls", "op_s", "kernel_s",
                      "n_ops", "top_ops", "idle_by_host"}
    assert r["window_s"] == pytest.approx(1385.855e-6)
    assert r["busy_s"] == pytest.approx(200.795e-6)
    assert r["op_s"] == pytest.approx(200.795e-6)
    assert r["kernel_s"] == pytest.approx(174.508e-6)
    assert (r["calls"], r["n_ops"]) == (2, 292)
    top = [(n, round(v * 1e9)) for n, v in r["top_ops"]]
    assert top == [
        ("separable_fused_pallas.18", 29571),
        ("separable_fused_pallas.17", 27156),
        ("separable_fused_pallas.19", 21205),
        ("separable_fused_pallas.20", 16998),
        ("separable_fused_pallas.22", 10490),
        ("separable_fused_pallas.21", 10325),
        ("separable_fused_pallas.23", 7953),
        ("copy", 7173),
        ("separable_fused_pallas.28", 6426),
        ("separable_fused_pallas.29", 5984)]
    assert [(n, round(v * 1e9)) for n, v in r["idle_by_host"]] == [
        ("dispatch", 490483), ("sync", 460117), ("other", 222340),
        ("next_input", 12120)]


def test_breakdown_on_the_recorded_trace(recorded):
    """The trace predates the program's spans: its idle time falls to the
    harness's spans as before, and its op times sum to the reduction's."""
    events = reduce_trace.load(recorded)
    r = reduce_trace.reduce(events, FIXTURE_KERNELS)
    assert breakdown.load_program_spans(recorded) == []
    op_s = dict(reduce_trace.reduce(events, FIXTURE_KERNELS,
                                    top=None)["top_ops"])
    assert sum(op_s.values()) == pytest.approx(r["op_s"])
    assert dict(breakdown.idle_by_program(events, [], FIXTURE_KERNELS)) == \
        pytest.approx(dict(r["idle_by_host"]))
    # a program without block scopes: no share of SAME pads to read
    assert breakdown.same_pad_share(op_s, {n: ("unscoped", ())
                                           for n in op_s}) is None


@pytest.mark.parametrize("cell", ["mnv2-b1-bf16", "mnv1-b128-bf16"])
def test_block_ideal_times_add_up_to_the_body(cell):
    """Bound by bytes alone, or by FLOPs alone, the blocks' ideal times
    add up to the body's."""
    c = run.load_cell(cell)
    bd = body.Body(c["config"], c["traffic"]["batch"])
    for peak in ({"bf16_flops_per_s": float("inf"), "hbm_bytes_per_s": 1.0},
                 {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": float("inf")}):
        ideal = breakdown.block_ideal_s(bd, peak)
        assert len(ideal) == len(bd.blocks)
        assert sum(ideal) == pytest.approx(bd.ideal_s_per_call(peak))


def _build_reader():
    return body.load_module(os.path.join(HERE, "metrics",
                                         "network_build_s.py"))


def test_network_build_s_reads_the_counter(monkeypatch):
    from repro.runtime import telemetry
    monkeypatch.setattr(telemetry, "_COUNTERS",
                        telemetry.collections.Counter())
    reader = _build_reader()
    assert reader.read({}) is None          # no counter: nothing to read
    telemetry.record_build(2_500_000_000)
    assert reader.read({}) == pytest.approx(2.5)


def test_breakdown_window_on_the_cpu(monkeypatch, tmp_path):
    """One window of a small latency cell, the program on its XLA path:
    the program's spans are read, nothing is built inside the window, and
    without a TPU in the trace no device number is made up."""
    monkeypatch.setattr(run, "STATE_DIR", str(tmp_path))
    for var in ("REPRO_QUARANTINE", "REPRO_TUNE_CACHE", "TPU_LOG_DIR"):
        monkeypatch.setenv(var, "")

    class XlaProgram(run.Program):
        def __init__(self, cfg, bd, params):
            super().__init__(cfg, bd, params)
            self.policy = dataclasses.replace(self.policy, impl="xla")

    cell = run.load_cell("mnv2-b1-bf16")
    cell["config"] = dict(cell["config"], body_input=[16, 16, 32])
    cell["traffic"] = dict(cell["traffic"], pool=2, sample=2,
                           warmup_calls=2)
    st = run.setup(cell, 2**31 + 11, device_check=False,
                   make_program=XlaProgram)
    try:
        assert _build_reader().read({}) > 0
        peak = body.load_peak("TPU v5 lite")
        off = breakdown.measure(cell, st, 0.4, False, {}, [], peak)
        on = breakdown.measure(cell, st, 0.4, True, {}, [], peak)
    finally:
        st["prog"].free()
    assert off["network_memo_us"] is None and off["spans"] == {}
    assert on["network_memo_us"] > 0 and on["network_call_us"] > 0
    assert on["spans"]["network.memo"]["count"] == on["requests"]
    assert off["builds"] == on["builds"] == 0
    assert "same_pad_share" not in on
    assert on["metrics"]["host_call_us.latency"] > 0
