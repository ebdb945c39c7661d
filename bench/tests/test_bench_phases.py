"""The engine's phase spans in the benchmark: the host time of each phase
(`bench/phases.py`) and the reader of the program's read counter,
`host_reads_per_step`, on the synthetic trace of `test_bench_trace.py` with
"tiered.*" spans inside its steps."""
import json

import pytest

from bench import harness, phases
from bench.tests.conftest import ROOT
from bench.tests.test_bench_trace import DEVICE, HOST, _plane, _record, _reduce

# device busy in the window: [20000, 45000], [70000, 80000], [85000, 90000],
# [108000, 110000]; steps [15000, 55000] and [60000, 105000]
TIERED = [
    ("tiered.lru", 5000, 8000),  # before the window: not counted
    ("tiered.residency", 15000, 19000),  # host 4000
    ("tiered.promote", 19000, 30000),  # busy 10000, host 1000
    ("tiered.decode", 30000, 50000),  # busy 15000, host 5000
    ("tiered.lru", 50000, 54000),  # host 4000
    ("tiered.compact", 60000, 82000),  # busy 10000, host 12000
    ("tiered.residency", 82000, 84000),  # host 2000
    ("tiered.promote", 84000, 86000),  # busy 1000, host 1000
    ("tiered.decode", 86000, 95000),  # busy 4000, host 5000
    ("tiered.lru", 95000, 100000),  # host 5000
]
READERS = ("host_ms_per_step", "parks_per_ktok", "itl_p90_ms.pressure",
           "promoted_pages_per_ktok", "decode_step_device_ms", "compact_device_ms",
           "paged_attn_roofline", "decode_mfu", "device_idle_share")


def _profile(device, host):
    from jax.profiler import ProfileData

    text = (_plane(1, "/device:TPU:0", ["XLA Modules", "XLA Ops"], device)
            + _plane(2, "/host:CPU", ["python"], [("python", n, s, e) for n, s, e in host]))
    return ProfileData.from_text_proto(text)


@pytest.fixture(scope="module")
def traced():
    prof = _profile(DEVICE, HOST + TIERED)
    red = phases.trace_reduce.reduce_profile(prof)
    return red, phases.program_spans(prof)


def test_program_spans_leave_the_reduction_unchanged(traced):
    red, spans = traced
    assert red == _reduce(DEVICE, HOST)
    assert sorted(spans) == sorted("tiered." + p for p in phases.PHASES)
    assert spans["tiered.lru"] == [[5000, 8000], [50000, 54000], [95000, 100000]]
    before = _record(_reduce(DEVICE, HOST))
    after = _record(red)
    for name in READERS:
        read = harness.load_metric(ROOT, name)
        assert read(after) == read(before), name


def test_phase_host_time_adds_up_to_the_step(traced):
    red, spans = traced
    got = phases.split(red, spans)
    want = {"compact": 0.006, "residency": 0.003, "promote": 0.001,
            "decode": 0.005, "lru": 0.0045}  # ms per step: ns over 2 steps
    assert got["phases"] == pytest.approx(want)
    assert got["host_ms_per_step"] == pytest.approx(0.0225)
    assert got["host_ms_per_step"] == harness.load_metric(ROOT, "host_ms_per_step")(
        _record(red))
    assert got["unspanned_ms"] == pytest.approx(0.003)  # [54000, 55000], [100000, 105000]
    assert sum(got["phases"].values()) + got["unspanned_ms"] == pytest.approx(
        got["host_ms_per_step"])
    assert got["share"]["compact"] == pytest.approx(100 * 0.006 / 0.0225)
    assert phases.split(dict(red, spans={}), spans) is None


def test_idle_gaps_are_named_by_the_innermost_phase(traced):
    red, spans = traced
    assert phases.split(red, spans)["idle_gaps"] == [
        ["bench.bookkeeping", 25e-6], ["tiered.lru", 18e-6],
        ["tiered.residency", 10e-6], ["tiered.residency", 5e-6]]


def test_host_reads_per_step(traced):
    rec = _record(traced[0])
    read = harness.load_metric(ROOT, "host_reads_per_step")
    assert read(rec) is None  # a program without the counter
    rec.stats = dict(rec.stats, host_reads=3001)
    assert read(rec) == pytest.approx(1500.5)
    rec.steps = []
    assert read(rec) is None


def test_phases_of_a_recorded_engine_step(tmp_path, capsys):
    """The program's own spans, as the profiler records them on the CPU
    around an engine step that compacts (no device plane: busy time is 0,
    so each phase's host time is its length)."""
    import jax
    from jax.profiler import TraceAnnotation

    from repro.configs import get_reduced
    from repro.core.tiering import TieredKVConfig
    from repro.models.api import ModelSpec
    from repro.serving.engine import Request, TieredEngine

    spec = ModelSpec(get_reduced("qwen3-1.7b"))
    kv = TieredKVConfig(page_size=8, n_hbm_pages=32, max_requests=2,
                        max_pages_per_req=8, log_slots=8, batch=2)
    eng = TieredEngine(spec, spec.init(jax.random.PRNGKey(0)), kv)
    for rid in range(2):
        eng.add_request(Request(rid=rid, prompt=list(range(3 + rid, 19)),
                                max_new_tokens=12))
    for _ in range(3):
        eng.step()
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("bench.window"):
        for _ in range(3):
            with TraceAnnotation("bench.step"):
                eng.step()
    jax.profiler.stop_trace()
    assert eng.stats.compactions == 1
    phases.main([str(tmp_path)])
    got = json.loads(capsys.readouterr().out)
    assert all(got["phases"][p] > 0 for p in phases.PHASES)
    assert 0 < got["unspanned_ms"] < got["host_ms_per_step"]
