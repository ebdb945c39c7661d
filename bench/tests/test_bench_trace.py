"""The trace reduction and the per-layer readers, on a trace whose numbers
are known by hand, written as an XSpace text proto in the layout the
profiler gives on a v5e, and on an excerpt of a trace recorded on the chip."""
import json

import pytest

from bench import flops, harness, trace_reduce
from bench.tests.conftest import DATA, ROOT

# (line, name, start_ns, end_ns): "XLA Ops" events are named by the HLO
# instruction's text and belong to the "XLA Modules" execution (named
# "<module>(<id>)") that holds their start
DEVICE = [
    ("XLA Ops", "%fusion.0 = f32[8]{0} fusion(f32[8]{0} %p)", 2000, 5000),  # before the window
    ("XLA Ops", "%while.3 = (s32[]) while((s32[]) %t)", 20000, 45000),  # control flow: no row
    ("XLA Ops", "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 20000, 30000),
    ("XLA Ops", "%paged_decode_attention_pallas.7 = bf16[8,16,128]{2,1,0} custom-call()",
     30000, 40000),
    ("XLA Ops", "%copy.2 = f32[8]{0} copy(f32[8]{0} %p)", 35000, 45000),  # overlaps the kernel
    ("XLA Ops", "%log_compact_pallas.3 = bf16[2,10]{1,0} custom-call()", 70000, 80000),
    ("XLA Ops", "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 85000, 90000),
    ("XLA Ops", "%fusion.9 = f32[8]{0} fusion(f32[8]{0} %p)", 108000, 114000),  # runs past the end
    ("XLA Modules", "jit_other(3)", 2000, 5000),
    ("XLA Modules", "jit_step(7)", 20000, 45000),
    ("XLA Modules", "jit__unknown(9)", 70000, 80000),
    ("XLA Modules", "jit_step(7)", 85000, 90000),
    ("XLA Modules", "jit_other(3)", 108000, 114000),
]
HOST = [
    ("bench.window", 10000, 110000),
    ("bench.step", 15000, 55000),
    ("bench.bookkeeping", 55000, 60000),
    ("bench.step", 60000, 105000),
]


def _plane(pid, name, lines, events, ps=1000):
    """An XSpace plane as text proto; event times are in units of `ps`."""
    meta, out = {}, []
    for line_name in lines:
        evs = []
        for ev_line, ev_name, s, e in events:
            if ev_line != line_name:
                continue
            mid = meta.setdefault(ev_name, len(meta) + 1)
            evs.append(f"events {{ metadata_id: {mid} offset_ps: {s * ps} "
                       f"duration_ps: {(e - s) * ps} }}")
        out.append(f'lines {{ id: {len(out) + 1} name: "{line_name}" timestamp_ns: 0 '
                   + " ".join(evs) + " }")
    md = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} name: {json.dumps(n)} }} }}'
                  for n, i in meta.items())
    return f'planes {{ id: {pid} name: "{name}" ' + " ".join(out) + f" {md} }}"


def _reduce(device, host, ps=1000):
    from jax.profiler import ProfileData

    text = (_plane(1, "/device:TPU:0", ["XLA Modules", "XLA Ops"], device, ps)
            + _plane(2, "/host:CPU", ["python"],
                     [("python", n, s, e) for n, s, e in host], ps))
    return trace_reduce.reduce_profile(ProfileData.from_text_proto(text))


@pytest.fixture(scope="module")
def reduced():
    return _reduce(DEVICE, HOST)


def test_busy_idle_and_per_program_time(reduced):
    assert reduced["window_ns"] == [10000, 110000]
    # union: [20000, 45000] + [70000, 80000] + [85000, 90000] + [108000, 110000]
    assert reduced["busy_ns"] == 25000 + 10000 + 5000 + 2000
    assert reduced["modules"] == {"jit_step": {"calls": 2, "ns": 30000},
                                  "jit__unknown": {"calls": 1, "ns": 10000},
                                  "jit_other": {"calls": 1, "ns": 2000}}
    assert reduced["ops"]["jit_step"]["paged_decode_attention_pallas.7"] == {
        "calls": 1, "ns": 10000}
    assert reduced["ops"]["jit_other"] == {"fusion.9": {"calls": 1, "ns": 2000}}
    assert reduced["spans"]["bench.step"] == [[15000, 55000], [60000, 105000]]


def test_an_excerpt_of_a_chip_trace():
    """Numbers worked out from the excerpt's events by hand: the decode
    step's one execution, its 28 paged-attention calls (one a layer) and
    the union of every event's interval."""
    data = json.loads((DATA / "chip-trace-excerpt.json").read_text())
    red = _reduce([tuple(e) for e in data["device"]], [tuple(e) for e in data["host"]], ps=1)
    assert red["modules"]["jit_step"] == {"calls": 1, "ns": 18567840}
    assert red["ops"]["jit_step"]["paged_decode_attention_pallas.7"] == {
        "calls": 28, "ns": 11444071}
    assert not any(trace_reduce.CONTAINER.match(op) for op in red["ops"]["jit_step"])
    assert red["modules"]["jit_scatter"] == {"calls": 3, "ns": 3231278}
    assert red["busy_ns"] == 21862514
    lo, hi = red["window_ns"]
    assert hi - lo == 50310412
    assert "?" not in red["ops"]


def test_breakdown_names_idle_gaps_by_host_span(reduced):
    assert trace_reduce.idle_gaps(reduced) == [
        ["bench.bookkeeping", 25e-6], ["bench.step", 18e-6],
        ["bench.step", 10e-6], ["bench.step", 5e-6]]
    top = trace_reduce.top_ops(reduced)
    assert top[0] == ["jit_step/fusion.1", 15e-6]
    assert len(top) == 5


def _record(reduced):
    cell = harness.load_cell(ROOT, "qwen3-1.7b.docqa-pressure")
    import json

    cell.config = json.loads((ROOT / "bench/tests/data/tiny.json").read_text())
    cell.mix = json.loads((ROOT / "bench/tests/data/tiny-mix.json").read_text())
    steps = [harness.Step(0.0, 1.0, [100, 40]), harness.Step(1.0, 2.0, [101])]
    stats = {"parks": 3, "promoted_pages": 6}
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    itl = [0.5] * 9 + [1.5]  # seconds
    return harness.RunRecord(cell, steps, (0.0, 2.0), stats, reduced, peaks, itl)


def test_per_layer_readers(reduced):
    rec = _record(reduced)
    read = lambda name: harness.load_metric(ROOT, name)(rec)  # noqa: E731
    assert read("device_idle_share") == pytest.approx(58.0)
    assert read("decode_step_device_ms") == pytest.approx(0.015)
    assert read("compact_device_ms") == pytest.approx(0.01)
    # steps of 40000 and 45000 ns hold 25000 and 15000 ns of device time
    assert read("host_ms_per_step") == pytest.approx(0.0225)
    assert read("parks_per_ktok") == pytest.approx(1000.0)
    assert read("promoted_pages_per_ktok") == pytest.approx(2000.0)
    assert read("itl_p90_ms.pressure") == pytest.approx(600.0)  # 0.5 + 0.1 * (1.5 - 0.5)
    # pages from below (log 8 slots / batch 2): 6 + 3, then 7; 2 layers of
    # K+V pages (16 x 2 x 128 x 2 B each) plus q and out (4 x 128 x 2 B each)
    nbytes = 2 * (9 * 16384 + 2 * 2048) + 2 * (7 * 16384 + 2048)
    assert nbytes == 536576
    assert read("paged_attn_roofline") == pytest.approx(100 * nbytes / 819e9 / 1e-5)
    mfu = sum(flops.decode_token_flops(rec.cell.config, c) for c in (100, 40, 101))
    assert mfu == 3 * 2 * 1835008 + 2 * 4 * 4 * 128 * 241
    assert read("decode_mfu") == pytest.approx(100 * mfu / (42000e-9 * 197e12))


def test_readers_report_nothing_where_nothing_ran(reduced):
    rec = _record(dict(reduced, modules={}, ops={}))
    rec.steps, rec.stats = [], {"parks": 0, "promoted_pages": 0, "compactions": 0}
    for name in ("decode_step_device_ms", "compact_device_ms", "paged_attn_roofline"):
        assert harness.load_metric(ROOT, name)(rec) is None


@pytest.mark.parametrize("name", ["decode_step_device_ms", "compact_device_ms",
                                  "paged_attn_roofline"])
def test_readers_refuse_a_trace_that_misses_what_ran(reduced, name):
    rec = _record(dict(reduced, modules={}, ops={}))
    rec.stats = {"parks": 3, "promoted_pages": 6, "compactions": 1}
    with pytest.raises(ValueError):
        harness.load_metric(ROOT, name)(rec)


def test_host_spans_from_a_recorded_profile(tmp_path):
    """The bench.* spans as the profiler itself records them (on the CPU,
    which has no device plane: busy time is then 0)."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("bench.window"):
        for _ in range(3):
            with TraceAnnotation("bench.step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    red = trace_reduce.reduce_file(next(tmp_path.rglob("*.xplane.pb")))
    lo, hi = red["window_ns"]
    steps = red["spans"]["bench.step"]
    assert len(steps) == 3 and lo <= steps[0][0] and steps[-1][1] <= hi
    assert all(a < b <= c for (a, b), (c, _) in zip(steps, steps[1:]))
    assert red["busy_ns"] == 0 and red["modules"] == {}
