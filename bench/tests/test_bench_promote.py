"""The reader of the promotion program's device time, `promote_device_ms`,
on the synthetic trace of `test_bench_trace.py` with two executions of the
module "jit_promote_pages" added inside the window."""
import pytest

from bench import harness
from bench.tests.conftest import ROOT
from bench.tests.test_bench_trace import DEVICE, HOST, _record, _reduce

PROMOTE = [
    ("XLA Ops", "%dynamic-update-slice.4 = bf16[2,8]{1,0} dynamic-update-slice()",
     46000, 49000),
    ("XLA Modules", "jit_promote_pages(11)", 46000, 49000),
    ("XLA Ops", "%dynamic-update-slice.4 = bf16[2,8]{1,0} dynamic-update-slice()",
     62000, 66000),
    ("XLA Modules", "jit_promote_pages(11)", 62000, 66000),
]


@pytest.fixture(scope="module")
def read():
    return harness.load_metric(ROOT, "promote_device_ms")


def test_nothing_without_the_counter_or_a_launch(read):
    rec = _record(_reduce(DEVICE + PROMOTE, HOST))
    assert "promote_calls" not in rec.stats  # a program that does not count them
    assert read(rec) is None
    rec.stats = dict(rec.stats, promote_calls=0)
    assert read(rec) is None


def test_device_time_per_launch(read):
    rec = _record(_reduce(DEVICE + PROMOTE, HOST))
    rec.stats = dict(rec.stats, promote_calls=2)
    assert read(rec) == pytest.approx((3000 + 4000) / 2 / 1e6)


def test_refuses_launches_the_trace_lacks(read):
    rec = _record(_reduce(DEVICE, HOST))
    rec.stats = dict(rec.stats, promote_calls=2)
    with pytest.raises(ValueError):
        read(rec)
