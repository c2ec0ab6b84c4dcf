"""The trace reduction on small traces recorded on the chip (a cut of the
compact form that ``run.py --keep-trace`` writes): busy plus idle is the
window, a kernel's or module's time is at most the busy time, and every
gap lies inside the window.
"""

import gzip
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import tracing  # noqa: E402

SAMPLES = sorted((HERE / "tests" / "data").glob("*.json.gz"))


def load(path):
    with gzip.open(path, "rt") as f:
        return json.load(f)


@pytest.mark.parametrize("path", SAMPLES, ids=[p.name for p in SAMPLES])
def test_busy_plus_idle_is_window(path):
    red = tracing.Reduced(load(path))
    assert red.window_s > 0 and red.busy_s > 0
    gaps = sum(e - s for s, e in red.gaps()) * 1e-9
    assert abs(red.busy_s + gaps - red.window_s) < 1e-9 * 10
    assert abs(red.idle_s - gaps) < 1e-9 * 10
    for s, e in red.gaps():
        assert red.w0 <= s < e <= red.w1


@pytest.mark.parametrize("path", SAMPLES, ids=[p.name for p in SAMPLES])
def test_parts_within_busy(path):
    red = tracing.Reduced(load(path))
    eps = 1e-9
    assert red.op_time(lambda n: "tpu_custom_call" in n) <= red.busy_s + eps
    assert red.module_time(lambda n: True) <= red.window_s + eps
    for name, secs in red.top_ops(10):
        assert 0 < secs <= red.busy_s + eps
    assert sum(v for v in red.idle_by_label().values()) == \
        pytest.approx(red.idle_s, abs=1e-6)


def test_union_merges_overlaps():
    trace = {"chips": [{"ops": [["a", 0, 10], ["b", 5, 10], ["c", 30, 10]],
                        "modules": [["m", 0, 40]]}],
             "host": [["window", 0, 50], ["wait", 15, 15]]}
    red = tracing.Reduced(trace)
    assert red.busy_s == pytest.approx(25e-9)
    assert red.idle_s == pytest.approx(25e-9)
    assert red.gaps() == [[15, 30], [40, 50]]
    assert red.top_gaps(2)[0] == ["wait", pytest.approx(15e-9)]
    assert red.label(45) == "host_other"
    assert red.op_time(lambda n: n == "b") == pytest.approx(10e-9)
    assert red.count("ops", lambda n: True) == 3
