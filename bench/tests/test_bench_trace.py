"""``bench/trace.py``: the interval arithmetic on made-up intervals, and
the whole reduction on a short trace recorded on a TPU v5e."""
from pathlib import Path

import numpy as np
import pytest

from bench import trace

RECORDED = Path(__file__).parent / "data" / "amz.pr.sgr.xplane.pb"


def test_merge_takes_the_union():
    s, e = trace._merge(np.array([5.0, 0.0, 1.0, 9.0]),
                        np.array([6.0, 2.0, 3.0, 10.0]))
    assert s.tolist() == [0.0, 5.0, 9.0] and e.tolist() == [3.0, 6.0, 10.0]


def test_covered_before_counts_interval_length_up_to_t():
    starts, ends = np.array([0.0, 5.0]), np.array([2.0, 8.0])
    got = trace._covered_before(np.array([-1.0, 1.0, 3.0, 6.0, 9.0]),
                                starts, ends)
    assert got.tolist() == [0.0, 1.0, 2.0, 3.0, 5.0]


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(trace.load(RECORDED))


def test_recorded_trace_reduces(reduced):
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
    idle = sum(reduced["idle_s"].values())
    assert idle == pytest.approx(reduced["window_s"] - reduced["busy_s"],
                                 rel=1e-6, abs=1e-6)
    assert all(v >= -1e-9 for v in reduced["idle_s"].values())
    assert sum(reduced["op_s"].values()) >= reduced["busy_s"] * (1 - 1e-6)


def test_breakdown_keeps_the_largest_ten(reduced):
    b = trace.breakdown(reduced)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    secs = [v for _, v in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert secs[0] == max(reduced["op_s"].values())
