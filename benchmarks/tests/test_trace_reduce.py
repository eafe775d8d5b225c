"""The reduction from a profiler trace to numbers: the interval
arithmetic on made-up intervals, then the whole reduction on a small
trace recorded once by ``jax.profiler`` on the CPU (two steps of the
tiny preset through the train driver; ``recorded_cpu_trace.xplane.pb.gz``
beside this file)."""

import os

import pytest

from benchmarks import trace_reduce as tr

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "recorded_cpu_trace.xplane.pb.gz")


def test_union_of_intervals():
    assert tr.union_length([]) == 0.0
    assert tr.union_length([(0, 1), (2, 3)]) == 2.0
    assert tr.union_length([(0, 2), (1, 3)]) == 3.0
    assert tr.union_length([(0, 10), (1, 2), (3, 4)]) == 10.0
    assert tr.union_length([(5, 6), (0, 1), (0.5, 5.5)]) == 6.0


def test_gaps_and_their_attribution():
    gaps = tr.gaps_of([(1, 2), (4, 5)], 0, 6)
    assert gaps == [(0, 1), (2, 4), (5, 6)]
    assert tr.gaps_of([], 0, 2) == [(0, 2)]
    assert tr.gaps_of([(0, 3)], 1, 2) == []
    spans = [("outer", 0.0, 6.0), ("sync", 2.0, 4.5), ("data.wait", 5.0, 5.4)]
    by = tr.attribute_gaps(gaps, spans)
    # (0,1): only outer; (2,4): sync covers it all and is shorter than
    # outer; (5,6): outer covers most
    assert by == {"outer": 2.0, "sync": 2.0}
    assert tr.attribute_gaps([(10, 11)], spans) == {"(no span)": 1.0}


@pytest.fixture(scope="module")
def recorded():
    assert os.path.getsize(RECORDED) < 1 << 20
    return tr.reduce_trace(RECORDED, layout=tr.CPU_LAYOUT,
                           span_names=("step", "sync", "data.wait"))


def test_recorded_trace_window_busy_and_idle(recorded):
    # the window is the bench.window annotation; busy is a union, so it
    # cannot pass the window, and the CPU's thunks do run inside it
    assert 0.0 < recorded.busy_s <= recorded.window_s
    assert 0.0 <= recorded.idle_share < 1.0
    assert recorded.chips == 1


def test_recorded_trace_sums_by_name_and_spans(recorded):
    assert recorded.op_seconds
    assert all(s >= 0 for s in recorded.op_seconds.values())
    assert max(recorded.op_seconds.values()) > 0
    # nested events overlap, so the sum by name is at least the union
    assert sum(recorded.op_seconds.values()) >= recorded.busy_s * 0.999
    top = recorded.top_ops(3)
    assert top == sorted(top, key=lambda kv: -kv[1]) and len(top) == 3
    # the loop's spans, bridged into the trace, are on the same clock
    assert len(recorded.host_spans["step"]) >= 2
    assert "sync" in recorded.host_spans
    # every idle second is attributed to something
    idle = recorded.window_s - recorded.busy_s
    assert sum(recorded.gap_seconds.values()) == pytest.approx(idle,
                                                              abs=1e-6)


def test_a_trace_without_device_operations_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_trace(RECORDED, layout=tr.TPU_LAYOUT)
