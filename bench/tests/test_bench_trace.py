"""The trace reduction on synthetic device and host events."""
import pytest

from bench import trace


def test_busy_is_the_union_of_overlapping_ops_inside_the_window():
    devices = {"/device:TPU:0": [(0, 40, "a"), (30, 70, "b"), (90, 150, "c")],
               "/device:TPU:1": [(10, 20, "a")]}
    spans = [(10, 110, "bench.window")]
    red = trace.reduce_events(devices, spans)
    # TPU:0 busy in [10, 70) and [90, 110) -> 80; TPU:1 in [10, 20) -> 10; mean 45
    assert red.window_s == pytest.approx(100e-9)
    assert red.busy_s == pytest.approx(45e-9)
    assert red.idle_share == pytest.approx(0.55)
    assert red.n_devices == 2


def test_top_ops_count_self_time_of_nested_ops():
    devices = {"/device:TPU:0": [(0, 100, "while.1"), (10, 40, "fusion.2"), (50, 90, "fusion.3"),
                                 (60, 70, "fusion.2")]}
    red = trace.reduce_events(devices, [(0, 100, "bench.window")])
    got = dict(red.top_ops)
    assert got["while.1"] == pytest.approx(30e-9)  # 100 - 30 - 40
    assert got["fusion.3"] == pytest.approx(30e-9)  # 40 - 10 nested
    assert got["fusion.2"] == pytest.approx(40e-9)
    assert [name for name, _ in red.top_ops][0] == "fusion.2"


def test_each_idle_gap_goes_to_the_span_that_covers_most_of_it():
    devices = {"/device:TPU:0": [(0, 20, "x"), (60, 80, "y")]}  # gaps [20, 60) and [80, 100)
    spans = [(0, 100, "bench.window"), (0, 50, "bench.run_round"), (50, 100, "bench.host"),
             (80, 100, "bench.inner")]
    red = trace.reduce_events(devices, spans)
    gaps = dict(red.idle_gaps)
    assert gaps["bench.run_round"] == pytest.approx(40e-9)  # covers 30 of 40 ns
    assert gaps["bench.inner"] == pytest.approx(20e-9)  # as much as bench.host, and shorter
    assert "bench.host" not in gaps


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_events({"/device:TPU:0": [(0, 1, "x")]}, [])


def test_op_names_drop_the_instruction_text():
    assert trace.op_name("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop") == "fusion.12"


def test_a_trace_without_host_spans_takes_the_window_length_given():
    devices = {"/device:TPU:0": [(100, 140, "a"), (120, 160, "b"), (300, 310, "c")]}
    red = trace.reduce_events(devices, [], window_ns=1000)
    assert red.window_s == pytest.approx(1e-6)
    assert red.busy_s == pytest.approx(70e-9)
    assert red.idle_gaps == [["bench.window", pytest.approx(930e-9)]]
    with pytest.raises(ValueError):
        trace.reduce_events(devices, [])
