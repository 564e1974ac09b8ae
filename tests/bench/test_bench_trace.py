"""The trace reduction against hand-counted values."""
import os

import pytest

from bench import trace as T

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
HOST, DEV = "/host:CPU", "/device:TPU:0"
CALL = ('f32[8,8]{1,0} custom-call(f32[8,8]{1,0} %a), '
        'custom_call_target="tpu_custom_call"')


def _ev(plane, line, name, start, end, meta=""):
    return T.Event(plane, line, name, start, end, meta)


#: a window of 10 us; four busy stretches on the device, one cut by each
#: edge of the window, one the union of two overlapping kernel launches
SYNTH = [
    _ev(HOST, "python", "bench.window", 1000, 11000),
    _ev(HOST, "python", "ops.ca_run", 1100, 3000),
    _ev(HOST, "python", "PjitFunction(_ca_run_impl)", 1200, 1500),
    _ev(HOST, "python", "ops.ca_run", 5000, 7000),
    _ev(HOST, "other-thread", "ops.ca_run", 0, 20000),
    _ev(DEV, "XLA Modules", "jit__ca_run_impl(123)", 0, 9500),
    _ev(DEV, "XLA Modules", "jit__unknown(456)", 9500, 20000),
    _ev(DEV, "XLA Ops", "fusion.1", 500, 2000),
    _ev(DEV, "XLA Ops", "%closed_call.7 = " + CALL, 2500, 4500),
    _ev(DEV, "XLA Ops", "%closed_call.7 = " + CALL, 4000, 5500),
    _ev(DEV, "XLA Ops", "%_paged_impl.3 = " + CALL, 9990, 10000),
    _ev(DEV, "XLA Ops", "copy.3", 8000, 9000),
    _ev(DEV, "XLA Ops", "fusion.2", 10500, 12000),
]


def test_busy_and_idle_by_hand():
    s = T.Summary(SYNTH)
    assert s.window_s == pytest.approx(10e-6)
    # [1000,2000] + [2500,5500] + [8000,9000] + [9990,10000]
    # + [10500,11000]
    assert s.busy_s == pytest.approx(5510e-9)
    assert s.busy_between(5000, 7000) == pytest.approx(500e-9)


def test_kernel_time_by_hand():
    s = T.Summary(SYNTH)
    assert s.kernel_seconds(T.pallas_kernel("_ca_run_impl")) \
        == pytest.approx(3500e-9)
    assert s.kernel_seconds(T.pallas_kernel("_write_impl")) == 0
    # a nested jit names the instruction, whatever the program
    assert s.kernel_seconds(T.pallas_kernel("_paged_impl")) \
        == pytest.approx(10e-9)


def test_gaps_are_labelled_by_the_covering_host_span():
    s = T.Summary(SYNTH)
    assert s.idle_gaps() == [(2000, 2500), (5500, 8000), (9000, 9990),
                             (10000, 10500)]
    b = s.breakdown()
    assert b["idle_gaps"] == [["ops.ca_run", pytest.approx(2500e-9)],
                              ["(no host span)", pytest.approx(990e-9)],
                              ["ops.ca_run", pytest.approx(500e-9)],
                              ["(no host span)", pytest.approx(500e-9)]]
    assert b["device_ops"][0] == [
        "jit__ca_run_impl/closed_call.7 tpu_custom_call",
        pytest.approx(3500e-9)]
    assert s.spans("ops.ca_run") == [(1100, 3000), (5000, 7000)]


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        T.Summary(SYNTH[1:])


def test_fixture_round_trip(tmp_path):
    path = tmp_path / "events.json"
    T.dump(SYNTH, str(path))
    assert T.read(str(path)) == SYNTH


def test_recorded_chip_trace_by_hand():
    """40 ms of a traced ``gasket.map-write`` window on one TPU v5e (the
    window span cut to 40 ms, times from its start): two write calls and
    a snapshot between them, each call XLA's copy of the input and then
    the map kernel."""
    s = T.Summary(T.read(os.path.join(FIXTURES, "write_trace.json")))
    assert s.window_s == pytest.approx(0.04)
    # idle: 3504 ns after the first kernel, 2221 ns before the second
    # call's copy, and ten 1-2 ns seams between small ops (14 ns)
    assert s.busy_s == pytest.approx((40_000_000 - 3504 - 2221 - 14) * 1e-9)
    # the first kernel whole, the second cut at the window's end
    assert s.kernel_seconds(T.pallas_kernel("_write_impl")) == \
        pytest.approx((12_269_598 + 40_000_000 - 28_138_286) * 1e-9)
    assert s.kernel_seconds(T.pallas_kernel("_ca_run_impl")) == 0
    b = s.breakdown()
    assert [n for n, _ in b["device_ops"][:4]] == [
        "jit__write_impl/_write_impl.1 tpu_custom_call",
        "jit__write_impl/copy.3", "jit__snapshot/reshape.14",
        "jit__snapshot/copy.3"]
    assert b["device_ops"][1][1] == pytest.approx((3_762_814 + 3_922_019)
                                                  * 1e-9)
    # the host ran ahead of the device: no span covers the long gaps
    assert b["idle_gaps"][:2] == [["(no host span)", pytest.approx(3504e-9)],
                                  ["(no host span)", pytest.approx(2221e-9)]]
    assert len(s.spans("ops.sierpinski_write")) == 4
