"""The reduction from trace events to busy and idle time, op time and the
naming of idle gaps, on events whose answers are known and on a trace
recorded on the chip."""
import pytest

import tiny
import xplane


def _reduced():
    dev = {"/device:TPU:0": [("fusion.1", 1.0, 2.0),     # 1-3
                             ("Eigh.2", 2.5, 1.5),       # 2.5-4 overlaps
                             ("lloyd_kernel", 6.0, 1.0),  # 6-7
                             ("fusion.1", 9.5, 1.0)]}    # 9.5-10.5, clipped
    host = [("bench.window", 0.0, 10.0), ("select", 3.5, 3.0),
            ("meta_train", 7.0, 3.0)]
    return xplane.reduce_events(dev, host, (0.0, 10.0))


def test_busy_and_idle():
    r = _reduced()
    assert r["window_s"] == pytest.approx(10.0)
    # union: [1, 4] + [6, 7] + [9.5, 10] = 3 + 1 + 0.5
    assert r["busy_s"] == pytest.approx(4.5)
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.55)


def test_op_seconds_and_counts():
    r = _reduced()
    assert r["op_seconds"]["fusion.1"] == pytest.approx(2.5)
    assert r["op_counts"]["fusion.1"] == 2
    assert r["op_seconds"]["Eigh.2"] == pytest.approx(1.5)
    assert r["op_seconds"]["lloyd_kernel"] == pytest.approx(1.0)


def test_gaps_named_by_innermost_annotation():
    r = _reduced()
    # gaps: [0, 1] (window), [4, 6] (select), [7, 9.5] (meta_train)
    assert r["idle_by_host"]["bench.window"] == pytest.approx(1.0)
    assert r["idle_by_host"]["select"] == pytest.approx(2.0)
    assert r["idle_by_host"]["meta_train"] == pytest.approx(2.5)
    b = xplane.breakdown(r, top=2)
    assert [k for k, _ in b["idle_gaps"]] == ["meta_train", "select"]
    assert b["device_ops"][0][0] == "fusion.1"


def test_devices_are_averaged():
    dev = {"/device:TPU:0": [("a", 0.0, 4.0)],
           "/device:TPU:1": [("a", 0.0, 2.0)]}
    r = xplane.reduce_events(dev, [], (0.0, 8.0))
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx(3.0)
    assert r["idle_by_host"]["no annotation"] == pytest.approx(4.0 + 6.0)


# A trace recorded on one TPU v5e: a 2048^2 bf16 matmul, 50 ms of host
# sleep under the annotation "host_wait", then a 256^2 Jacobi eigh, all
# inside the annotation "bench.window".
FIXTURE = tiny.BENCH / "tests" / "data" / "v5e_fixture.xplane.pb"
NAMES = {"bench.window", "matmul", "host_wait", "eigh"}


def test_recorded_trace_device_ops():
    r = xplane.read_xplane(str(FIXTURE))
    assert r["devices"] == 1
    eigh = [v for k, v in r["op_seconds"].items()
            if 'custom_call_target="EighTpu"' in k]
    assert eigh == [pytest.approx(1.912233e-3)]
    # the window runs from the first to the last device op here
    assert r["window_s"] == pytest.approx(0.053391765)
    assert r["busy_s"] == pytest.approx(2.021996e-3)
    top = xplane.breakdown(r)["device_ops"][0]
    assert top[0] == "%custom-call.0 [EighTpu]"


def test_recorded_trace_names_the_host_wait():
    r = xplane.read_xplane(str(FIXTURE), "bench.window", NAMES)
    assert r["window_s"] == pytest.approx(0.054337188)
    gaps = dict(xplane.breakdown(r)["idle_gaps"])
    assert gaps["host_wait"] == pytest.approx(0.0505291, rel=1e-4)
    assert gaps["host_wait"] > 0.9 * r["window_s"]
