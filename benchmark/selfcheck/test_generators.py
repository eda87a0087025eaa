"""The closed loop's read check and the end-to-end arithmetic, on
synthetic cases."""

import time

import pytest

from benchmark import harness

closed_loop = harness.load_module(harness.HERE, "generators", "closed_loop")


def _later() -> float:
    time.sleep(0.002)
    return time.monotonic()


def test_a_read_may_not_return_what_an_acknowledged_write_replaced():
    h = closed_loop.History()
    v1, _ = h.send(5, new=False)
    h.ack(5, v1)
    before_v2 = _later()
    v2, _ = h.send(5, new=False)
    during_v2 = _later()
    h.ack(5, v2)
    after = _later()
    assert (v1, v2) == (1, 2)
    # the pre-written version was replaced by v1 before any read began
    assert not h.valid(5, 0, before_v2)
    # a read that began before v2 was acknowledged may see v1 or v2
    assert h.valid(5, 1, during_v2) and h.valid(5, 2, during_v2)
    # one that began after it may not see v1 any more
    assert not h.valid(5, 1, after) and h.valid(5, 2, after)
    # never a version nobody sent, nor an answer that is no version
    assert not h.valid(5, 3, after) and not h.valid(5, None, after)
    # an untouched key holds what set-up wrote
    assert h.valid(6, 0, after) and not h.valid(6, 1, after)


def test_racing_writes_leave_either_and_a_failed_write_may_have_landed():
    h = closed_loop.History()
    a, _ = h.send(7, new=False)
    b, _ = h.send(7, new=False)       # sent while a is out
    h.ack(7, b)
    h.ack(7, a)
    now = _later()
    assert h.valid(7, a, now) and h.valid(7, b, now)
    assert not h.valid(7, 0, now)
    c, _ = h.send(7, new=False)       # never acknowledged: it failed
    now = _later()
    assert h.valid(7, c, now) and h.valid(7, a, now) and h.valid(7, b, now)
    assert h.latest_acked() == {7: a}


@pytest.mark.parametrize("ok_slow", [True, False])
def test_a_failed_op_counts_in_the_tail_with_the_time_it_took(ok_slow):
    """19 ops of 100 ms and one that took 30 s: the tail is the slow
    one's whether it completed or died at its timeout, and a failed op
    carries no bytes."""
    done = [("read", 0.0, 0.1, True, 4096)] * 18 + \
        [("write", 0.0, 0.1, True, 4096), ("write", 0.0, 30.0, ok_slow, 0)]
    reader = harness.load_module(harness.HERE, "readers", "op_latency")

    class R:
        window_ops = done
        log = staticmethod(lambda msg: None)

    assert reader.read(R, {"percentile": 96}) == pytest.approx(30000.0)
    assert reader.read(R, {"percentile": 95}) == pytest.approx(100.0)
    assert reader.read(R, {"percentile": 50, "op": "read"}) == \
        pytest.approx(100.0)
    assert reader.read(R, {"percentile": 95, "op": "scrub"}) is None
    assert harness.latency_ms(done, 100) == pytest.approx(30000.0)
    rate = harness.end_to_end_value({"kind": "rate_mibps", "op": "write"},
                                    done, 1.0, 1.0)
    assert rate == pytest.approx(4096 / (1 << 20))
