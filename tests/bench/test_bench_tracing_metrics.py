"""The program-span readers (``route_wait_ms_per_req``,
``dispatches_ahead_per_route``, ``enqueue_ms_per_dispatch``,
``sync_wait_share``) on span lists built by hand, against readings
worked out by hand, and ``None`` where their spans are missing."""
import pytest

from bench import spec
from bench.driver import View
from bench.families import dense

READERS = ("route_wait_ms_per_req", "dispatches_ahead_per_route",
           "enqueue_ms_per_dispatch", "sync_wait_share")


def _span(sid, name, ts, dur_ms, parent=0, cat="host", **args):
    return {"name": name, "cat": cat, "ph": "X", "ts": ts,
            "dur": dur_ms * 1e3, "tid": "MainThread", "id": sid,
            "parent": parent, "args": args}


def _view(spans):
    arch = dense.Arch(layers=1, hidden=8, heads=1, kv_heads=1, head_dim=8,
                      ffn=8, vocab=8, tied=True, qkv_bias=False)
    return View(family=dense, arch=arch, experts=2, centroids=2,
                experts_per_dispatch=1, peaks={}, counters={},
                trace_counters=None, spans=spans, trace=None, served=[])


# two routing calls (2 and 3 rows) with three blocking transfers between
# them, and two steps, one of them with a decode wave's device span and a
# chunk event inside it; the host plane nests as the tracer links it
SPANS = [
    _span(1, "route", 0, 10.0, rows=2, uids=[0, 1], ahead=4),
    _span(2, "route.wait", 100, 6.0, parent=1),
    _span(3, "route.wait", 7000, 1.5, parent=1),
    _span(4, "step", 11000, 20.0, cat="enqueue"),
    _span(5, "engine.enqueue", 11100, 0.3, parent=4, cat="enqueue",
          kind="decode"),
    _span(6, "engine.enqueue", 11500, 0.5, parent=4, cat="enqueue",
          kind="decode"),
    _span(7, "engine.sync", 12000, 4.0, parent=4),
    _span(8, "wave.decode", 11100, 5.0, parent=4, cat="device"),
    {"name": "wave.chunk", "cat": "host", "ph": "i", "ts": 11200,
     "dur": 0.0, "tid": "MainThread", "id": 9, "parent": 4, "args": {}},
    _span(10, "route", 32000, 5.0, rows=3, uids=[2, 3, 4], ahead=1),
    _span(11, "route.wait", 32100, 2.5, parent=10),
    _span(12, "step", 40000, 10.0, cat="enqueue"),
    _span(13, "engine.enqueue", 40100, 1.2, parent=12, cat="enqueue",
          kind="prefill"),
    _span(14, "engine.sync", 41500, 1.0, parent=12),
]


@pytest.mark.parametrize("name,want", [
    # (6 + 1.5 + 2.5) ms of waits over 2 + 3 rows
    ("route_wait_ms_per_req", 2.0),
    # mean of 4 and 1
    ("dispatches_ahead_per_route", 2.5),
    # (0.3 + 0.5 + 1.2) ms over three dispatches
    ("enqueue_ms_per_dispatch", 2.0 / 3),
    # (4 + 1) ms of sync in (20 + 10) ms of steps
    ("sync_wait_share", 100.0 * 5.0 / 30.0),
])
def test_reading_by_hand(name, want):
    assert spec.metric_reader(name)(_view(SPANS)) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_no_spans_reads_none(name):
    assert spec.metric_reader(name)(_view([])) is None


def test_a_program_without_the_new_spans_reads_none():
    """What the harness records from a program that has only the older
    ``route`` span (rows and uids, no ``ahead``) and no step spans: every
    new reader is silent, and ``route_ms_per_req`` still reads."""
    old = [_span(1, "route", 0, 12.0, rows=3, uids=[0, 1, 2]),
           _span(2, "route", 20000, 6.0, rows=1, uids=[3])]
    for name in READERS:
        assert spec.metric_reader(name)(_view(old)) is None
    assert spec.metric_reader("route_ms_per_req")(_view(old)) == \
        pytest.approx(18.0 / 4)


def test_steps_without_a_sync_read_zero_share():
    steps = [_span(1, "step", 0, 3.0, cat="enqueue"),
             _span(2, "engine.enqueue", 10, 0.4, parent=1,
                   cat="enqueue", kind="decode")]
    assert spec.metric_reader("sync_wait_share")(_view(steps)) == 0.0
    assert spec.metric_reader("enqueue_ms_per_dispatch")(_view(steps)) \
        == pytest.approx(0.4)
    # a route with rows and ahead but no blocking transfer recorded
    route = [_span(3, "route", 0, 2.0, rows=2, uids=[0, 1], ahead=0)]
    assert spec.metric_reader("route_wait_ms_per_req")(_view(route)) \
        is None
    assert spec.metric_reader("dispatches_ahead_per_route")(
        _view(route)) == 0.0
