"""The trace reduction: busy union, idle share, collective and exposed
time, grouping by program, and the idle gaps named by host spans."""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from chipbench import trace as T  # noqa: E402


def _reduced():
    """One chip, window [0, 100] ns: a step program 10-40 holding two
    overlapping ops and an async all-gather 20-50 (start 20-22, done
    48-50), a decode program 60-70, and host spans around them."""
    ops = [("fusion.1", 10, 25), ("convolution.2", 24, 40),
           ("all-gather-start.3", 20, 22), ("all-gather-done.3", 48, 50),
           ("fusion.4", 60, 70), ("fusion.5", 95, 110)]
    modules = [("jit_step(7)", 10, 50), ("jit__decode(9)", 60, 70),
               ("jit_step(7)", 95, 110)]
    spans = [("chipbench.window", 0, 100), ("chipbench.feed", 0, 9),
             ("chipbench.wait", 50, 59), ("chipbench.idle", 70, 95),
             ("chipbench.iteration", 55, 75)]
    return T.from_events([(ops, modules)], spans)


def test_window_and_clipping():
    red = _reduced()
    assert red.window == (0, 100)
    assert red.window_s == pytest.approx(100e-9)
    # the last op is cut at the window's close
    assert max(o.end for o in red.chips[0]) == 100


def test_busy_union_and_idle_share():
    red = _reduced()
    # 10-40 (two ops overlap 24-25) + 48-50 + 60-70 + 95-100; the async
    # start 20-22 lies inside 10-40
    assert T.busy(red.chips[0]) == [(10, 40), (48, 50), (60, 70), (95, 100)]
    assert T.busy_s(red) == pytest.approx(47e-9)
    assert T.idle_share(red) == pytest.approx(0.53)


def test_collective_and_exposed_time():
    red = _reduced()
    assert T.collective_intervals(red.chips[0]) == [(20, 50)]
    coll, exposed = T.collective_s(red)
    # 20-50 is a collective; other ops cover 20-40 of it
    assert coll == pytest.approx(30e-9)
    assert exposed == pytest.approx(10e-9)


def test_collective_names():
    for name in ("all-reduce.1", "all-gather-start", "reduce-scatter.12",
                 "collective-permute-done.2", "all-to-all"):
        assert T.is_collective(name)
    for name in ("fusion.1", "all-gatherer", "copy-start"):
        assert not T.is_collective(name)


def test_grouping_by_program():
    red = _reduced()
    assert {o.name: o.module for o in red.chips[0]}["fusion.4"] == \
        "jit__decode"
    assert T.module_s(red, "decode") == pytest.approx(10e-9)
    assert T.module_s(red, "jit_step") == pytest.approx(37e-9)


def test_device_time_inside_spans_and_gaps():
    red = _reduced()
    its = T.spans_named(red, "chipbench.iteration")
    assert its == [(55, 75)]
    assert T.device_s_within(red, its) == pytest.approx(10e-9)
    gaps = T.idle_gaps(red)
    # longest gap 70-95 under the idle span, then 0-10 under the feed
    assert gaps[0] == ["chipbench.idle", pytest.approx(25e-9)]
    assert gaps[1] == ["chipbench.feed", pytest.approx(10e-9)]
    top = T.top_ops(red)
    assert top[0] == ["convolution.2", pytest.approx(16e-9)]


def test_several_chips_average():
    ops0 = [("fusion.1", 0, 50)]
    ops1 = [("fusion.1", 0, 30)]
    red = T.from_events([(ops0, []), (ops1, [])],
                        [("chipbench.window", 0, 100)])
    assert T.busy_s(red) == pytest.approx(40e-9)
    assert T.idle_share(red) == pytest.approx(0.6)


def _recorded(name, **kw):
    return T.reduce(str(HERE / "data" / name), **kw)


def _sweep_busy(intervals):
    """Busy time of a set of intervals by an event sweep (independent of
    trace.union)."""
    events = sorted([(s, 1) for s, _ in intervals] +
                    [(e, -1) for _, e in intervals])
    depth, last, busy = 0, None, 0.0
    for t, d in events:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def test_recorded_cpu_trace():
    """Three steps of two small programs (``jit_step``, ``jit_finish``)
    recorded on the CPU with the benchmark's spans: the host's XLA op
    events stand in for one chip."""
    red = _recorded("trace_cpu.xplane.pb", host_ops_as_chip=True)
    assert len(red.chips) == 1
    ops = red.chips[0]
    assert {o.module for o in ops} == {"jit_step", "jit_finish"}
    assert all(red.window[0] <= o.start <= o.end <= red.window[1]
               for o in ops)
    busy = _sweep_busy([(o.start, o.end) for o in ops]) * 1e-9
    assert T.busy_s(red) == pytest.approx(busy)
    assert 0.0 < T.idle_share(red) < 1.0
    assert T.idle_share(red) == pytest.approx(1 - busy / red.window_s)
    parts = T.module_s(red, "jit_step") + T.module_s(red, "jit_finish")
    assert parts == pytest.approx(busy, rel=1e-6)
    names = {n for n, _, _ in red.spans}
    assert {"chipbench.window", "chipbench.step", "chipbench.wait",
            "chipbench.idle"} <= names
    assert len(T.spans_named(red, "chipbench.step")) == 3
    assert T.collective_s(red) == (0.0, 0.0)
    b = T.breakdown(red)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert sum(t for _, t in b["idle_gaps"]) <= red.window_s - busy + 1e-12


def test_trace_without_a_tpu_plane_is_refused():
    """Unless a test asks for the host stand-in, a trace with no TPU plane
    is an error, never host events read as device time."""
    with pytest.raises(ValueError, match="no /device:TPU:n plane"):
        _recorded("trace_cpu.xplane.pb")


def test_training_readers_on_a_made_trace():
    """The training metrics' readers over a made record: two steps of
    1000 tokens in a 100 ns window with an exposed 10 ns collective."""
    from chipbench import harness as H
    red = _reduced()
    rec = {"kind": "train", "trace": red, "steps": 2, "tokens": 2000,
           "flops_per_token": 1e-3, "chips": 1,
           "peak": {"bf16_flops_per_s": 1e9}}
    read = lambda name: H.metric_reader(name)(rec)
    assert read("idle_share.train") == pytest.approx(53.0)
    # 2 flop over 100 ns at 1 GFLOP/s is 2%
    assert read("mfu.train") == pytest.approx(2.0)
    assert read("collective_ms.train") == pytest.approx(15e-6)
    assert read("exposed_collective_ms.train") == pytest.approx(5e-6)
    rec["trace"] = T.from_events([([("fusion.1", 0, 10)], [])],
                                 [("chipbench.window", 0, 100)])
    assert read("collective_ms.train") is None
    assert read("idle_share.serve") is None
