"""The trace reduction on a hand-made trace and on one recorded on a TPU v5e."""

import glob
import os

import numpy as np
import pytest

import xtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def hand_made():
    # window [0, 100) ns; chip 0 busy [0, 15) (a while around two fusions)
    # and [30, 40); chip 1 busy [0, 60)
    ops = {0: [("%while.1", 0, 15), ("%fusion.1", 0, 10), ("%fusion.2", 10, 5),
               ("%all-reduce.3", 30, 10), ("%outside", 120, 5)],
           1: [("%fusion.1", 0, 60)]}
    spans = [("bench/window", 0, 100), ("bench/stream", 0, 22), ("bench/finalize", 26, 48),
             ("bench/init", 50, 100)]
    async_ops = {0: [("%all-reduce-start.4", 35, 20)]}
    # on both chips: stream [0, 26), finalize [26, 48), after the job's end
    # marker nothing until init [50, 100)
    marks = {c: [("stream", 0), ("finalize", 26), ("end", 48), ("init", 50)] for c in (0, 1)}
    return xtrace.Trace(ops=ops, spans=spans, async_ops=async_ops, marks=marks)


def test_busy_and_stages():
    tr = hand_made()
    assert tr.window_s() == pytest.approx(100e-9)
    assert tr.busy_s(0) == pytest.approx(25e-9)
    assert tr.busy_s(1) == pytest.approx(60e-9)
    assert tr.busy_s(0, "stream") == pytest.approx(15e-9)
    assert tr.busy_s(0, "finalize") == pytest.approx(10e-9)
    assert tr.busy_s(1, "init") == pytest.approx(10e-9)
    assert tr.busy_s(1, "stream") == pytest.approx(26e-9)


def test_stages_follow_each_chips_markers():
    tr = hand_made()
    assert tr.stage(0, "finalize") == [(26, 48)]
    assert tr.stage(0, "init") == [(50, 100)]  # the last stage runs to the window's end
    # markers of one chip say nothing of another; a chip without them has no stages
    tr.marks[1] = [("stream", 40), ("end", 45)]
    assert tr.busy_s(1, "stream") == pytest.approx(5e-9)
    assert tr.busy_s(0, "stream") == pytest.approx(15e-9)
    del tr.marks[1]
    assert tr.busy_s(1, "stream") == 0
    assert tr.has_stage("finalize") and not tr.has_stage("nowhere")
    # a job of two rounds: each marker opens one interval of its stage
    tr.marks[0] = [("stream", 0), ("finalize", 5), ("stream", 30), ("end", 35)]
    assert tr.stage(0, "stream") == [(0, 5), (30, 35)]
    assert tr.busy_s(0, "stream") == pytest.approx(10e-9)


def test_json_round_trip(tmp_path):
    tr = hand_made()
    path = str(tmp_path / "t.json.gz")
    tr.to_json(path)
    back = xtrace.Trace.from_json(path)
    assert back == tr


def test_op_stats_and_top_ops():
    tr = hand_made()
    assert tr.op_stats(0, "all-reduce") == (1, pytest.approx(10e-9))
    assert tr.op_stats(0, "outside") == (0, 0)
    assert tr.op_stats(0, "while") == (0, 0)  # it encloses the fusions
    # [30, 40) on the ops line and [35, 55) in flight on the async line
    assert tr.in_flight_s(0, "all-reduce") == pytest.approx(25e-9)
    assert tr.in_flight_s(1, "all-reduce") == 0
    top = dict(tr.top_ops())
    assert top["%fusion.1"] == pytest.approx((10e-9 + 60e-9) / 2)
    assert top["%fusion.2"] == pytest.approx(5e-9 / 2)
    assert "%outside" not in top and "%while.1" not in top


def test_idle_gaps_name_the_host_stage():
    tr = hand_made()
    gaps = tr.idle_gaps()
    # chip 0 idles most: gaps [15, 30) (stream 7 ns, finalize 4 ns) and [40, 100)
    assert gaps[0] == ["bench/init", pytest.approx(60e-9)]
    assert gaps[1] == ["bench/stream", pytest.approx(15e-9)]


def brute_busy(ops, lo, hi):
    """Busy nanoseconds by marking whole microseconds: at least the exact
    union, and at most 2 us more per operation."""
    mark = np.zeros((hi - lo) // 1000 + 1, bool)
    for _, s, d in ops:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            mark[(a - lo) // 1000:(b - lo + 999) // 1000] = True
    return mark.sum() * 1e3


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(DATA, "*.json.gz"))))
def test_recorded_trace(path):
    tr = xtrace.Trace.from_json(path)
    lo, hi = tr.window()
    assert tr.chips, "a recorded TPU trace has device operations"
    for chip in tr.chips:
        busy = tr.busy_s(chip)
        brute = brute_busy(tr.ops[chip], lo, hi)
        assert brute - 2e3 * len(tr.ops[chip]) <= busy * 1e9 <= brute + 1.0
        assert 0 < busy <= tr.window_s()
        stages = [tr.busy_s(chip, s) for s in ("init", "stream", "finalize")]
        assert sum(stages) <= busy * (1 + 1e-9)
        # every job was marked: the stream stage holds most of the device's time
        assert len(tr.marks[chip]) % 4 == 0 and stages[1] > 0.5 * busy
    gaps = tr.idle_gaps()
    assert all(label.startswith("bench/") or label == "none" for label, _ in gaps)
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    leaves = {n for c in tr.chips for n, _, _ in tr.leaves(c)}
    assert not any(n.startswith("%while") for n in leaves)


def test_recorded_kernel_trace():
    """Two cur_32k.gaussian jobs on a TPU v5e (64 panels each): 128 calls of
    the panel_update kernel, inside a while that the leaves leave out."""
    tr = xtrace.Trace.from_json(os.path.join(DATA, "cur_32k_gaussian_v5e.json.gz"))
    calls, seconds = tr.op_stats(0, r"^%panel_update_kernel")
    assert calls == 128
    assert 0 < seconds < tr.busy_s(0, "stream")
