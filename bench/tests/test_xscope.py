"""Scopes and program spans on hand-made traces and on one recorded on a TPU
v5e (``look_scopes.py --small --raw``, reduced by ``xscope.load_dir``); the
base reduction reads what it read."""

import dataclasses
import os

import pytest

import xscope
import xtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
OLD = os.path.join(DATA, "cur_32k_gaussian_v5e.json.gz")
SCOPED = os.path.join(DATA, "cur_32k_scoped_v5e.json.gz")


def hand_made():
    # window [0, 100) ns; chip 0: a while in stream.sketch around a fusion
    # with no scope of its own and one in stream.mfold, then a psum; chip 1
    # one long operation with no scope
    ops = {0: [("%while.1", 0, 15), ("%fusion.1", 0, 10), ("%fusion.2", 10, 5),
               ("%all-reduce.3", 30, 10), ("%outside", 120, 5)],
           1: [("%fusion.1", 0, 60)]}
    scopes = {0: ["stream.sketch", None, "stream.mfold", "stream.psum", "stream.admit"],
              1: [None]}
    spans = [("bench/window", 0, 100), ("bench/stream", 0, 22), ("bench/finalize", 26, 48),
             ("bench/init", 50, 100)]
    program = [("stream/x/scan", 0, 22), ("stream/x/finalize", 26, 27),
               ("stream/x/init", 50, 100)]
    marks = {c: [("stream", 0), ("finalize", 26), ("end", 48), ("init", 50)] for c in (0, 1)}
    return xscope.ScopedTrace(ops=ops, spans=spans, marks=marks, scopes=scopes,
                              program_spans=program)


def test_scope_seconds_inherit_the_enclosing_operation():
    tr = hand_made()
    assert tr.scope_s(0, "stream.sketch") == pytest.approx(10e-9)  # %fusion.1, in the while
    assert tr.scope_s(0, "stream.mfold") == pytest.approx(5e-9)
    assert tr.scope_s(0, "stream.psum") == pytest.approx(10e-9)
    assert tr.scope_s(0, "stream.admit") == 0  # outside the window
    assert tr.scope_s(1, None) == pytest.approx(60e-9)
    assert tr.scope_s(0, "stream.psum", "stream") == 0  # [30, 40) is finalize's
    assert tr.scope_s(0, "stream.psum", "finalize") == pytest.approx(10e-9)
    assert tr.scope_s(1, None, "stream") == pytest.approx(26e-9)
    shares = tr.scope_shares(0, "stream")
    assert shares == {"stream.sketch": pytest.approx(10e-9), "stream.mfold": pytest.approx(5e-9)}


def test_idle_inside_program_spans():
    tr = hand_made()
    # spans cover [0, 22), [26, 27) and [50, 100): 73 ns; chip 0 busy 15 of them
    assert tr.idle_in_spans_s(0, "stream/") == pytest.approx(58e-9)
    assert tr.idle_in_spans_s(0, "stream/x/init") == pytest.approx(50e-9)
    # chip 1 busy [0, 60): 22 + 1 + 10 ns of the spans
    assert tr.idle_in_spans_s(1, "stream/") == pytest.approx(40e-9)
    assert tr.idle_in_spans_s(0, "serve/") == 0
    # chip 0's gaps [40, 100) and [15, 30), named by the program's spans
    assert tr.span_gaps() == [["stream/x/init", pytest.approx(60e-9)],
                              ["stream/x/scan", pytest.approx(15e-9)]]


def test_base_reduction_reads_the_same():
    tr = hand_made()
    base = xtrace.Trace(**{f.name: getattr(tr, f.name) for f in dataclasses.fields(xtrace.Trace)})
    assert tr.idle_gaps() == base.idle_gaps()
    assert tr.top_ops() == base.top_ops()
    for chip in tr.chips:
        assert tr.busy_s(chip) == base.busy_s(chip)
        assert tr.busy_s(chip, "stream") == base.busy_s(chip, "stream")


def test_json_round_trip(tmp_path):
    tr = hand_made()
    path = str(tmp_path / "t.json.gz")
    tr.to_json(path)
    assert xscope.ScopedTrace.from_json(path) == tr
    base = xtrace.Trace.from_json(path)
    assert base == xtrace.Trace(**{f.name: getattr(tr, f.name)
                                   for f in dataclasses.fields(xtrace.Trace)})


def test_scope_of_an_op_name_path():
    assert xscope.scope_of("jit(f)/while/body/closed_call/stream.mfold/scatter-add:") == "stream.mfold"
    assert xscope.scope_of("jit(f)/stream.admit/jit(_take)/stream.sketch/x:") == "stream.sketch"
    assert xscope.scope_of("jit(f)/finalize.solve:") == "finalize.solve"
    assert xscope.scope_of("jit(f)/while/body/dynamic_slice:") is None
    assert xscope.scope_of("jit(f)/upstream.mfold/x:") is None


def test_device_scopes_from_the_xplane(tmp_path):
    """The op-name path of a TPU operation is the ``tf_op`` stat of its event
    metadata (as a string or a reference to a stat name); a copy XLA
    inserted takes the scope of the last run of what it copies."""
    space = xscope._xspace_class()()
    plane = space.planes.add(name=b"/device:TPU:0")
    for key, name in ((1, b"tf_op"), (2, b"flops"), (3, b"jit(f)/stream.rows/gather:")):
        plane.stat_metadata.add(key=key, value={"id": key, "name": name})
    hlo = {10: b"%fusion.1 = f32[8] fusion(f32[8] %p)",
           11: b"%copy-start.2 = (f32[8], f32[8], u32[]) copy-start(f32[8] %fusion.1)",
           12: b"%copy-done.2 = f32[8] copy-done((f32[8], f32[8], u32[]) %copy-start.2)",
           13: b"%gather.3 = f32[8] gather(f32[8] %p, s32[2] %i)",
           14: b"%copy.4 = f32[8] copy(f32[8] %p)"}
    for key, text in hlo.items():
        meta = plane.event_metadata.add(key=key, value={"id": key, "name": text})
        if key == 10:
            meta.value.stats.add(metadata_id=1, str_value=b"jit(f)/while/body/stream.mfold/add:")
            meta.value.stats.add(metadata_id=2, str_value=b"7")
        if key == 13:
            meta.value.stats.add(metadata_id=1, ref_value=3)
    line = plane.lines.add(name=b"XLA Ops")
    for key in (10, 11, 12, 13, 14):
        line.events.add(metadata_id=key)
    space.planes.add(name=b"/host:CPU").lines.add(name=b"python").events.add(metadata_id=10)
    pb = tmp_path / "t.xplane.pb"
    pb.write_bytes(space.SerializeToString())
    assert xscope.device_scopes(str(pb)) == {
        0: ["stream.mfold", "stream.mfold", "stream.mfold", "stream.rows", None]}


def test_recorded_scoped_trace():
    """One cur_32k.gaussian job and one CountSketch CUR job of 2048 x 2048 on
    a TPU v5e, traced with the engine's scopes and spans."""
    tr = xscope.ScopedTrace.from_json(SCOPED)
    (chip,) = tr.chips
    assert len(tr.scopes[chip]) == len(tr.ops[chip])
    # every panel_update launch is in the kernel's scope, inside its while
    eff = tr.effective_scopes(chip)
    kernels = [op for op in tr.leaves(chip) if op[0].startswith("%panel_update_kernel")]
    assert len(kernels) == 64 and {eff[op] for op in kernels} == {"stream.panel_kernel"}
    shares = tr.scope_shares(chip, "stream")
    busy = tr.busy_s(chip, "stream")
    assert max(shares, key=shares.get) == "stream.panel_kernel"
    # the Gaussian route, and the CountSketch route's sketch, fold and admission
    assert {"stream.sketch", "stream.mfold", "stream.admit", "stream.chunk_fold",
            "stream.rows", "stream.panel_kernel"} <= set(shares)
    assert sum(v for k, v in shares.items() if k) >= 0.95 * busy
    assert tr.scope_s(chip, "finalize.solve") > 0.5 * tr.busy_s(chip, "finalize")
    # the program's spans: each job's init, scan and finalize, in order
    names = [n for n, _, _ in tr.program_spans]
    assert names == ["stream/adaptive_cur/init", "stream/adaptive_cur/scan",
                     "stream/adaptive_cur/finalize"] * 2
    idle = tr.window_s() - tr.busy_s(chip)
    assert 0 < tr.idle_in_spans_s(chip, "stream/") <= idle
    assert tr.idle_in_spans_s(chip, "stream/adaptive_cur/init") <= tr.idle_in_spans_s(chip, "stream/")
    assert all(label.startswith("stream/") or label == "none" for label, _ in tr.span_gaps())


def test_old_fixture_reads_as_before():
    """The recorded trace of the first chip benchmark: no scopes, no program
    spans, and every number of the base class unchanged."""
    old = xtrace.Trace.from_json(OLD)
    tr = xscope.ScopedTrace.from_json(OLD)
    assert not tr.scopes and not tr.program_spans
    assert tr.top_ops(10) == old.top_ops(10) and tr.idle_gaps(10) == old.idle_gaps(10)
    for chip in old.chips:
        for stage in (None, "init", "stream", "finalize"):
            assert tr.busy_s(chip, stage) == old.busy_s(chip, stage)
        assert tr.op_stats(chip, r"^%panel_update_kernel") == old.op_stats(chip, r"^%panel_update_kernel")
        assert tr.scope_s(chip, "stream.panel_kernel") == 0
        assert tr.idle_in_spans_s(chip, "stream/") == 0
