"""bench/layers/permute_ms.batch.py on a trace written out by hand and on
the trace of one engine call recorded on a TPU v5e."""
import collections
import glob
import importlib.util
import os

import pytest
from jax.profiler import ProfileData

import cell
import phases
import trace_reduce
from test_phases import RECORDED, _ctx
from test_trace_reduce import KERNEL, _plane

NAME = "permute_ms.batch"


def _module():
    path = os.path.join(cell.BENCH, "layers", NAME + ".py")
    spec = importlib.util.spec_from_file_location("permute_ms", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _gather(i):
    return (f"%fusion.{i} = f32[8] fusion(f32[8] %p, s32[8] %i), "
            f"kind=kCustom, calls=%fused_computation.{i}")


def test_hand_trace_counts_gathers_inside_whole_calls():
    """Window 0..100 us, one engine call 10..60.  Device: a gather 12..15,
    a scatter 20..24 and a sort 25..27 inside a while 11..50, a plain
    fusion 30..40, a kernel 41..45, a gather 55..65 that outlives the
    call and a gather 70..72 after it: 3 + 4 + 2 us count."""
    device = _plane(1, "/device:TPU:0", "XLA Ops",
                    [(1, 11, 39), (2, 12, 3), (3, 20, 4), (8, 25, 2),
                     (4, 30, 10), (5, 41, 4), (6, 55, 10), (7, 70, 2)],
                    {1: "%while.1 = (f32[]) while()",
                     2: _gather(2), 3: _gather(3),
                     8: "%sort.8 = (f32[8], s32[8]) sort(f32[8] %w, "
                        "s32[8] %i), dimensions={0}",
                     4: "%fusion.4 = f32[8] fusion(), kind=kLoop",
                     5: f"%jvp_softsort_band_fwd_.5 = f32[8] custom-call(), "
                        f"{KERNEL}",
                     6: _gather(6), 7: _gather(7)})
    host = _plane(2, "/host:CPU", "python", [(1, 0, 100), (2, 10, 50)],
                  {1: "bench.window", 2: "bench.engine_call#call=1#"})
    start, calls, chips, found = _module().reduce(
        ProfileData.from_text_proto(device + "\n" + host))
    assert (start, calls, chips) == (0, 1, 1)
    assert [ns for _, ns in found] == [3_000, 4_000, 2_000]


def test_recorded_engine_call(monkeypatch):
    """The v5e trace of one call (B 1, N 256, d 50, 2 rounds of 8 inner
    steps, banded kernels, gathers and scatters throughout): seven
    gathers and scatters and the argsort run in every inner step, five
    gathers and scatters and three sorts in every round, and the reader
    gives their time for the one call."""
    path, = glob.glob(os.path.join(RECORDED, "*.xplane.pb"))
    start, calls, chips, found = _module().reduce(trace_reduce.load(path))
    assert (calls, chips) == (1, 1)
    runs = collections.Counter(op.split(" = ", 1)[0] for op, _ in found)
    assert sorted(collections.Counter(runs.values()).items()) == [
        (2, 8), (16, 8)]
    monkeypatch.setattr(phases, "WORK", RECORDED)
    ms = cell.layer_reader(NAME)(_ctx(path))
    assert ms == pytest.approx(sum(ns for _, ns in found) / 1e6)
    assert 0.1 < ms < 1.0
