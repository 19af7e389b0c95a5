"""bench/phases.py and the readers built on it, on a trace written out
by hand, where every number can be counted, and on a small trace of one
engine call recorded on a TPU v5e."""
import glob
import os
import time

import pytest
from jax.profiler import ProfileData

import cell
import phases
import run
import trace_reduce
from test_trace_reduce import KERNEL, _plane, hand_trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "engine_call")
READERS = ("host_gap_ms.batch", "program_gap_ms.batch",
           "fwd_kernel_ms.batch", "bwd_kernel_ms.batch")


def phase_trace():
    """Window 0..200 us.  Host: engine call 10..150 holding sort.call
    11..149: prep 11..40, launch 40..50, wait 50..130, collect 130..149.
    Device: a fusion 20..30; a while 45..90 holding the forward sweep
    46..56, its colsum 58..62 and a backward pass 64..80; a while 95..125
    holding the other backward pass 100..110; a fusion 135..140; and a
    forward sweep 170..175 outside the call.  Idle: 0..20, 30..45,
    90..95, 125..135, 140..170, 175..200."""
    device = _plane(1, "/device:TPU:0", "XLA Ops",
                    [(1, 20, 10), (2, 45, 45), (3, 46, 10), (4, 58, 4),
                     (5, 64, 16), (6, 95, 30), (7, 100, 10), (8, 135, 5),
                     (9, 170, 5)],
                    {1: "%fusion.1 = f32[8] fusion()",
                     2: "%while.1 = (f32[]) while()",
                     3: f"%jvp_softsort_band_fwd_.3 = f32[8] custom-call(), "
                        f"{KERNEL}",
                     4: f"%jvp_softsort_band_colsum_.3 = f32[8] "
                        f"custom-call(), {KERNEL}",
                     5: f"%transpose_jvp_softsort_band_bwd_delta__.3 = "
                        f"f32[8] custom-call(), {KERNEL}",
                     6: "%while.2 = (f32[]) while()",
                     7: f"%transpose_jvp_softsort_band_bwd_dcol__.5 = "
                        f"f32[8] custom-call(), {KERNEL}",
                     8: "%fusion.2 = f32[8] fusion()",
                     9: f"%jvp_softsort_band_fwd_.9 = f32[8] custom-call(), "
                        f"{KERNEL}"})
    host = _plane(2, "/host:CPU", "python",
                  [(1, 0, 200), (2, 10, 140), (3, 11, 138), (4, 11, 29),
                   (5, 40, 10), (6, 50, 80), (7, 130, 19)],
                  {1: "bench.window", 2: "bench.engine_call#call=1#",
                   3: "sort.call#b=8#", 4: "sort.prep", 5: "sort.launch",
                   6: "sort.wait", 7: "sort.collect"})
    return ProfileData.from_text_proto(device + "\n" + host)


def test_phase_idle_and_kernels_by_name():
    p = phases.reduce(phase_trace())
    assert p.window_start == 0
    assert (p.calls, p.n_devices) == (1, 1)
    assert p.idle_ns == pytest.approx({
        "sort.prep": 19_000,          # 11..20, 30..40
        "sort.launch": 5_000,         # 40..45
        "sort.wait": 10_000,          # 90..95, 125..130
        "sort.collect": 14_000,       # 130..135, 140..149
        "sort.call": 48_000,          # the four phases
        "bench.engine_call": 50_000,  # and 10..11, 149..150
    })
    assert p.kernel_ns == pytest.approx({
        "pallas:jvp_softsort_band_fwd_": 10_000,
        "pallas:jvp_softsort_band_colsum_": 4_000,
        "pallas:transpose_jvp_softsort_band_bwd_delta__": 16_000,
        "pallas:transpose_jvp_softsort_band_bwd_dcol__": 10_000,
    })
    assert p.idle_ms(phases.HOST_PHASES) == pytest.approx(0.038)
    assert p.idle_ms((phases.WAIT_PHASE,)) == pytest.approx(0.010)
    assert p.kernel_ms(phases.FWD_KERNEL) == pytest.approx(0.014)
    assert p.kernel_ms(phases.BWD_KERNEL) == pytest.approx(0.026)
    # The same kernels as the harness's own count inside the call.
    s = trace_reduce.summarize(phase_trace(),
                               kernel_spans=[(10_000, 150_000)])
    assert sum(p.kernel_ns.values()) == s.kernel_ns


def test_program_without_spans_or_names_reads_nothing():
    """The trace of a program that has neither (its kernels named only
    by JAX, ``jvp__`` and ``transpose_jvp___``): every reading is None."""
    p = phases.reduce(hand_trace())
    assert p.calls == 1
    assert p.idle_ms(phases.HOST_PHASES) is None
    assert p.idle_ms((phases.WAIT_PHASE,)) is None
    assert p.kernel_ms(phases.FWD_KERNEL) is None
    assert p.kernel_ms(phases.BWD_KERNEL) is None


def _ctx(path):
    tracer = type("T", (), {"xplane": staticmethod(lambda: path)})
    summary = run._reduce(tracer, None, trace_reduce)
    return cell.LayerInput(summary=summary, counters={}, calls=[], peak={})


def test_recorded_engine_call(monkeypatch):
    """A trace of one shuffle_soft_sort_batched call (grid 16x16, d 50,
    B 1, 2 rounds, banded kernels) on a TPU v5e, inside bench.window and
    bench.engine_call spans: every reader reads a number, and the named
    kernels are all the kernel time the harness counts."""
    path, = glob.glob(os.path.join(RECORDED, "*.xplane.pb"))
    monkeypatch.setattr(phases, "WORK", RECORDED)
    ctx = _ctx(path)
    p = phases.of(ctx)
    assert p is not None and p.calls == 1
    assert set(phases.SPANS) <= set(p.idle_ns)
    assert sum(p.kernel_ns.values()) == ctx.summary.kernel_ns > 0
    for name in READERS:
        v = cell.layer_reader(name)(ctx)
        assert v is not None and v >= 0, name
    assert cell.layer_reader("fwd_kernel_ms.batch")(ctx) > 0
    assert cell.layer_reader("bwd_kernel_ms.batch")(ctx) > 0


def test_window_compiles_reader():
    """Counts the compiles between the first call after set-up's and the
    end of the last; set-up's own call is left out."""
    import jax
    import numpy as np
    from repro.launch.compile_cache import compile_counter
    read = cell.layer_reader("window_compiles.batch")
    compile_counter()

    def call(i, t0, t1):
        return cell.Call(i, t0, t1, 0.0, 0.0, 1)

    t0 = time.perf_counter()
    jax.jit(lambda x: x - 5)(np.ones(3, np.float32))     # "set-up"
    t1 = time.perf_counter()
    jax.jit(lambda x: x * 7)(np.ones(5, np.float32))
    t2 = time.perf_counter()
    assert read(cell.LayerInput(None, {}, [call(0, t0, t1),
                                           call(1, t1, t2)], {})) == 1
    t3 = time.perf_counter()
    assert read(cell.LayerInput(None, {}, [call(0, t0, t1),
                                           call(1, t2, t3)], {})) == 0
    assert read(cell.LayerInput(None, {}, [call(0, t0, t1)], {})) is None
