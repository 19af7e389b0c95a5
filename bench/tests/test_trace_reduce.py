"""bench/trace_reduce.py on a trace written out by hand, where every
number can be counted, and on a small trace recorded on a TPU v5e."""
import glob
import os

import pytest
from jax.profiler import ProfileData

import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
KERNEL = 'custom_call_target=\\"tpu_custom_call\\"'


def _event(mid, start_us, dur_us):
    return (f"events {{ metadata_id: {mid} offset_ps: {start_us * 10**6} "
            f"duration_ps: {dur_us * 10**6} }}")


def _plane(pid, name, line, events, names):
    meta = "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                     f'name: "{n}" }} }}' for i, n in names.items())
    evs = "\n".join(_event(*e) for e in events)
    return (f'planes {{ id: {pid} name: "{name}" lines {{ id: 1 '
            f'name: "{line}" timestamp_ns: 0 {evs} }} {meta} }}')


def hand_trace():
    """Window 0..100 us.  Device: a while 10..50 holding a kernel
    15..25 and a fusion 30..40; a fusion 60..70; a kernel 80..85.
    Host: engine call 2..51, fetch 52..59, submit 72..78."""
    device = _plane(1, "/device:TPU:0", "XLA Ops",
                    [(1, 10, 40), (2, 15, 10), (3, 30, 10), (4, 60, 10),
                     (5, 80, 5)],
                    {1: "%while.3 = (f32[]) while()",
                     2: f"%jvp__.2 = f32[8] custom-call(), {KERNEL}",
                     3: "%fusion.7 = f32[8] fusion()",
                     4: "%fusion.9 = f32[8] fusion()",
                     5: f"%transpose_jvp___.4 = f32[8] custom-call(), "
                        f"{KERNEL}"})
    host = _plane(2, "/host:CPU", "python",
                  [(1, 0, 100), (2, 2, 49), (3, 52, 7), (4, 72, 6)],
                  {1: "bench.window", 2: "bench.engine_call#call=4#",
                   3: "bench.fetch", 4: "bench.submit"})
    return ProfileData.from_text_proto(device + "\n" + host)


def test_hand_trace_busy_idle_kernels():
    s = trace_reduce.summarize(hand_trace())
    assert s.window_ns == 100_000
    assert s.busy_ns == [55_000]                 # 40 + 10 + 5
    assert s.idle_share == pytest.approx(0.45)
    assert s.kernel_ns == 15_000                 # 10 + 5
    assert dict(s.top_ops) == pytest.approx(
        {"while": 20e-6, "fusion": 20e-6, "pallas:jvp__": 10e-6,
         "pallas:transpose_jvp___": 5e-6})
    assert dict(s.idle_gaps) == pytest.approx(
        {"bench.engine_call": 10e-6, "bench.fetch": 10e-6,
         "bench.submit": 10e-6, "bench.window": 15e-6})
    spans = {sp.name: sp.args for sp in s.spans}
    assert spans["bench.engine_call"] == {"call": "4"}


def test_hand_trace_kernels_inside_calls_only():
    s = trace_reduce.summarize(hand_trace(),
                               kernel_spans=[(2_000, 51_000)])
    assert s.kernel_ns == 10_000


def test_recorded_trace():
    """A trace of one run_round_segment call (grid 16x16, d 50, B 1,
    2 rounds) on a TPU v5e, inside a bench.engine_call span."""
    path, = glob.glob(os.path.join(DATA, "*.xplane.pb"))
    s = trace_reduce.summarize(trace_reduce.load(path))
    assert s.n_devices == 1
    assert 0 < s.busy_s <= s.window_s
    assert 0 < s.kernel_ns <= s.busy_ns[0]
    # Self times partition the busy time: ops only nest, never overlap.
    assert sum(t for _, t in s.top_ops) <= s.busy_s * (1 + 1e-9)
    kinds = dict(s.top_ops)
    assert any(k.startswith("pallas:") for k in kinds)
    assert any(sp.name == "bench.engine_call" for sp in s.spans)
