"""Fault tolerance / elastic / compression / SOG-codec tests."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.runtime.checkpoint import CheckpointManager
from repro.runtime.compression import compress_gradients, init_compression
from repro.runtime.fault_tolerance import (
    FaultInjector,
    TrainSupervisor,
    WorkerFailure,
)
from repro.runtime.straggler import StragglerMonitor
from repro.runtime import sog_compress
from repro.runtime.sog_compress import (
    WORKING_SET,
    _grid_hw,
    _quantize,
    codec_config,
    compress_checkpoint,
    compress_group,
    plan_group,
    sog_compress_tensor,
    sog_decompress_tensor,
)


# ------------------------------------------------------------- checkpoint

def _state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"w": jax.random.normal(k, (8, 8)),
            "opt": (jnp.zeros((8, 8)), jnp.int32(3)),
            "blocks": ({"a": jnp.ones((2, 3))},)}


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    st = _state()
    mgr.save(10, st)
    restored, step = mgr.restore(st)
    assert step == 10
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(restored)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_checkpoint_keep_k_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    st = _state()
    for s in (1, 2, 3, 4):
        mgr.save(s, st)
    assert mgr.all_steps() == [3, 4]


def test_checkpoint_atomic_no_partial_on_existing(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=False)
    st = _state()
    mgr.save(1, st)
    # tmp dir from an interrupted save must not shadow a published one
    os.makedirs(tmp_path / "tmp-99", exist_ok=True)
    assert mgr.latest_step() == 1
    restored, _ = mgr.restore(st)
    assert restored is not None


def test_checkpoint_async_save(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=True)
    st = _state()
    mgr.save(5, st)
    mgr.wait()
    assert mgr.latest_step() == 5


def test_checkpoint_stale_tmp_swept_on_init(tmp_path):
    """A crash mid-save strands tmp-<step> staging dirs; opening a
    manager over the directory must sweep them (they never published,
    so they are garbage by definition)."""
    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=False)
    mgr.save(1, _state())
    os.makedirs(tmp_path / "tmp-2")
    with open(tmp_path / "tmp-2" / "arrays.npz", "w") as f:
        f.write("half-written garbage")
    mgr2 = CheckpointManager(str(tmp_path), keep=3, async_save=False)
    assert not (tmp_path / "tmp-2").exists()
    assert mgr2.latest_step() == 1          # published steps untouched
    restored, _ = mgr2.restore(_state())
    assert restored is not None


def test_checkpoint_restore_num_leaves_mismatch_is_typed(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=False)
    mgr.save(1, _state())
    like = dict(_state(), extra_leaf=jnp.zeros(2))
    with pytest.raises(ValueError, match="layout changed"):
        mgr.restore(like)


def test_checkpoint_keep_k_gc_under_async_saves(tmp_path):
    """Keep-k GC with the async writer: save() serializes one in-flight
    write at a time, so a burst of async saves must still end with
    exactly the newest k checkpoints on disk, no torn tmp dirs."""
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=True)
    st = _state()
    for s in range(1, 6):
        mgr.save(s, st)
    mgr.wait()
    assert mgr.all_steps() == [4, 5]
    assert not [n for n in os.listdir(tmp_path) if n.startswith("tmp-")]
    restored, step = mgr.restore(st)
    assert step == 5


def test_checkpoint_restore_casts_to_like_dtype(tmp_path):
    """restore() casts each leaf to the like-leaf's dtype when it has
    one — the mixed-precision resume path — and leaves dtype-less
    (plain int) like-leaves uncast."""
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    st = {"w": np.arange(4, dtype=np.float32),
          "k": np.array([1, 2], np.uint32)}
    mgr.save(1, st)
    like = {"w": np.zeros(4, np.float64), "k": 0}
    restored, _ = mgr.restore(like)
    assert restored["w"].dtype == np.float64       # cast to like
    assert restored["k"].dtype == np.uint32        # int leaf: uncast
    np.testing.assert_array_equal(restored["w"], st["w"])
    np.testing.assert_array_equal(restored["k"], st["k"])


def test_checkpoint_resharding_on_load(tmp_path):
    """Elastic restart: restore with explicit (1-device) shardings."""
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    st = _state()
    mgr.save(7, st)
    mesh = jax.make_mesh((1,), ("data",))
    sh = jax.tree.map(
        lambda a: jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec()), st)
    restored, step = mgr.restore(st, shardings=sh)
    assert step == 7
    leaf = jax.tree.leaves(restored)[0]
    assert isinstance(leaf.sharding, jax.sharding.NamedSharding)


# ------------------------------------------------------------- supervisor

def _quadratic_problem():
    """Tiny convex problem so convergence is checkable."""
    target = jnp.array([1.0, -2.0, 3.0])

    @jax.jit
    def step(state, batch):
        w = state["w"]
        g = 2 * (w - target)
        w = w - 0.1 * g
        return {"w": w}, {"loss": jnp.sum((w - target) ** 2)}

    return step, {"w": jnp.zeros(3)}


def test_supervisor_runs_and_checkpoints(tmp_path):
    step, state0 = _quadratic_problem()
    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=False)
    sup = TrainSupervisor(step, lambda s: None, mgr, checkpoint_every=10)
    state, step_idx = sup.run(state0, 0, 50)
    assert step_idx == 50
    assert mgr.latest_step() == 50
    assert float(jnp.sum((state["w"] - jnp.array([1., -2., 3.])) ** 2)) < 1e-3


def test_supervisor_recovers_from_failures(tmp_path):
    base_step, state0 = _quadratic_problem()
    fail_at = {15, 27}

    calls = {"n": 0}

    def flaky_step(state, batch):
        calls["n"] += 1
        if calls["n"] in fail_at:
            raise WorkerFailure(f"injected at call {calls['n']}")
        return base_step(state, batch)

    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=False)
    sup = TrainSupervisor(flaky_step, lambda s: None, mgr,
                          checkpoint_every=5)
    state, step_idx = sup.run(state0, 0, 40)
    assert step_idx == 40
    assert sup.restarts == 2
    assert float(jnp.sum((state["w"] - jnp.array([1., -2., 3.])) ** 2)) < 1e-3


def test_supervisor_resumes_from_existing_checkpoint(tmp_path):
    step, state0 = _quadratic_problem()
    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=False)
    sup = TrainSupervisor(step, lambda s: None, mgr, checkpoint_every=10)
    sup.run(state0, 0, 20)
    # new supervisor, same dir: must resume at 20, not redo work
    sup2 = TrainSupervisor(step, lambda s: None, mgr, checkpoint_every=10)
    _, step_idx = sup2.run(state0, 0, 30)
    assert step_idx == 30


def test_supervisor_failure_before_first_checkpoint_restores_state(tmp_path):
    """Regression: a failure BEFORE the first checkpoint used to reset
    only the step counter while keeping the partially-advanced state —
    the retried run then advanced the counter state twice for the early
    steps.  The restart must replay from the INITIAL state."""
    calls = {"n": 0}

    def counting_step(state, batch):
        calls["n"] += 1
        if calls["n"] == 3:                # fail before any checkpoint
            raise WorkerFailure("early failure")
        return {"count": state["count"] + 1}, {"count": state["count"]}

    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=False)
    sup = TrainSupervisor(counting_step, lambda s: None, mgr,
                          checkpoint_every=100)
    state, step_idx = sup.run({"count": jnp.int32(0)}, 0, 10)
    assert step_idx == 10
    assert sup.restarts == 1
    # 2 steps advanced + failed attempt discarded + 10 clean steps:
    # final count must equal a clean run's, not 2 + 10.
    assert int(state["count"]) == 10


def test_fault_injector_thread_safe():
    """Concurrent dispatches must draw unique call indices: the chaos
    schedule fires each injected fault exactly once, and the counters
    add up, under heavy thread contention."""
    import threading

    inj = FaultInjector(lambda: "ok", fail_calls={5, 50, 500},
                        delay_calls={10: 0.0, 100: 0.0})
    outcomes = {"faults": 0, "ok": 0}
    lock = threading.Lock()

    def worker():
        for _ in range(100):
            try:
                inj()
            except WorkerFailure:
                with lock:
                    outcomes["faults"] += 1
            else:
                with lock:
                    outcomes["ok"] += 1

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert inj.calls == 800
    assert inj.faults == 3 and outcomes["faults"] == 3
    assert inj.delays == 2
    assert outcomes["ok"] == 797


# -------------------------------------------------------------- straggler

def test_straggler_detection():
    mon = StragglerMonitor(z=3.0, min_ratio=1.5, warmup=3)
    for i in range(20):
        mon.record(i, 0.1 + 0.001 * (i % 3))
    assert mon.flagged == []
    assert mon.record(20, 0.5)          # 5x slower: flagged
    assert mon.flagged and mon.flagged[0][0] == 20
    # baseline not poisoned by the outlier
    assert mon.mean < 0.12


def test_straggler_callback_fires():
    events = []
    mon = StragglerMonitor(z=3.0, warmup=3,
                           on_straggler=lambda s, dt, m: events.append(s))
    for i in range(10):
        mon.record(i, 0.05)
    mon.record(10, 1.0)
    assert events == [10]


def test_straggler_warmup_only_stream_never_flags():
    """A stream that ends inside the warmup window primes the EWMA but
    can never flag — even a wildly slow step is just more priming."""
    mon = StragglerMonitor(z=3.0, min_ratio=1.5, warmup=5)
    dts = [0.1, 0.1, 50.0, 0.1, 0.1]       # outlier inside warmup
    assert [mon.record(i, dt) for i, dt in enumerate(dts)] == [False] * 5
    assert mon.flagged == []
    assert mon.count == 5
    # warmup priming is a plain running mean over everything seen
    np.testing.assert_allclose(mon.mean, np.mean(dts), rtol=1e-12)


def test_straggler_first_post_warmup_step_can_flag():
    """The very first step after warmup is already judged against the
    primed baseline — no grace period beyond ``warmup``."""
    mon = StragglerMonitor(z=3.0, min_ratio=1.5, warmup=3)
    for i in range(3):
        mon.record(i, 0.1)
    assert mon.record(3, 5.0)              # step warmup+1, flagged
    assert mon.flagged == [(3, 5.0)]
    # and a healthy first post-warmup step does NOT flag
    mon2 = StragglerMonitor(z=3.0, min_ratio=1.5, warmup=3)
    for i in range(3):
        mon2.record(i, 0.1)
    assert not mon2.record(3, 0.1)


def test_straggler_baseline_updates_from_healthy_steps_only():
    """Flagged steps never enter the EWMA: after a burst of stragglers
    the mean is exactly what the healthy-only stream would produce."""
    mon = StragglerMonitor(z=3.0, min_ratio=1.5, alpha=0.05, warmup=3)
    twin = StragglerMonitor(z=3.0, min_ratio=1.5, alpha=0.05, warmup=3)
    healthy = [0.1, 0.1, 0.1, 0.11, 0.09, 0.1, 0.12, 0.1]
    mixed = healthy[:4] + [2.0, 3.0, 2.5] + healthy[4:]
    for i, dt in enumerate(mixed):
        mon.record(i, dt)
    for i, dt in enumerate(healthy):
        twin.record(i, dt)
    assert len(mon.flagged) == 3
    assert mon.mean == twin.mean           # bit-identical, not approx
    assert mon.var == twin.var


# ------------------------------------------------------------ compression

def test_int8_error_feedback_reduces_bias():
    k = jax.random.PRNGKey(0)
    grads = {"w": jax.random.normal(k, (256,))}
    state = init_compression(grads)
    acc_raw = jnp.zeros((256,))
    acc_deq = jnp.zeros((256,))
    for i in range(50):
        g = {"w": grads["w"] * (1.0 + 0.01 * i)}
        deq, state, _ = compress_gradients(g, state)
        acc_raw += g["w"]
        acc_deq += deq["w"]
    # error feedback: accumulated compressed grads track accumulated raw
    rel = float(jnp.linalg.norm(acc_deq - acc_raw)
                / jnp.linalg.norm(acc_raw))
    assert rel < 1e-2, rel


def test_compressed_training_converges():
    target = jnp.array([0.5, -1.5, 2.5, 0.0])
    w = jnp.zeros(4)
    state = init_compression({"w": w})
    for _ in range(300):
        g = {"w": 2 * (w - target)}
        deq, state, _ = compress_gradients(g, state)
        w = w - 0.05 * deq["w"]
    assert float(jnp.sum((w - target) ** 2)) < 1e-4


# --------------------------------------------------------------- SOG codec

def _structured_weight(d=48, f=256, seed=0):
    """Low-rank + noise: columns have correlated structure (like trained
    nets) so there is something for the sorter to exploit."""
    rng = np.random.RandomState(seed)
    u = rng.randn(d, 4)
    v = rng.randn(4, f)
    return (u @ v + 0.1 * rng.randn(d, f)).astype(np.float32)


def test_sog_tensor_roundtrip_exact_at_int8():
    w = _structured_weight()
    blob = sog_compress_tensor(w, sort_rounds=60)
    rec = sog_decompress_tensor(blob)
    q_err = np.max(np.abs(rec - w))
    # exact at the int8 quantization level
    assert q_err <= (np.max(np.abs(w)) / 127.0) * 1.01 + 1e-6


def test_sog_grid_never_degenerates_to_a_line():
    """Prime F used to collapse the sorting grid to 1 x F, starving the
    neighbor loss of its second dimension; now those F get a padded
    near-square grid (h * w >= F with fewer than one extra row)."""
    from repro.runtime.sog_compress import _grid_hw

    for n in (97, 113, 178, 254, 1009):    # primes and 2*prime shapes
        h, w = _grid_hw(n)
        assert h > 1, (n, h, w)
        assert w <= 2 * h, (n, h, w)       # near-square
        assert h * w >= n and h * w - n < h, (n, h, w)
    for n in (64, 100, 256, 12):           # composites keep exact grids
        h, w = _grid_hw(n)
        assert h * w == n, (n, h, w)


def test_sog_prime_column_count_roundtrips():
    w = _structured_weight(d=32, f=97)     # F=97 is prime
    blob = sog_compress_tensor(w, sort_rounds=30)
    assert sorted(blob["perm"].tolist()) == list(range(97))
    rec = sog_decompress_tensor(blob)
    assert rec.shape == w.shape
    q_err = np.max(np.abs(rec - w))
    assert q_err <= (np.max(np.abs(w)) / 127.0) * 1.01 + 1e-6


def test_sog_sorting_beats_unsorted_baseline():
    # larger tensor so the stored permutation (4F bytes) amortizes;
    # see EXPERIMENTS.md §SOG for the measured ~10% deflate gain
    w = _structured_weight(d=256, f=256)
    blob = sog_compress_tensor(w, sort_rounds=200)
    assert blob["bytes"] < blob["baseline_bytes"], (
        blob["bytes"], blob["baseline_bytes"])


def test_sog_checkpoint_pipeline():
    params = {
        "wq": jnp.asarray(_structured_weight(32, 128, 1)),
        "norm": jnp.ones((32,)),            # skipped (1-D)
        "emb": jnp.asarray(_structured_weight(16, 256, 2)),
    }
    out = compress_checkpoint(params, min_cols=64, sort_rounds=40)
    st = out["stats"]
    assert st["ratio_vs_raw"] > 2.0        # int8+deflate vs f32
    assert st["sog_bytes"] > 0
    blobs = [b for b in out["blobs"] if b is not None]
    assert len(blobs) == 2


def _experts(g, d, f, seed):
    """G seeded tensors whose columns share a rank-4 factor plus noise."""
    rng = np.random.default_rng(seed)
    u = rng.standard_normal((g, d, 4))
    z = rng.standard_normal((g, 4, f))
    return list((u @ z + 0.5 * rng.standard_normal((g, d, f)))
                .astype(np.float32))


def test_compress_group_equals_per_tensor_sorts():
    """One batched sort of G tensors gives each the order its own sort
    with the same key gives, and each blob decodes exactly to the int8
    tensor in that order."""
    ws = _experts(3, 24, 64, seed=5)
    keys = np.stack([np.asarray(jax.random.PRNGKey(40 + i))
                     for i in range(3)])
    cfg = codec_config(12)
    blobs = compress_group(ws, cfg=cfg, keys=keys)
    for w, key, blob in zip(ws, keys, blobs):
        alone, = compress_group([w], cfg=cfg, keys=key[None])
        np.testing.assert_array_equal(blob["perm"], alone["perm"])
        assert sorted(blob["perm"].tolist()) == list(range(64))
        q, scale = _quantize(w.T[blob["perm"]])
        want = np.empty_like(w.T)
        want[blob["perm"]] = q.astype(np.float32) * scale
        np.testing.assert_array_equal(sog_decompress_tensor(blob), want.T)


@pytest.mark.parametrize("d,f", [(4096, 4096), (4096, 16384),
                                 (262144, 4096), (4096, 262144)])
def test_plan_group_fits_device_memory(d, f):
    """command-a-plus-05-2026's expert, attention and embedding shapes,
    and the embedding's transpose, against half a v5e's 16 GiB: each
    group's working set fits the budget, and no width exceeds the
    banded kernels' (the 262144-wide embedding columns are cut)."""
    from repro.kernels.softsort_apply import band_max_width
    budget = 8 * 2**30
    width, tensors = plan_group(d, f, budget)
    h, w = _grid_hw(f)
    assert 1 <= width <= min(d, band_max_width(4)), width
    assert tensors * WORKING_SET * h * w * width * 4 <= budget
    if (d, f) == (4096, 4096):
        assert (width, tensors) == (4096, 10)     # whole columns, G >= 8
    if d == 262144:
        assert width == band_max_width(4)


def test_compress_group_cuts_over_wide_columns(monkeypatch):
    """Columns wider than the banded kernels hold are sorted on evenly
    spaced rows, as the tensor of just those rows is sorted whole, and
    still coded whole: each blob decodes exactly to the int8 tensor."""
    monkeypatch.setattr(sog_compress, "band_max_width", lambda itemsize: 16)
    ws = _experts(2, 40, 64, seed=11)
    keys = np.stack([np.asarray(jax.random.PRNGKey(80 + i))
                     for i in range(2)])
    cfg = codec_config(6)
    rows = np.arange(16) * 40 // 16
    cut = compress_group([w[rows] for w in ws], cfg=cfg, keys=keys)
    for w, blob, want in zip(ws, compress_group(ws, cfg=cfg, keys=keys),
                             cut):
        np.testing.assert_array_equal(blob["perm"], want["perm"])
        assert blob["shape"] == (40, 64)
        q, scale = _quantize(w.T[blob["perm"]])
        rec = np.empty_like(w.T)
        rec[blob["perm"]] = q.astype(np.float32) * scale
        np.testing.assert_array_equal(sog_decompress_tensor(blob), rec.T)


def test_compress_group_refuses_mixed_shapes():
    for ws in ([], [np.zeros(8, np.float32)],
               [np.zeros((4, 16), np.float32), np.zeros((4, 17), np.float32)]):
        with pytest.raises(ValueError, match="same-shape 2-D"):
            compress_group(ws, cfg=codec_config(2), keys=None)


def test_compress_group_first_rounds_match_reference():
    """The grouped path's first 3 rounds, on whole 640-wide columns of
    seeded weights at N 64, against the benchmark's plain reference
    (``bench/reference.py``): the same losses round by round, and the
    same order after the last."""
    import dataclasses
    import sys
    bench = os.path.join(os.path.dirname(__file__), "..", "bench")
    sys.path.insert(0, bench)
    try:
        import reference
    finally:
        sys.path.remove(bench)
    ws = _experts(2, 640, 64, seed=9)
    keys = np.stack([np.asarray(jax.random.PRNGKey(70 + i))
                     for i in range(2)])
    cfg = codec_config(3)
    ref = reference.Reference(dataclasses.asdict(cfg))
    for w, key, blob in zip(ws, keys, compress_group(ws, cfg=cfg,
                                                     keys=keys)):
        orders, losses = ref.rounds(w.T, np.arange(64), key, 0, 3)
        # 1e-5: the limit the benchmark's round_loss_gap holds the chip to.
        np.testing.assert_allclose(blob["losses"], losses, rtol=1e-5)
        np.testing.assert_array_equal(blob["perm"], orders[-1])


def test_compress_group_spans_nest(tmp_path):
    """A traced ``compress_group`` writes ``compress.group`` (args
    tensors, n, d) holding ``compress.sort``, which holds the engine's
    ``sort.call``, and then ``compress.encode``."""
    import glob
    from jax.profiler import ProfileData
    ws = _experts(2, 8, 16, seed=3)
    keys = np.stack([np.asarray(jax.random.PRNGKey(i)) for i in range(2)])
    cfg = codec_config(2)
    compress_group(ws, cfg=cfg, keys=keys)               # compiles
    with jax.profiler.trace(str(tmp_path)):
        compress_group(ws, cfg=cfg, keys=keys)
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = ("compress.group", "compress.sort", "compress.encode",
             "sort.call")
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                name = ev.name.split("#")[0]
                if name in names:
                    spans[name] = (ev.start_ns, ev.start_ns + ev.duration_ns,
                                   {k: v for k, v in ev.stats})
    assert sorted(spans) == sorted(names)
    g0, g1, args = spans["compress.group"]
    assert {k: int(v) for k, v in args.items()} == {
        "tensors": 2, "n": 16, "d": 8}
    s0, s1, _ = spans["compress.sort"]
    c0, c1, _ = spans["sort.call"]
    e0, e1, _ = spans["compress.encode"]
    assert g0 <= s0 <= c0 <= c1 <= s1 <= e0 <= e1 <= g1
