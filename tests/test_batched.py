"""Batched multi-problem / multi-restart engine tests.

Deliberately hypothesis-free so this module runs everywhere — it is the
primary coverage for the batched throughput path when the property-test
modules are skipped for a missing ``hypothesis``.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.metrics import mean_neighbor_distance
from repro.core.softsort import is_valid_permutation, softsort_apply_chunked
from repro.core.shufflesoftsort import (
    ShuffleSoftSortConfig,
    shuffle_soft_sort,
    shuffle_soft_sort_batched,
)
from repro.kernels.ops import softsort_apply
from repro.kernels.ref import softsort_apply_ref


# ------------------------------------------------- engine: host spans

def test_fixed_path_spans_nest_in_order(tmp_path):
    """One call on the fixed-schedule fast path writes one ``sort.call``
    span to the profiler's trace, holding ``sort.prep``, ``sort.launch``,
    ``sort.wait`` and ``sort.collect`` in that order, on one thread."""
    import glob
    from jax.profiler import ProfileData
    cfg = ShuffleSoftSortConfig(rounds=2, inner_steps=2, chunk=16)
    xs = jax.random.uniform(jax.random.PRNGKey(3), (2, 16, 3))
    shuffle_soft_sort_batched(xs, (4, 4), cfg)           # compiles
    with jax.profiler.trace(str(tmp_path)):
        shuffle_soft_sort_batched(xs, (4, 4), cfg, n_restarts=2)
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = ("sort.call", "sort.prep", "sort.launch", "sort.wait",
             "sort.collect")
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            spans += [(ev.start_ns, ev.start_ns + ev.duration_ns,
                       ev.name.split("#")[0], line.name,
                       {k: v for k, v in ev.stats})
                      for ev in line.events
                      if ev.name.split("#")[0] in names]
    spans.sort()
    calls = [sp for sp in spans if sp[2] == "sort.call"]
    assert len(calls) == 1
    c0, c1, _, thread, args = calls[0]
    assert {k: int(v) for k, v in args.items()} == {
        "b": 2, "s": 2, "n": 16, "rounds": 2}
    phases = [sp for sp in spans if sp[2] != "sort.call"]
    assert [sp[2] for sp in phases] == [
        "sort.prep", "sort.launch", "sort.wait", "sort.collect"]
    assert all(sp[3] == thread and c0 <= sp[0] <= sp[1] <= c1
               for sp in phases)
    assert all(a[1] <= b[0] for a, b in zip(phases, phases[1:]))


# ------------------------------------------------- engine: bit-identity

def test_batched_bit_identical_to_sequential():
    """B x S = 8 instances must reproduce 8 sequential calls exactly."""
    b, s, n, hw = 4, 2, 36, (6, 6)
    cfg = ShuffleSoftSortConfig(rounds=6, inner_steps=4, chunk=36)
    xs = jax.random.uniform(jax.random.PRNGKey(42), (b, n, 2))
    keys = jnp.stack([jax.random.PRNGKey(100 + i) for i in range(b * s)])

    res = shuffle_soft_sort_batched(xs, hw, cfg, n_restarts=s, keys=keys)
    assert res.all_orders.shape == (b, s, n)
    for bi in range(b):
        for si in range(s):
            o, xs_sorted, losses = shuffle_soft_sort(
                xs[bi], hw, cfg, key=keys[bi * s + si])
            np.testing.assert_array_equal(res.all_orders[bi, si], o)
            np.testing.assert_array_equal(res.all_losses[bi, si],
                                          np.asarray(losses))


def test_batched_streaming_callback_matches_scan_path():
    b, n, hw = 3, 16, (4, 4)
    cfg = ShuffleSoftSortConfig(rounds=5, inner_steps=2, chunk=16)
    xs = jax.random.uniform(jax.random.PRNGKey(0), (b, n, 3))
    fast = shuffle_soft_sort_batched(xs, hw, cfg, key=jax.random.PRNGKey(7))
    seen = []
    slow = shuffle_soft_sort_batched(xs, hw, cfg, key=jax.random.PRNGKey(7),
                                     callback=lambda r, o, l: seen.append(r))
    assert seen == list(range(cfg.rounds))
    np.testing.assert_array_equal(fast.all_orders, slow.all_orders)
    np.testing.assert_array_equal(fast.all_losses, slow.all_losses)


def test_batched_result_contract():
    """(order, sorted, losses) per problem + restart bookkeeping."""
    b, s, n, hw = 2, 3, 16, (4, 4)
    cfg = ShuffleSoftSortConfig(rounds=4, inner_steps=2, chunk=16)
    xs = jax.random.uniform(jax.random.PRNGKey(1), (b, n, 2))
    res = shuffle_soft_sort_batched(xs, hw, cfg, n_restarts=s,
                                    key=jax.random.PRNGKey(2))
    assert res.order.shape == (b, n)
    assert res.sorted.shape == (b, n, 2)
    assert res.losses.shape == (b, cfg.rounds)
    assert res.all_losses.shape == (b, s, cfg.rounds)
    for bi in range(b):
        assert is_valid_permutation(res.order[bi])
        for si in range(s):
            assert is_valid_permutation(res.all_orders[bi, si])
    # Best restart is the argmin of final losses, and the reported
    # per-problem fields are that restart's.
    np.testing.assert_array_equal(res.best_restart,
                                  np.argmin(res.all_losses[:, :, -1], axis=1))
    for bi in range(b):
        np.testing.assert_array_equal(
            res.order[bi], res.all_orders[bi, res.best_restart[bi]])
        np.testing.assert_array_equal(res.sorted[bi],
                                      np.asarray(xs[bi])[res.order[bi]])
        np.testing.assert_array_equal(
            res.losses[bi], res.all_losses[bi, res.best_restart[bi]])


def test_batched_improves_layouts():
    b, n, hw = 3, 64, (8, 8)
    cfg = ShuffleSoftSortConfig(rounds=100, inner_steps=8, chunk=32)
    xs = jax.random.uniform(jax.random.PRNGKey(5), (b, n, 3))
    res = shuffle_soft_sort_batched(xs, hw, cfg, key=jax.random.PRNGKey(2))
    for bi in range(b):
        base = mean_neighbor_distance(np.asarray(xs[bi]), hw)
        assert mean_neighbor_distance(res.sorted[bi], hw) < 0.8 * base


def test_batched_kernel_path_runs():
    b, n, hw = 2, 16, (4, 4)
    cfg = ShuffleSoftSortConfig(rounds=2, inner_steps=2, use_kernel=True)
    xs = jax.random.uniform(jax.random.PRNGKey(3), (b, n, 2))
    res = shuffle_soft_sort_batched(xs, hw, cfg, n_restarts=2,
                                    key=jax.random.PRNGKey(4))
    for bi in range(b):
        for si in range(2):
            assert is_valid_permutation(res.all_orders[bi, si])
    assert np.isfinite(res.all_losses).all()


# ------------------------------------------- batched apply primitives

@pytest.mark.parametrize("n,d", [(64, 3), (100, 2), (300, 7)])
def test_batched_kernel_forward_matches_ref(n, d):
    b = 3
    w = jax.random.normal(jax.random.PRNGKey(n), (b, n)) * 2.0
    x = jax.random.normal(jax.random.PRNGKey(n + 1), (b, n, d))
    y, c = softsort_apply(w, x, 0.7)
    assert y.shape == (b, n, d) and c.shape == (b, n)
    for bi in range(b):
        yr, cr = softsort_apply_ref(w[bi], x[bi], 0.7)
        np.testing.assert_allclose(np.asarray(y[bi]), np.asarray(yr),
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(c[bi]), np.asarray(cr),
                                   atol=2e-5)


def test_batched_kernel_gradients_match_ref():
    b, n, d = 2, 129, 5
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    w = jax.random.normal(keys[0], (b, n)) * 3
    x = jax.random.normal(keys[1], (b, n, d))
    a = jax.random.normal(keys[2], (b, n, d))
    v = jax.random.normal(keys[3], (b, n))

    def loss(apply_fn):
        def f(w, x, tau):
            y, c = apply_fn(w, x, tau)
            return jnp.sum(y * a) + jnp.sum(c * v)
        return f

    gk = jax.grad(loss(lambda w, x, t: softsort_apply(w, x, t, 256, 256, 64)),
                  argnums=(0, 1, 2))(w, x, jnp.float32(0.6))
    ref_b = jax.vmap(softsort_apply_ref, in_axes=(0, 0, None))
    gr = jax.grad(loss(ref_b), argnums=(0, 1, 2))(w, x, jnp.float32(0.6))
    for kk, rr in zip(gk, gr):
        scale = float(jnp.max(jnp.abs(rr))) + 1e-9
        np.testing.assert_allclose(np.asarray(kk), np.asarray(rr),
                                   atol=2e-3 * scale)


def test_unbatched_kernel_is_b1_special_case():
    n, d = 200, 4
    w = jax.random.normal(jax.random.PRNGKey(7), (n,)) * 10
    x = jax.random.normal(jax.random.PRNGKey(8), (n, d))
    y1, c1 = softsort_apply(w, x, 0.5)
    yb, cb = softsort_apply(w[None], x[None], 0.5)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(yb[0]))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(cb[0]))


@pytest.mark.parametrize("chunk", [16, 64])
def test_batched_chunked_apply_matches_per_instance(chunk):
    b, n, d = 3, 64, 5
    w = jax.random.normal(jax.random.PRNGKey(0), (b, n))
    x = jax.random.normal(jax.random.PRNGKey(1), (b, n, d))
    yb, cb = softsort_apply_chunked(w, x, 0.7, chunk=chunk)
    assert yb.shape == (b, n, d) and cb.shape == (b, n)
    for bi in range(b):
        y, c = softsort_apply_chunked(w[bi], x[bi], 0.7, chunk=chunk)
        np.testing.assert_allclose(np.asarray(yb[bi]), np.asarray(y),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(cb[bi]), np.asarray(c),
                                   atol=1e-6)


# --------------------------------------------------- sort serving queue

def test_sort_server_coalesces_and_matches_sequential():
    from repro.launch.serve import SortServer

    n, hw, d = 16, (4, 4), 2
    cfg = ShuffleSoftSortConfig(rounds=4, inner_steps=2, chunk=16)
    server = SortServer(hw, d=d, cfg=cfg, max_batch=4, max_wait_ms=200.0)
    rng = np.random.RandomState(0)
    xs = rng.rand(4, n, d).astype(np.float32)
    try:
        futs = [server.submit(xs[i], key=jax.random.PRNGKey(i))
                for i in range(4)]
        results = [f.result(timeout=300) for f in futs]
    finally:
        server.close()

    # Coalesced: the continuous-batching scheduler dispatches one device
    # call per rung segment, with all 4 requests sharing each one — far
    # fewer dispatches than the 4 requests x 4 segments worst case.
    assert server.stats["requests"] == 4
    assert server.stats["batches"] < 4 * 4
    assert max(server.stats["batch_sizes"]) > 1
    for i, (order, xs_sorted, losses) in enumerate(results):
        o_ref, xs_ref, losses_ref = shuffle_soft_sort(
            xs[i], hw, cfg, key=jax.random.PRNGKey(i))
        np.testing.assert_array_equal(order, o_ref)
        np.testing.assert_array_equal(xs_sorted, xs_ref)
        np.testing.assert_array_equal(losses, np.asarray(losses_ref))
