"""ShuffleSoftSort — Algorithm 1 of the paper.

Learns a permutation of N items with only N parameters by iterating:

  for r in 1..R:                      (outer: anneal tau, re-shuffle)
      tau_r = tau_start * (tau_end / tau_start) ** (r / R)
      w     = arange(N)               (linear init preserves incoming order)
      shuf  = randperm(N)
      for i in 1..I:                  (inner: a few SoftSort grad steps)
          tau_i = tau_r * (0.2 .. 1.0 ramp)
          P     = SoftSort_tau_i(w)           (streamed, never N^2)
          y     = unshuffle(P @ x[order][shuf])
          loss  = L_nbr(y) + l_s * L_s + l_sig * L_sigma      (eq. 2)
          w    <- Adam step
      order <- commit argsort(w) through the shuffle

The random shuffle re-linearizes the grid along a fresh path each outer
iteration, so elements can take long-range jumps that pure 1-D SoftSort
transport cannot (paper Fig. 3/4).  The whole outer body is one jitted
function; in the sequential API the R-loop stays in Python so callers
can stream metrics.

Because one instance costs only N parameters (vs Gumbel-Sinkhorn's N^2),
many instances fit on a device at once.  ``shuffle_soft_sort_batched``
exploits that: it vmaps the outer round over B problems x S restarts
(each with its own PRNG stream, shuffle, and Adam state), runs the whole
annealing schedule as one scanned device program when no streaming
callback is requested, and keeps each problem's best-loss restart.
Per-seed results are bit-identical to the sequential API.

Above one device, the same engine shards: pass a 1-D "data" mesh
(``repro.launch.mesh.make_sort_mesh``) and the flattened B x S instance
axis is shard_mapped across devices — same per-instance program, tail
shard padded, winner picked by a cross-device argmin — still per-seed
bit-identical.  ``restart_tournament`` layers successive halving on
top: anneal in rungs, cull the worst restarts at each boundary, spend
the freed compute finishing only plausible seeds.  Scaling and
cull-tradeoff measurements: EXPERIMENTS.md §Scaling.

``run_round_segment`` exposes the same engines to continuous-batching
servers (``repro.launch.serve.SortServer``): one scanned device call
advances BS instances by ``seg_len`` rounds where each instance
consumes its OWN slice of the tau schedule, so requests join and leave
the annealing loop at round boundaries — the tournament's rung
structure as a preemption point — without cohort barriers, and chained
``orders``/``keys`` keep every instance bit-identical to an
uninterrupted run.

Orthogonally, ``cfg.band`` swaps the O(N^2) SoftSort apply for the
O(N * K) banded tier once the anneal is cold enough: the schedule
splits at a single dense->banded switch round (``_band_switch_round``,
a host-side model of the tail bound on the trainer's linear re-init),
so every engine — sequential, vmap, shard_map, tournament — runs the
identical per-round apply and the bit-identity contracts above carry
over unchanged.  Banded model + measured tradeoff: EXPERIMENTS.md
§Perf.

Return contract, shared by every driver here: ``order`` is the (N,)
int32 permutation mapping grid cell -> input row, ``sorted`` is
``x[order]``, and ``losses`` is the per-round loss trace (leading batch
axes in the batched API).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.losses import grid_sorting_loss, mean_pairwise_distance
from repro.core.softsort import softsort_apply_banded, softsort_apply_chunked


@dataclasses.dataclass(frozen=True)
class ShuffleSoftSortConfig:
    rounds: int = 1000          # R — outer iterations (paper: "few hundred")
    inner_steps: int = 8        # I — SoftSort grad steps per round (paper: 4)
    tau_start: float = 1.0
    tau_end: float = 0.2        # below ~0.2 the SoftSort gradient vanishes
    inner_tau_ramp: float = 0.2  # inner tau starts at ramp*tau_r
    lr: float = 0.3             # calibrated: see EXPERIMENTS.md §Paper-claims
    b1: float = 0.5             # short inner runs want fast-adapting Adam
    b2: float = 0.9
    lambda_s: float = 1.0       # eq. 2 regularizer weights (paper values)
    lambda_sigma: float = 2.0
    chunk: int = 256            # row-block size for streamed softsort
    use_kernel: bool = False    # route the apply through the Pallas kernel
    # Banded apply tier (EXPERIMENTS.md §Perf): None = always dense;
    # an int K or "auto" enables the O(N*K) banded apply once the anneal
    # is cold enough that its modeled tail bound drops below band_eps —
    # early hot-tau rounds still run dense (see _band_switch_round).
    band: int | str | None = None
    band_eps: float = 1e-6      # tail-mass threshold for the tau switch
    # Kernel-tier compute precision ("float32" or "bfloat16"), honoured
    # only with use_kernel=True: bf16 halves the kernels' payload HBM
    # traffic and runs the score/payload math in bf16 while the keys
    # (the N parameters), softmax stats, accumulators, and this file's
    # Adam math all stay f32 (EXPERIMENTS.md §Perf precision table).
    compute_dtype: str = "float32"
    # Adaptive annealing (EXPERIMENTS.md §Adaptive).  "fixed" runs the
    # precomputed R-round schedule to the end — byte-for-byte the
    # behavior before the adaptive tier existed.  "adaptive" runs the
    # SAME nominal schedule under core.annealing.AdaptiveController:
    # when an instance's per-round loss EWMA improves by less than
    # plateau_rtol (relative) for patience consecutive rungs, it jumps
    # decay_rungs rungs ahead in the schedule (colder tau early; a jump
    # past the end stops the instance at that boundary), and the
    # dense->banded switch comes from the MEASURED band_tail_bound on
    # the instance's own keys instead of the linear-init model.  All
    # decisions are per-instance and host-side, so adaptive runs stay
    # bit-identical per seed across every engine path.
    schedule: str = "fixed"     # "fixed" | "adaptive"
    adapt_every: int = 0        # decision quantum in rounds (0 = auto:
                                # largest divisor of rounds <= rounds/8)
    patience: int = 2           # plateau rungs before a tau jump
    plateau_rtol: float = 1e-3  # relative EWMA improvement threshold
    ewma_alpha: float = 0.5     # per-round loss EWMA smoothing
    decay_rungs: int = 1        # rungs skipped per plateau fire


class NumericalDivergence(RuntimeError):
    """A rung-boundary sentinel saw a non-finite loss (or trained key).

    SoftSort's ``exp(-|w - sorted(w)| / tau)`` relaxation under/overflows
    exactly where long anneals spend most of their time — cold tau,
    reduced precision — and a NaN that enters the loss silently poisons
    the Adam moments and every later round.  The engines therefore check
    the (host-side, already-materialized) per-round losses at each rung
    boundary and raise this typed error with enough context to act on:
    ``round`` (first non-finite global round), ``tau`` (the nominal
    schedule temperature there), ``dtype`` (``cfg.compute_dtype``), and
    ``context`` (which engine tripped).  ``runtime.fault_tolerance
    .AnnealSupervisor`` catches it and — under an opt-in
    ``DivergencePolicy`` — retries from the last rung checkpoint with
    escalating fallbacks (EXPERIMENTS.md §Robustness).
    """

    def __init__(self, message: str, *, round: int | None = None,
                 tau: float | None = None, dtype: str | None = None,
                 context: str | None = None):
        super().__init__(message)
        self.round = round
        self.tau = tau
        self.dtype = dtype
        self.context = context


def _check_finite(losses_seg, start: int, cfg: "ShuffleSoftSortConfig",
                  context: str, ws=None) -> None:
    """Host-side divergence sentinel over one segment's losses.

    ``losses_seg`` is (T, ...) round-major, covering global rounds
    [start, start + T); ``ws`` optionally carries end-of-rung trained
    keys (the adaptive path has them on host anyway).  Raises
    ``NumericalDivergence`` pinpointing the first non-finite round.
    """
    losses_seg = np.asarray(losses_seg)
    bad = ~np.isfinite(losses_seg)
    if bad.any():
        per_round = bad.reshape(losses_seg.shape[0], -1).any(axis=1)
        t = int(np.argmax(per_round))
        rnd = start + t
        taus = _tau_schedule(cfg)
        tau = float(taus[min(rnd, cfg.rounds - 1)])
        raise NumericalDivergence(
            f"non-finite loss at round {rnd} (tau~{tau:.4g}, "
            f"compute_dtype={cfg.compute_dtype}, engine={context})",
            round=rnd, tau=tau, dtype=cfg.compute_dtype, context=context)
    if ws is not None and not np.isfinite(np.asarray(ws)).all():
        rnd = start + losses_seg.shape[0] - 1
        taus = _tau_schedule(cfg)
        tau = float(taus[min(rnd, cfg.rounds - 1)])
        raise NumericalDivergence(
            f"non-finite trained keys at round {rnd} (tau~{tau:.4g}, "
            f"compute_dtype={cfg.compute_dtype}, engine={context})",
            round=rnd, tau=tau, dtype=cfg.compute_dtype, context=context)


def _loss_fn(w, x_shuf, inv_shuf, tau, hw, norm, cfg: ShuffleSoftSortConfig,
             apply_fn) -> jnp.ndarray:
    y_shuf, colsum = apply_fn(w, x_shuf, tau)
    y = y_shuf[inv_shuf]  # reverse-shuffle: loss sees the grid layout
    return grid_sorting_loss(
        y, colsum, x_shuf, hw, norm,
        lambda_s=cfg.lambda_s, lambda_sigma=cfg.lambda_sigma)


def _outer_round_full(x, order, key, tau_r, norm, *, hw,
                      cfg: ShuffleSoftSortConfig, apply_fn):
    """``_outer_round_impl`` plus the round's final trained keys ``w``.

    The adaptive controller's measured dense->banded switch needs the
    end-of-round ``w`` to evaluate the true tail bound; the fixed
    engines wrap this and drop ``w`` (same trace — the extra output was
    always computed as the fori_loop carry), so exposing it does not
    perturb the fixed path.
    """
    n = x.shape[0]
    shuf = jax.random.permutation(key, n)
    inv_shuf = jnp.argsort(shuf)
    x_cur = x[order]
    x_shuf = x_cur[shuf]

    w0 = jnp.arange(n, dtype=jnp.float32)
    grad_fn = jax.value_and_grad(_loss_fn)

    def inner(i, carry):
        w, mu, nu, _ = carry
        frac = i.astype(jnp.float32) / jnp.maximum(cfg.inner_steps - 1, 1)
        tau_i = tau_r * (cfg.inner_tau_ramp + (1.0 - cfg.inner_tau_ramp) * frac)
        loss, g = grad_fn(w, x_shuf, inv_shuf, tau_i, hw, norm, cfg, apply_fn)
        t = i.astype(jnp.float32) + 1.0
        mu = cfg.b1 * mu + (1 - cfg.b1) * g
        nu = cfg.b2 * nu + (1 - cfg.b2) * jnp.square(g)
        mhat = mu / (1 - cfg.b1 ** t)
        nuhat = nu / (1 - cfg.b2 ** t)
        w = w - cfg.lr * mhat / (jnp.sqrt(nuhat) + 1e-8)
        return (w, mu, nu, loss)

    w, _, _, loss = jax.lax.fori_loop(
        0, cfg.inner_steps, inner,
        (w0, jnp.zeros_like(w0), jnp.zeros_like(w0), jnp.float32(0.0)))

    # Commit the hard permutation through the shuffle:
    #   new_grid[shuf[i]] = x_shuf[sort_idx[i]] = x_cur[shuf[sort_idx[i]]]
    sort_idx = jnp.argsort(w)          # == argmax(P_soft, -1) with repaired ties
    g = jnp.zeros(n, dtype=jnp.int32).at[shuf].set(shuf[sort_idx])
    return order[g], loss, w


def _outer_round_impl(x, order, key, tau_r, norm, *, hw,
                      cfg: ShuffleSoftSortConfig, apply_fn):
    """One un-jitted outer round for a single problem instance.

    This is the unit the batched engine vmaps: every array argument is
    per-instance ((N, d) / (N,) / PRNG key), so ``jax.vmap`` over a
    leading batch axis gives B independent rounds — each with its own
    shuffle, PRNG stream, and (implicitly, via the inner fori_loop
    carry) its own Adam state.
    """
    order, loss, _ = _outer_round_full(x, order, key, tau_r, norm,
                                       hw=hw, cfg=cfg, apply_fn=apply_fn)
    return order, loss


_outer_round = functools.partial(
    jax.jit,
    static_argnames=("hw", "cfg", "apply_fn"),
    donate_argnums=(1,),
)(_outer_round_impl)


@functools.partial(
    jax.jit,
    static_argnames=("hw", "cfg", "apply_fn"),
    donate_argnums=(1,),
)
def _outer_round_batched(xs, orders, keys, tau_r, norms, *, hw,
                         cfg: ShuffleSoftSortConfig, apply_fn):
    """Vmapped outer round over a leading batch axis.

    Args:
      xs:     (BS, N, d) problem instances (restarts are tiled copies).
      orders: (BS, N) int32 current permutations.
      keys:   (BS, 2) uint32 per-instance PRNG keys for this round.
      tau_r:  scalar round temperature, shared across the batch.
      norms:  (BS,) per-instance loss normalization constants.

    Returns:
      (orders, losses): (BS, N) int32 and (BS,) float32.
    """
    def one(x, order, key, norm):
        return _outer_round_impl(x, order, key, tau_r, norm,
                                 hw=hw, cfg=cfg, apply_fn=apply_fn)

    return jax.vmap(one)(xs, orders, keys, norms)


def _run_rounds_impl(xs, orders, keys, taus, norms, *, hw,
                     cfg: ShuffleSoftSortConfig, apply_fn):
    """Whole-schedule batched run: lax.scan over the R outer rounds.

    One device program instead of R dispatches — the throughput path the
    batched benchmark measures.  Numerically identical to calling
    ``_outer_round_batched`` once per round (the scan body is the same
    vmapped round, consuming the same per-instance key splits), so
    results stay bit-identical to the sequential API per seed.

    Un-jitted on purpose: this is both the body ``_run_rounds_batched``
    jits for the single-device vmap engine AND the per-shard program
    ``_run_rounds_sharded`` maps over the mesh "data" axis — the two
    paths literally run the same code per instance, which is what makes
    the sharded engine's bit-identity contract hold.

    Args:
      taus: (R,) float32 precomputed outer-round temperature schedule
        (any contiguous slice of the full schedule works — the
        tournament scheduler runs the anneal rung by rung).

    Returns:
      (orders (BS, N), keys (BS, 2), losses (R, BS)).
    """
    def step(carry, tau_r):
        orders, keys = carry
        pair = jax.vmap(jax.random.split)(keys)
        keys, subs = pair[:, 0], pair[:, 1]

        def one(x, order, key, norm):
            return _outer_round_impl(x, order, key, tau_r, norm,
                                     hw=hw, cfg=cfg, apply_fn=apply_fn)

        orders, losses = jax.vmap(one)(xs, orders, subs, norms)
        return (orders, keys), losses

    (orders, keys), losses = jax.lax.scan(step, (orders, keys), taus)
    return orders, keys, losses


_run_rounds_batched = functools.partial(
    jax.jit,
    static_argnames=("hw", "cfg", "apply_fn"),
    donate_argnums=(1,),
)(_run_rounds_impl)


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "hw", "cfg", "apply_fn"),
)
def _run_rounds_sharded(xs, orders, keys, taus, norms, *, mesh, hw,
                        cfg: ShuffleSoftSortConfig, apply_fn):
    """Mesh-sharded whole-schedule run: ``_run_rounds_impl`` shard_mapped
    over the mesh's "data" axis.

    The flattened B x S instance axis is split across devices; each
    shard runs the identical scanned program on its slice (instances
    are embarrassingly parallel — no collectives until best-restart
    selection), so per-seed results are bit-identical to the vmap
    engine.  Callers pad the leading axis to a multiple of the mesh
    size first (``_pad_instances``).  Measured scaling lives in
    EXPERIMENTS.md §Scaling.
    """
    body = functools.partial(_run_rounds_impl, hw=hw, cfg=cfg,
                             apply_fn=apply_fn)
    # check_vma=False: the body is purely per-shard (no collectives), so
    # there is no replication for the checker to verify.
    return shard_map(
        body, mesh=mesh,
        in_specs=(P("data"), P("data"), P("data"), P(), P("data")),
        out_specs=(P("data"), P("data"), P(None, "data")),
        check_vma=False,
    )(xs, orders, keys, taus, norms)


@functools.partial(jax.jit, static_argnames=("b", "s"))
def _best_restart_device(orders, losses_rb, *, b, s):
    """Cross-device argmin over the restart axis.

    One jitted program over the still-sharded engine outputs; XLA
    inserts the gather/reduce collectives, so winner selection is a
    mesh-native step rather than host post-processing.  (The batched
    result contract also reports every restart, so the host gathers
    the full arrays regardless — this function exists to keep the
    selection itself on-device, and the tests assert it agrees with
    the host-side argmin exactly.)

    Returns (best (B,) int32, best_orders (B, N) int32).
    """
    final = losses_rb[-1, :b * s].reshape(b, s)
    best = jnp.argmin(final, axis=1)
    rows = jnp.arange(b) * s + best
    return best, orders[rows]


def _pad_instances(arrs, to: int):
    """Pad each array's leading instance axis to ``to`` rows by repeating
    instance 0 — valid (discarded) work, so uneven B x S grids shard
    over any mesh size."""
    out = []
    for a in arrs:
        p = to - a.shape[0]
        out.append(a if p == 0 else
                   jnp.concatenate([a, jnp.repeat(a[:1], p, axis=0)], axis=0))
    return out


def _engine_run(xs_t, orders, keys, taus, norms_t, *, hw, cfg, apply_fn,
                mesh):
    """Run one contiguous slice of the anneal on BS flattened instances,
    dispatching to the vmap engine (``mesh=None``) or the shard_map
    engine (padding/unpadding the instance axis to the mesh size).

    Returns (orders (BS, N), keys (BS, 2), losses (R_slice, BS)) — the
    sharded outputs stay device-resident jax Arrays sharded over "data".
    """
    taus = jnp.asarray(taus)
    if mesh is None:
        return _run_rounds_batched(xs_t, orders, keys, taus, norms_t,
                                   hw=hw, cfg=cfg, apply_fn=apply_fn)
    d_mesh = mesh.shape["data"]
    bs = xs_t.shape[0]
    pad = (-bs) % d_mesh
    if pad:
        xs_t, orders, keys, norms_t = _pad_instances(
            (xs_t, orders, keys, norms_t), bs + pad)
    orders, keys, losses = _run_rounds_sharded(
        xs_t, orders, keys, taus, norms_t,
        mesh=mesh, hw=hw, cfg=cfg, apply_fn=apply_fn)
    if pad:
        orders, keys, losses = orders[:bs], keys[:bs], losses[:, :bs]
    return orders, keys, losses


def _run_segments(xs_t, orders, keys, taus, norms_t, *, start: int,
                  switch: int, hw, cfg: ShuffleSoftSortConfig,
                  dense_fn, band_fn, mesh):
    """Run a contiguous slice of the anneal, splitting it at the
    dense->banded switch round.

    ``taus`` is the slice covering global rounds [start, start +
    len(taus)); ``switch`` is the GLOBAL round index from
    ``_band_switch_round`` (so the tournament's per-rung slices land on
    the same per-round apply the uninterrupted engines use — the
    bit-identity contract needs every engine to agree round-by-round on
    which apply ran).  At most two ``_engine_run`` calls: the dense
    prefix and the banded suffix; keys/orders chain through, so the PRNG
    streams are exactly those of a single unsegmented run.

    Returns (orders (BS, N), keys (BS, 2), losses (R_slice, BS)).
    """
    end = start + len(taus)
    cut = min(max(switch, start), end)
    parts = []
    for s0, s1, fn in ((start, cut, dense_fn), (cut, end, band_fn)):
        if s1 > s0:
            orders, keys, seg = _engine_run(
                xs_t, orders, keys, taus[s0 - start:s1 - start], norms_t,
                hw=hw, cfg=cfg, apply_fn=fn, mesh=mesh)
            parts.append(seg)
    losses = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
    return orders, keys, losses


# --------------------------------------------------------------------------
# Rung-boundary checkpointing (EXPERIMENTS.md §Robustness).
# --------------------------------------------------------------------------

def _open_checkpointer(checkpoint_dir, resume):
    """Resolve the ``checkpoint_dir=`` / ``resume=`` knobs to an
    ``AnnealCheckpointer`` (or None).  Imported lazily: core stays
    importable without the runtime package on the path."""
    if checkpoint_dir is None:
        if resume:
            raise ValueError("resume=True requires checkpoint_dir=")
        return None
    from repro.runtime.anneal_checkpoint import AnnealCheckpointer
    return AnnealCheckpointer(str(checkpoint_dir))


def _open_guardrails(guardrail, cfg: "ShuffleSoftSortConfig",
                     context: str):
    """Resolve the ``guardrail=`` knob to a ``GuardrailMonitor`` (or
    None).  Accepts a ``GuardrailPolicy`` (a fresh monitor is built for
    this run) or an existing monitor (callers that want to read
    incident history afterwards).  Imported lazily, like
    ``_open_checkpointer`` — core stays importable without the runtime
    package on the path."""
    if guardrail is None:
        return None
    from repro.runtime.guardrails import GuardrailMonitor, GuardrailPolicy
    if isinstance(guardrail, GuardrailMonitor):
        return guardrail if guardrail.active else None
    if not isinstance(guardrail, GuardrailPolicy):
        raise TypeError(
            "guardrail= must be a GuardrailPolicy or GuardrailMonitor, "
            f"got {guardrail!r}")
    if guardrail.mode == "off":
        return None
    return GuardrailMonitor(guardrail, context=context,
                            dtype=cfg.compute_dtype)


def _checkpoint_edges(rounds: int, every: int) -> list[int]:
    """Rung-boundary rounds at which the fixed engines checkpoint:
    every ``every`` rounds, with a final edge at ``rounds``."""
    every = max(1, int(every))
    edges = list(range(every, rounds, every))
    if not edges or edges[-1] != rounds:
        edges.append(rounds)
    return edges


def _engine_meta(kind: str, cfg: ShuffleSoftSortConfig, n: int, bs: int,
                 hw) -> dict:
    """Checkpoint meta record: the structural fingerprint a resume must
    match (everything but ``cfg``, which the divergence-degradation
    ladder is allowed to adjust mid-run) plus the full config repr for
    audit."""
    return {"engine": kind, "rounds": int(cfg.rounds), "n": int(n),
            "instances": int(bs), "hw": list(hw),
            "schedule": cfg.schedule, "cfg": repr(cfg)}


def _meta_expect(meta: dict) -> dict:
    return {k: v for k, v in meta.items() if k != "cfg"}


def _run_fixed_checkpointed(xs_t, orders, keys, taus, norms_t, *,
                            switch: int, hw,
                            cfg: ShuffleSoftSortConfig, dense_fn, band_fn,
                            mesh, ckpt, resume: bool, every: int,
                            rung_hook, meta: dict,
                            check_finite: bool = True,
                            band: int | None = None, monitor=None,
                            mesh_hook=None):
    """Fixed-schedule batched run in checkpointed rung segments.

    Chains ``_run_segments`` calls across the checkpoint edges — the
    PR 6 segment-chaining contract makes the chained run bit-identical
    to the single-dispatch fast path, so adding checkpoints never
    perturbs results.  After each segment the full cross-round carry
    (orders, chained keys, losses so far) is published atomically; on
    ``resume`` the run restarts from the newest checkpoint's round (a
    bare directory starts from scratch).  ``rung_hook(start_round)``
    fires before each segment — the chaos harness's kill point.

    ``mesh_hook(start_round, mesh) -> mesh | None`` fires right after
    ``rung_hook`` and may return a REPLACEMENT mesh to run the next
    segment on — the elastic re-shard point.  Because the carry is
    layout-free (``_engine_run`` re-pads per call), swapping the mesh at
    a rung boundary is purely a throughput change: results stay
    bit-identical per seed (tests/test_elastic.py).

    With a ``monitor`` (``runtime.guardrails.GuardrailMonitor``) the
    integrity probes run on each rung's synced state AFTER the finite
    sentinel and BEFORE ``ckpt.save`` — so the newest checkpoint is
    always the last *verified* rung, and a violation replays from
    there.  Shadow-sampled rungs snapshot the rung's input orders/keys
    to host first (the engines donate their input buffers) and re-run
    the segment through the pure-jnp oracle tier for comparison.

    Returns (orders (BS, N), keys (BS, 2), losses (R, BS) np.float32).
    """
    rounds = int(cfg.rounds)
    start = 0
    parts: list[np.ndarray] = []
    mon = monitor if (monitor is not None and monitor.active) else None
    if mon is not None:
        cfg_o = dataclasses.replace(cfg, use_kernel=False)
        dense_o = _select_apply_fn(cfg_o)
        band_o = dense_o if band is None else _select_apply_fn(cfg_o, band)
    if resume and ckpt is not None:
        got = ckpt.restore_latest(_meta_expect(meta))
        if got is not None:
            state, start, _ = got
            orders = jnp.asarray(state["orders"])
            keys = jnp.asarray(state["keys"])
            if start > 0:
                parts.append(np.asarray(state["losses"], np.float32))
            if start >= rounds:
                return orders, keys, parts[0]
    for end in _checkpoint_edges(rounds, every):
        if end <= start:
            continue
        if rung_hook is not None:
            rung_hook(start)
        if mesh_hook is not None:
            new_mesh = mesh_hook(start, mesh)
            if new_mesh is not None:
                mesh = new_mesh
                # The carry is committed to the old mesh's devices;
                # round-trip it through host numpy so the next dispatch
                # re-places (and re-pads) it onto the new mesh.
                orders = jnp.asarray(np.asarray(orders))
                keys = jnp.asarray(np.asarray(keys))
        k_in = o_in = None
        if mon is not None:
            k_in = np.asarray(keys)
            if mon.wants_shadow(start):
                o_in = np.asarray(orders)
        orders, keys, seg = _run_segments(
            xs_t, orders, keys, taus[start:end], norms_t, start=start,
            switch=switch, hw=hw, cfg=cfg, dense_fn=dense_fn,
            band_fn=band_fn, mesh=mesh)
        seg_np = np.asarray(seg, np.float32)
        if check_finite:
            _check_finite(seg_np, start, cfg, meta["engine"])
        if mon is not None:
            oracle_l = oracle_o = None
            if o_in is not None:
                o_sh, _, seg_sh = _run_segments(
                    xs_t, jnp.asarray(o_in), jnp.asarray(k_in),
                    taus[start:end], norms_t, start=start, switch=switch,
                    hw=hw, cfg=cfg_o, dense_fn=dense_o, band_fn=band_o,
                    mesh=mesh)
                oracle_l = np.asarray(seg_sh, np.float32)
                if mon.compare_orders():
                    oracle_o = np.asarray(o_sh)
            mon.check_rung(
                start=start, losses=seg_np, orders=np.asarray(orders),
                keys_in=k_in, keys_out=np.asarray(keys),
                seg_len=end - start, tau=float(taus[start]),
                oracle_losses=oracle_l, oracle_orders=oracle_o)
        parts.append(seg_np)
        if ckpt is not None:
            ckpt.save(end, {"orders": np.asarray(orders),
                            "keys": np.asarray(keys),
                            "losses": np.concatenate(parts, axis=0)},
                      meta=dict(meta, round=end))
        start = end
    losses = parts[0] if len(parts) == 1 else np.concatenate(parts, axis=0)
    return orders, keys, losses


def _run_rounds_ragged_impl(xs, orders, keys, tau_rows, norms, *, hw,
                            cfg: ShuffleSoftSortConfig, apply_fn):
    """Per-instance-temperature variant of ``_run_rounds_impl``.

    ``tau_rows`` is (T, BS): row t holds each instance's OWN outer-round
    temperature for the t-th round of this segment, so instances at
    DIFFERENT global positions in the anneal can share one scanned
    device program — the primitive continuous-batching servers need to
    let requests join and leave at round boundaries without waiting for
    a whole cohort to finish.  The scan body is the same vmapped
    ``_outer_round_impl`` the homogeneous engines run, with tau promoted
    from a broadcast scalar to a vmapped per-instance input; the tau
    math is elementwise f32, so per instance the computed values — and
    hence the committed orders and PRNG stream — are bit-identical to a
    homogeneous run at the same temperatures (asserted in
    tests/test_serving.py across the jnp, kernel, and banded tiers).

    Returns (orders (BS, N), keys (BS, 2), losses (T, BS)).
    """
    def step(carry, tau_b):
        orders, keys = carry
        pair = jax.vmap(jax.random.split)(keys)
        keys, subs = pair[:, 0], pair[:, 1]

        def one(x, order, key, norm, tau_r):
            return _outer_round_impl(x, order, key, tau_r, norm,
                                     hw=hw, cfg=cfg, apply_fn=apply_fn)

        orders, losses = jax.vmap(one)(xs, orders, subs, norms, tau_b)
        return (orders, keys), losses

    (orders, keys), losses = jax.lax.scan(step, (orders, keys), tau_rows)
    return orders, keys, losses


_run_rounds_ragged = functools.partial(
    jax.jit,
    static_argnames=("hw", "cfg", "apply_fn"),
    donate_argnums=(1,),
)(_run_rounds_ragged_impl)


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "hw", "cfg", "apply_fn"),
)
def _run_rounds_ragged_sharded(xs, orders, keys, tau_rows, norms, *, mesh,
                               hw, cfg: ShuffleSoftSortConfig, apply_fn):
    """``_run_rounds_ragged_impl`` shard_mapped over the mesh "data"
    axis: the instance axis (and each instance's tau column) splits
    across devices, each shard runs the identical ragged program on its
    slice.  Same check_vma=False rationale as ``_run_rounds_sharded``."""
    body = functools.partial(_run_rounds_ragged_impl, hw=hw, cfg=cfg,
                             apply_fn=apply_fn)
    return shard_map(
        body, mesh=mesh,
        in_specs=(P("data"), P("data"), P("data"), P(None, "data"),
                  P("data")),
        out_specs=(P("data"), P("data"), P(None, "data")),
        check_vma=False,
    )(xs, orders, keys, tau_rows, norms)


def _run_rounds_ragged_w_impl(xs, orders, keys, tau_rows, norms, *, hw,
                              cfg: ShuffleSoftSortConfig, apply_fn):
    """``_run_rounds_ragged_impl`` that additionally returns the LAST
    round's trained keys ``w`` per instance.

    The adaptive controller evaluates the measured ``band_tail_bound``
    on these at every rung boundary (the ws ride in the scan carry, so
    only the final round's (BS, N) slab leaves the device).  The
    orders/losses/keys math is the identical vmapped
    ``_outer_round_full`` body, so values are bit-identical to the
    plain ragged engine at the same temperatures.

    Returns (orders (BS, N), keys (BS, 2), losses (T, BS), ws (BS, N)).
    """
    def step(carry, tau_b):
        orders, keys, _ = carry
        pair = jax.vmap(jax.random.split)(keys)
        keys, subs = pair[:, 0], pair[:, 1]

        def one(x, order, key, norm, tau_r):
            return _outer_round_full(x, order, key, tau_r, norm,
                                     hw=hw, cfg=cfg, apply_fn=apply_fn)

        orders, losses, ws = jax.vmap(one)(xs, orders, subs, norms, tau_b)
        return (orders, keys, ws), losses

    ws0 = jnp.zeros(xs.shape[:2], jnp.float32)
    (orders, keys, ws), losses = jax.lax.scan(
        step, (orders, keys, ws0), tau_rows)
    return orders, keys, losses, ws


_run_rounds_ragged_w = functools.partial(
    jax.jit,
    static_argnames=("hw", "cfg", "apply_fn"),
    donate_argnums=(1,),
)(_run_rounds_ragged_w_impl)


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "hw", "cfg", "apply_fn"),
)
def _run_rounds_ragged_w_sharded(xs, orders, keys, tau_rows, norms, *,
                                 mesh, hw, cfg: ShuffleSoftSortConfig,
                                 apply_fn):
    """``_run_rounds_ragged_w_impl`` shard_mapped over the mesh "data"
    axis.  Same check_vma=False rationale as ``_run_rounds_sharded``."""
    body = functools.partial(_run_rounds_ragged_w_impl, hw=hw, cfg=cfg,
                             apply_fn=apply_fn)
    return shard_map(
        body, mesh=mesh,
        in_specs=(P("data"), P("data"), P("data"), P(None, "data"),
                  P("data")),
        out_specs=(P("data"), P("data"), P(None, "data"), P("data")),
        check_vma=False,
    )(xs, orders, keys, tau_rows, norms)


def _ragged_w_run(xs_t, orders, keys, tau_rows, norms_t, *, hw, cfg,
                  apply_fn, mesh):
    """Dispatch one ragged-with-w segment to the vmap or shard_map
    engine, padding/unpadding the instance axis (and each padded
    instance's tau column) to the mesh size.

    Returns (orders (BS, N), keys (BS, 2), losses (T, BS), ws (BS, N)).
    """
    tau_rows = jnp.asarray(tau_rows)
    if mesh is None:
        return _run_rounds_ragged_w(xs_t, orders, keys, tau_rows, norms_t,
                                    hw=hw, cfg=cfg, apply_fn=apply_fn)
    d_mesh = mesh.shape["data"]
    bs = xs_t.shape[0]
    pad = (-bs) % d_mesh
    if pad:
        xs_t, orders, keys, norms_t = _pad_instances(
            (xs_t, orders, keys, norms_t), bs + pad)
        tau_rows = jnp.concatenate(
            [tau_rows, jnp.repeat(tau_rows[:, :1], pad, axis=1)], axis=1)
    o, k, l, w = _run_rounds_ragged_w_sharded(
        xs_t, orders, keys, tau_rows, norms_t,
        mesh=mesh, hw=hw, cfg=cfg, apply_fn=apply_fn)
    if pad:
        o, k, l, w = o[:bs], k[:bs], l[:, :bs], w[:bs]
    return o, k, l, w


def _check_schedule(cfg: ShuffleSoftSortConfig) -> None:
    if cfg.schedule not in ("fixed", "adaptive"):
        raise ValueError(
            f"cfg.schedule={cfg.schedule!r} must be 'fixed' or 'adaptive'")


def make_adaptive_controller(cfg: ShuffleSoftSortConfig, n_instances: int,
                             n: int, seg_len: int | None = None):
    """Build a ``core.annealing.AdaptiveController`` wired to this
    config's tau schedule and resolved band half-width for problem size
    ``n``.  ``seg_len`` overrides the decision quantum (it must divide
    ``cfg.rounds``) — ``SortServer`` passes its own rung length so
    controller boundaries land exactly on scheduler boundaries."""
    from repro.core.annealing import AdaptiveController, adaptive_seg_len
    return AdaptiveController(
        cfg, n_instances, taus=_tau_schedule(cfg),
        band=resolve_band(cfg, n),
        seg_len=adaptive_seg_len(cfg) if seg_len is None else int(seg_len))


def _run_adaptive(xs_t, orders, keys, norms_t, *, hw,
                  cfg: ShuffleSoftSortConfig, mesh, controller,
                  boundary_hook=None, ckpt=None, resume: bool = False,
                  meta: dict | None = None, rung_hook=None,
                  hook_state: dict | None = None,
                  check_finite: bool = True, monitor=None,
                  mesh_hook=None):
    """Host-side adaptive decision loop around the ragged engines.

    Each iteration advances every live instance by one ``seg_len`` rung
    — live instances are grouped by apply regime (dense vs banded, per
    the controller's MEASURED switch state) and each group runs as one
    ragged dispatch consuming its instances' own schedule slices; the
    controller then observes the rung's losses and end-of-rung keys and
    decides jumps / stops / switches for the next rung.  Stopped (or
    culled) instances simply leave the dispatch groups — their PRNG
    streams are per-instance, so survivors are unperturbed.

    ``boundary_hook(step, controller, losses)`` runs after each
    boundary's observe — the tournament culls from it.

    Checkpointing (EXPERIMENTS.md §Robustness): with ``ckpt`` every
    committed rung publishes orders/keys/losses plus the controller's
    full ``state_dict`` and — for callers whose boundary hook carries
    its own cross-rung state (the adaptive tournament's alive sets) —
    the entries of ``hook_state`` (a mutable dict the caller owns;
    restored IN PLACE on resume, so the hook closure sees the resumed
    values).  ``rung_hook(executed_rounds)`` fires at the TOP of each
    rung, before any work — a kill there loses at most the in-flight
    rung, and the resumed run replays it from the last committed
    boundary bit-identically (the controller's decisions are pure
    functions of committed observations).  ``mesh_hook(executed_rounds,
    mesh) -> mesh | None`` fires right after ``rung_hook`` and may
    swap in a replacement mesh for the remaining rungs — the elastic
    re-shard point; the ragged carry is layout-free, so the swap is
    bit-identity-preserving (tests/test_elastic.py).

    Returns (orders (BS, N) device, keys (BS, 2) device,
    losses (BS, R) np.float32 — NaN at never-executed rounds,
    device_rounds — instance-rounds spent, mesh padding included).
    """
    ctrl = controller
    seg = ctrl.seg_len
    bs, n = xs_t.shape[0], xs_t.shape[1]
    dense_fn = _select_apply_fn(cfg)
    band_fn = (dense_fn if ctrl.band is None
               else _select_apply_fn(cfg, ctrl.band))
    mon = monitor if (monitor is not None and monitor.active) else None
    if mon is not None:
        cfg_o = dataclasses.replace(cfg, use_kernel=False)
        dense_o = _select_apply_fn(cfg_o)
        band_o = (dense_o if ctrl.band is None
                  else _select_apply_fn(cfg_o, ctrl.band))
    losses_mat = np.full((bs, cfg.rounds), np.nan, np.float32)
    d_mesh = 1 if mesh is None else mesh.shape["data"]
    device_rounds = 0
    step = 0
    if resume and ckpt is not None:
        got = ckpt.restore_latest(_meta_expect(meta or {}))
        if got is not None:
            state, _, m = got
            orders = jnp.asarray(state["orders"])
            keys = jnp.asarray(state["keys"])
            losses_mat = np.asarray(state["losses"], np.float32).copy()
            ctrl.load_state_dict(
                {f: state["ctrl_" + f] for f in ctrl._STATE_FIELDS})
            if hook_state is not None:
                hook_state.clear()
                hook_state.update({k[3:]: np.asarray(v)
                                   for k, v in state.items()
                                   if k.startswith("hs_")})
            step = int(m["step"])
            device_rounds = int(m["device_rounds"])
    while True:
        live = ctrl.live_indices()
        if live.size == 0:
            break
        if rung_hook is not None:
            rung_hook(step * seg)
        if mesh_hook is not None:
            new_mesh = mesh_hook(step * seg, mesh)
            if new_mesh is not None:
                mesh = new_mesh
                d_mesh = mesh.shape["data"]
                # Drop the device-committed carry to host: the next
                # ragged dispatch re-places it on the new mesh.
                orders = jnp.asarray(np.asarray(orders))
                keys = jnp.asarray(np.asarray(keys))
        # All live instances have executed exactly step * seg rounds —
        # stopped instances never rejoin, so executed stays uniform.
        exec0 = step * seg
        seg_losses = np.empty((live.size, seg), np.float32)
        ws_live = np.empty((live.size, n), np.float32)
        banded_mask = ctrl.banded[live]
        want_shadow = mon is not None and mon.wants_shadow(exec0)
        if mon is not None:
            orders_live = np.empty((live.size, n), np.int32)
            keys_in = np.asarray(jnp.take(keys, jnp.asarray(live), axis=0))
        if want_shadow:
            shadow_l = np.empty((live.size, seg), np.float32)
            shadow_o = np.empty((live.size, n), np.int32)
        for is_banded in (False, True):
            sel = np.flatnonzero(banded_mask == is_banded)
            if sel.size == 0:
                continue
            gidx = live[sel]
            rows = jnp.asarray(gidx)
            tau_rows_g = ctrl.tau_rows(gidx)
            # Shadow rungs snapshot the group's input carry to host
            # BEFORE the primary dispatch: the ragged engines donate
            # their input buffers, so post-hoc reads would be invalid.
            if want_shadow:
                o_in = np.asarray(jnp.take(orders, rows, axis=0))
                k_in = np.asarray(jnp.take(keys, rows, axis=0))
            o, k2, l, w = _ragged_w_run(
                jnp.take(xs_t, rows, axis=0),
                jnp.take(orders, rows, axis=0),
                jnp.take(keys, rows, axis=0),
                tau_rows_g,
                jnp.take(norms_t, rows, axis=0),
                hw=hw, cfg=cfg,
                apply_fn=band_fn if is_banded else dense_fn, mesh=mesh)
            orders = orders.at[rows].set(o)
            keys = keys.at[rows].set(k2)
            seg_losses[sel] = np.asarray(l).T
            ws_live[sel] = np.asarray(w)
            if mon is not None:
                orders_live[sel] = np.asarray(o)
            if want_shadow:
                o_sh, _, l_sh, _ = _ragged_w_run(
                    jnp.take(xs_t, rows, axis=0), jnp.asarray(o_in),
                    jnp.asarray(k_in), tau_rows_g,
                    jnp.take(norms_t, rows, axis=0),
                    hw=hw, cfg=cfg_o,
                    apply_fn=band_o if is_banded else dense_o, mesh=mesh)
                shadow_l[sel] = np.asarray(l_sh).T
                shadow_o[sel] = np.asarray(o_sh)
            device_rounds += seg * (-(-gidx.size // d_mesh) * d_mesh)
        if check_finite:
            _check_finite(seg_losses.T, exec0, cfg, "adaptive", ws=ws_live)
        if mon is not None:
            tau_vec = np.asarray(ctrl.tau_rows(live))[0]
            mon.check_rung(
                start=exec0, losses=seg_losses.T, orders=orders_live,
                keys_in=keys_in,
                keys_out=np.asarray(
                    jnp.take(keys, jnp.asarray(live), axis=0)),
                seg_len=seg, ws=ws_live, tau=tau_vec, band=ctrl.band,
                banded_mask=banded_mask,
                oracle_losses=shadow_l.T if want_shadow else None,
                oracle_orders=(shadow_o if want_shadow
                               and mon.compare_orders() else None))
        losses_mat[live, exec0:exec0 + seg] = seg_losses
        ctrl.observe(live, seg_losses, ws_live)
        if boundary_hook is not None:
            boundary_hook(step + 1, ctrl, losses_mat)
        step += 1
        if ckpt is not None:
            st = {"orders": np.asarray(orders), "keys": np.asarray(keys),
                  "losses": losses_mat.copy()}
            for f in ctrl._STATE_FIELDS:
                st["ctrl_" + f] = getattr(ctrl, f).copy()
            if hook_state is not None:
                for k, v in hook_state.items():
                    st["hs_" + k] = np.asarray(v)
            ckpt.save(step, st, meta=dict(meta or {}, step=step,
                                          device_rounds=device_rounds))
    return orders, keys, losses_mat, device_rounds


def rung_aligned_switch(cfg: ShuffleSoftSortConfig, n: int,
                        seg_len: int) -> int:
    """The dense->banded switch round snapped UP to the next multiple of
    ``seg_len`` (capped at ``cfg.rounds``).

    A continuous-batching scheduler preempts only at rung boundaries
    (multiples of its segment length), so it cannot split a segment at a
    mid-rung switch the way ``_run_segments`` does — instead the switch
    is deferred to the next boundary: a few extra rounds run dense
    (exact, just costlier) and no segment ever straddles regimes.  With
    this snapped switch every instance whose progress is a boundary
    multiple is unambiguously in ONE regime, which is what
    ``run_round_segment`` requires of its callers.
    """
    switch = _band_switch_round(cfg, n)
    if switch >= cfg.rounds:
        return cfg.rounds
    return min(-(-switch // seg_len) * seg_len, cfg.rounds)


def run_round_segment(xs, orders, keys, norms, progress, seg_len: int, *,
                      hw, cfg: ShuffleSoftSortConfig, mesh=None,
                      regime: str | None = None, with_w: bool = False,
                      guardrail=None):
    """Round-boundary join/leave hook for continuous-batching servers.

    Runs ``seg_len`` outer rounds on BS flattened instances where
    instance i consumes ITS OWN slice ``[progress[i], progress[i] +
    seg_len)`` of the tau schedule — so a device batch can mix requests
    that joined the annealing loop at different times, and a request
    leaves (or is preempted, culled, or re-queued after a fault) at any
    boundary without perturbing the survivors.  Chaining the returned
    ``orders``/``keys`` through successive calls reproduces an
    uninterrupted run bit-exactly, the same contract the tournament's
    rung segments rely on.

    Banded dispatch: all instances in one call must be in the same
    apply regime relative to the RUNG-ALIGNED switch round
    (``rung_aligned_switch``) — callers group instances by regime; a
    mixed or straddling segment raises ``ValueError`` rather than
    silently running the wrong apply.  An adaptive scheduler that
    decides regimes from the MEASURED tail bound instead passes
    ``regime="dense"`` / ``"banded"`` explicitly, which bypasses the
    model-based check (the caller owns the grouping); ``with_w=True``
    additionally returns each instance's end-of-segment trained keys —
    the observation ``core.annealing.AdaptiveController`` consumes.

    Args:
      xs:      (BS, N, d) instances.
      orders:  (BS, N) int32 current permutations.
      keys:    (BS, 2) uint32 current per-instance PRNG keys.
      norms:   (BS,) float32 per-instance loss normalizations.
      progress: (BS,) int — each instance's current global round.
      seg_len: rounds to run (the scheduler's preemption quantum).
      mesh:    optional 1-D "data" mesh; instance axis is shard_mapped
        (tail padded with discarded copies of instance 0).
      regime:  None (default) infers the apply regime from the
        model-based rung-aligned switch and validates the batch against
        it; "dense" / "banded" selects the apply explicitly (adaptive
        schedulers own the grouping).
      with_w:  also return the end-of-segment trained keys.
      guardrail: optional ``runtime.guardrails.GuardrailPolicy`` (or
        monitor) — runs the permutation-integrity probes on this
        segment's results (bijectivity, loss sanity, PRNG key-chain,
        band-tail audit when ``with_w``, and sampled oracle shadow
        recompute), raising ``IntegrityViolation`` on corruption.
        Probes are read-only; results are unchanged.

    Returns:
      (orders (BS, N), keys (BS, 2), losses (seg_len, BS)) — plus
      ``ws (BS, N)`` as a fourth element when ``with_w=True``.
    """
    xs = jnp.asarray(xs, jnp.float32)
    orders = jnp.asarray(orders, jnp.int32)
    keys = jnp.asarray(keys)
    norms = jnp.asarray(norms, jnp.float32)
    seg_len = int(seg_len)
    n = xs.shape[1]
    p = np.asarray(progress, np.int64)
    assert seg_len >= 1, seg_len
    assert p.shape == (xs.shape[0],), (p.shape, xs.shape)
    if (p < 0).any() or (p + seg_len > cfg.rounds).any():
        raise ValueError(
            f"segment [{p.min()}, {p.max() + seg_len}) escapes the "
            f"{cfg.rounds}-round schedule")
    band = resolve_band(cfg, n)
    if regime is not None:
        if regime not in ("dense", "banded"):
            raise ValueError(f"regime={regime!r} must be 'dense' or "
                             "'banded'")
        if regime == "banded" and band is None:
            raise ValueError("regime='banded' requires a resolvable "
                             "cfg.band for this problem size")
        seg_banded = regime == "banded"
        apply_fn = (_select_apply_fn(cfg, band) if seg_banded
                    else _select_apply_fn(cfg))
    else:
        switch = rung_aligned_switch(cfg, n, seg_len)
        if band is None or (p + seg_len <= switch).all():
            seg_banded = False
            apply_fn = _select_apply_fn(cfg)
        elif (p >= switch).all():
            seg_banded = True
            apply_fn = _select_apply_fn(cfg, band)
        else:
            raise ValueError(
                f"instances at rounds {sorted(set(p.tolist()))} mix apply "
                f"regimes across the rung-aligned dense->banded switch "
                f"{switch}; group instances by regime "
                f"(rung_aligned_switch)")

    mon = _open_guardrails(guardrail, cfg, "segment")
    o_in_np = k_in_np = None
    shadow = False
    if mon is not None:
        # Host snapshots BEFORE dispatch: the ragged engines donate
        # their input orders buffers.  Taken pre-padding so the shadow
        # recursion sees the caller's exact instance set.
        o_in_np = np.asarray(orders)
        k_in_np = np.asarray(keys)
        xs0, norms0, p0 = xs, norms, p.copy()
        shadow = mon.wants_shadow(int(p.min()))

    bs = xs.shape[0]
    if mesh is not None:
        d_mesh = mesh.shape["data"]
        pad = (-bs) % d_mesh
        if pad:
            xs, orders, keys, norms = _pad_instances(
                (xs, orders, keys, norms), bs + pad)
            p = np.concatenate([p, np.repeat(p[:1], pad)])
    taus = _tau_schedule(cfg)
    tau_rows = jnp.asarray(taus[p[:, None] + np.arange(seg_len)].T)
    ws = None
    if with_w:
        if mesh is None:
            orders, keys, losses, ws = _run_rounds_ragged_w(
                xs, orders, keys, tau_rows, norms,
                hw=hw, cfg=cfg, apply_fn=apply_fn)
        else:
            orders, keys, losses, ws = _run_rounds_ragged_w_sharded(
                xs, orders, keys, tau_rows, norms,
                mesh=mesh, hw=hw, cfg=cfg, apply_fn=apply_fn)
            orders, keys = orders[:bs], keys[:bs]
            losses, ws = losses[:, :bs], ws[:bs]
    else:
        if mesh is None:
            orders, keys, losses = _run_rounds_ragged(
                xs, orders, keys, tau_rows, norms,
                hw=hw, cfg=cfg, apply_fn=apply_fn)
        else:
            orders, keys, losses = _run_rounds_ragged_sharded(
                xs, orders, keys, tau_rows, norms,
                mesh=mesh, hw=hw, cfg=cfg, apply_fn=apply_fn)
            orders, keys, losses = orders[:bs], keys[:bs], losses[:, :bs]
    if mon is not None:
        oracle_l = oracle_o = None
        if shadow:
            res_sh = run_round_segment(
                xs0, o_in_np, k_in_np, norms0, p0, seg_len, hw=hw,
                cfg=dataclasses.replace(cfg, use_kernel=False),
                mesh=mesh, regime=regime)
            oracle_l = np.asarray(res_sh[2], np.float32)
            if mon.compare_orders():
                oracle_o = np.asarray(res_sh[0])
        mon.check_rung(
            start=int(p0.min()), losses=np.asarray(losses, np.float32),
            orders=np.asarray(orders), n=n, keys_in=k_in_np,
            keys_out=np.asarray(keys), seg_len=seg_len,
            ws=None if ws is None else np.asarray(ws),
            tau=taus[p0].astype(np.float32),
            band=band if (seg_banded and ws is not None) else None,
            oracle_losses=oracle_l, oracle_orders=oracle_o)
    if with_w:
        return orders, keys, losses, ws
    return orders, keys, losses


def _tau_schedule(cfg: ShuffleSoftSortConfig) -> np.ndarray:
    """Outer-round temperatures, (R,) float32: geometric anneal from
    tau_start to tau_end.

    Single source of truth for BOTH engines: the batched API's
    "per-seed bit-identical to sequential" contract holds only while
    the two paths consume the exact same float32 values, so neither
    may inline its own copy of the formula.
    """
    return np.float32(cfg.tau_start * (cfg.tau_end / cfg.tau_start)
                      ** (np.arange(1, cfg.rounds + 1) / cfg.rounds))


@functools.lru_cache(maxsize=None)
def _select_apply_fn(cfg: ShuffleSoftSortConfig, band: int | None = None):
    """Resolve (``use_kernel``, ``band``) to a per-instance apply callable.

    Memoized on the (frozen, hashable) config: the returned partial is
    the STATIC ``apply_fn`` argument of every jitted engine, and jax
    caches static callables by identity — without the cache each
    public-API call would mint a fresh partial and recompile, which a
    continuous-batching server dispatching one rung at a time cannot
    afford (one recompile per rung instead of one per shape).

    ``use_kernel=False`` — streamed pure-jnp ``softsort_apply_chunked``
    (runs everywhere; the everywhere-runnable oracle twin of the kernel
    path).  ``use_kernel=True`` — the fused Pallas TPU path from
    ``repro.kernels.ops``, which now covers the FULL train step: the
    forward is one online-softmax sweep plus the colsum pass, and the
    backward runs in Pallas too, reusing the forward's ``(perm, m, l,
    y)`` residuals instead of falling back to a jnp re-computation
    (``interpret=True`` on the CPU backend; measured pass-count /
    HBM-traffic win in EXPERIMENTS.md §Perf).

    ``band`` (a RESOLVED half-width, see ``resolve_band``) swaps in the
    O(N * K) banded variant of whichever tier is selected — the windowed
    pure-jnp oracle or the band-grid Pallas kernels.  All four callables
    compute (P_soft @ x, colsum(P_soft)) without an (N, N) array and all
    are vmap- and grad-compatible, so every engine (sequential, vmap,
    mesh, tournament) accepts any of them transparently.

    ``cfg.compute_dtype`` reaches only the kernel paths (the jnp oracle
    tiers are the full-precision reference and stay f32), and the
    kernels' block sizes come from the committed autotune table
    (``repro.kernels.autotune``) since no explicit blocks are passed
    here — both are per-shape STATIC choices resolved at trace time, so
    every engine traces the identical apply for identical (N, d, K,
    dtype) and the bit-identity contracts hold per fixed choice.
    """
    if cfg.use_kernel:
        from repro.kernels.ops import softsort_apply
        from repro.kernels.ops import softsort_apply_banded as kernel_banded
        if band is not None:
            return functools.partial(kernel_banded, band=band,
                                     compute_dtype=cfg.compute_dtype)
        return functools.partial(softsort_apply,
                                 compute_dtype=cfg.compute_dtype)
    if band is not None:
        return functools.partial(softsort_apply_banded, band=band)
    return functools.partial(softsort_apply_chunked, chunk=cfg.chunk)


def resolve_band(cfg: ShuffleSoftSortConfig, n: int) -> int | None:
    """Resolve ``cfg.band`` to a concrete half-width K (or None = dense).

    ``"auto"`` sizes the band from two requirements (EXPERIMENTS.md
    §Perf): (a) large enough that the modeled tail bound clears
    ``band_eps`` at the COLDEST schedule temperature ``tau_end`` — the
    regime the run must finish banded in; hot early rounds are the
    DISPATCHER's job (``_band_switch_round`` holds them dense), so they
    don't inflate K.  With the trainer's linear re-init ``w =
    arange(N)`` each round the K-rank gap starts at K exactly and the
    per-round Adam drift is a few units, hence the half-gap model
    ``K >= 2 * tau_end * ln(N / eps)``.  And (b) a floor of N/16
    (rounded up to 64) so the asymptotic O(N/K) saving doesn't chase a
    needlessly tight window at large N.

    A resolved K >= N - 1 (tiny N, or an oversized explicit ``band``)
    covers every pair, so it resolves to None: the exact DENSE apply is
    the same math with none of the windowed gather overhead.
    """
    if cfg.band is None:
        return None
    if cfg.band == "auto":
        eps = max(cfg.band_eps, 1e-30)
        safety = int(np.ceil(2.0 * cfg.tau_end * np.log(max(n, 2) / eps)))
        floor = -(-max(n // 16, 1) // 64) * 64
        k = max(64, safety, floor)
    else:
        k = int(cfg.band)
    if k >= n - 1:
        return None
    return max(1, k)


def _band_switch_round(cfg: ShuffleSoftSortConfig, n: int) -> int:
    """First outer round whose temperature admits the banded apply;
    ``cfg.rounds`` means "never" (and None band means exactly that).

    The decision must be key-independent (the whole schedule compiles
    into one scanned program), so it uses the linear-init gap model: the
    trainer re-initializes ``w = arange(N)`` every round, making the
    K-rank key gap start at exactly K; a safety factor of 2 absorbs the
    few units of Adam drift the short inner loop can introduce.  A round
    switches once ``(N - K) * exp(-(K/2) / tau_r) <= band_eps`` at the
    round's hottest inner temperature ``tau_r``; the geometric anneal is
    monotone, so the rounds split into one dense prefix and one banded
    suffix.  The true data-dependent tail is reported by
    ``core.softsort.band_tail_bound`` for auditing.
    """
    k = resolve_band(cfg, n)
    if k is None:
        return cfg.rounds
    taus = _tau_schedule(cfg)
    ok = (n - k) * np.exp(-(k / 2.0) / taus) <= cfg.band_eps
    idx = np.flatnonzero(ok)
    return int(idx[0]) if idx.size else cfg.rounds


def shuffle_soft_sort(
    x: jnp.ndarray,
    hw: tuple[int, int],
    cfg: ShuffleSoftSortConfig = ShuffleSoftSortConfig(),
    key: jax.Array | None = None,
    callback: Optional[Callable[[int, np.ndarray, float], None]] = None,
    *,
    checkpoint_dir: str | None = None,
    resume: bool = False,
    checkpoint_every: int | None = None,
    rung_hook: Optional[Callable[[int], None]] = None,
    check_finite: bool = True,
    guardrail=None,
) -> tuple[np.ndarray, np.ndarray, list[float]]:
    """Sort x (N, d) onto an (h, w) grid.  Returns (order, x[order], losses).

    ``order`` is the permutation (N int32) mapping grid cell -> input row;
    only these N indices — plus the N learnable weights inside each round
    — are ever stored, which is the paper's headline claim.  ``losses``
    is the Python list of per-round final losses (one host sync per
    round; use ``shuffle_soft_sort_batched`` for the sync-free
    throughput path).  ``cfg.use_kernel`` routes the SoftSort apply —
    forward AND backward — through the fused Pallas kernel tier instead
    of the chunked-jnp stream; identical semantics, see
    ``repro.kernels.ops``.

    For many problems or random restarts at once, use
    ``shuffle_soft_sort_batched`` — per-seed bit-identical to this
    function.

    ``cfg.schedule="adaptive"`` (EXPERIMENTS.md §Adaptive) runs the
    same schedule under the plateau controller — the run may stop at a
    converged rung boundary, so ``losses`` holds only the executed
    rounds.  The controller observes at rung boundaries, which is
    incompatible with the per-round ``callback`` stream.

    Preemption safety (EXPERIMENTS.md §Robustness): ``checkpoint_dir``
    publishes the cross-round carry (order, chained PRNG key, losses)
    every ``checkpoint_every`` rounds (default ``rounds // 8``) through
    ``runtime.anneal_checkpoint.AnnealCheckpointer``; ``resume=True``
    restarts from the newest checkpoint there (a bare directory starts
    fresh) and finishes bit-identical to an uninterrupted run with the
    same seed.  ``rung_hook(start_round)`` fires before each segment
    (the chaos harness's kill point); ``check_finite=False`` disables
    the per-round ``NumericalDivergence`` sentinel.

    ``guardrail=`` (a ``runtime.guardrails.GuardrailPolicy``) runs the
    permutation-integrity probes at every rung edge — invariant checks
    plus sampled oracle shadow recompute — raising a typed
    ``IntegrityViolation`` on silent corruption.  Probes are read-only:
    a guarded run returns bit-identical results to an unguarded one.
    """
    _check_schedule(cfg)
    if key is None:
        key = jax.random.PRNGKey(0)
    if cfg.schedule == "adaptive":
        if callback is not None:
            raise ValueError(
                "callback streaming is not supported with "
                "schedule='adaptive' (decisions happen at rung "
                "boundaries, not per round)")
        res = shuffle_soft_sort_batched(
            jnp.asarray(x, jnp.float32)[None], hw, cfg,
            n_restarts=1, keys=jnp.asarray(key)[None],
            checkpoint_dir=checkpoint_dir, resume=resume,
            checkpoint_every=checkpoint_every, rung_hook=rung_hook,
            check_finite=check_finite, guardrail=guardrail)
        executed = int(res.rounds_executed[0, 0])
        return (res.order[0], res.sorted[0],
                [float(v) for v in res.losses[0][:executed]])
    ckpt = _open_checkpointer(checkpoint_dir, resume)
    mon = _open_guardrails(guardrail, cfg, "sequential")
    if callback is not None and (ckpt is not None or rung_hook is not None):
        raise ValueError("checkpoint_dir/rung_hook are incompatible with "
                         "the per-round callback stream")
    n = x.shape[0]
    assert n == hw[0] * hw[1], (n, hw)
    x = jnp.asarray(x, jnp.float32)
    norm = jnp.float32(mean_pairwise_distance(x))
    dense_fn = _select_apply_fn(cfg)
    band = resolve_band(cfg, n)
    switch = _band_switch_round(cfg, n)
    band_fn = dense_fn if band is None else _select_apply_fn(cfg, band)

    order = jnp.arange(n, dtype=jnp.int32)
    taus = _tau_schedule(cfg)
    losses: list[float] = []
    start = 0
    every = checkpoint_every or max(1, cfg.rounds // 8)
    meta = _engine_meta("sequential", cfg, n, 1, hw)
    if ckpt is not None or mon is not None:
        # Normalize a typed key to raw uint32 data so it survives the
        # numpy round-trip (identical stream either way).
        karr = jnp.asarray(key)
        if jnp.issubdtype(karr.dtype, jax.dtypes.prng_key):
            key = jax.random.key_data(karr)
    if ckpt is not None:
        if resume:
            got = ckpt.restore_latest(_meta_expect(meta))
            if got is not None:
                state, start, _ = got
                order = jnp.asarray(state["order"])
                key = jnp.asarray(state["key"])
                losses = [float(v) for v in state["losses"]]
    edges = set(_checkpoint_edges(cfg.rounds, every))
    if mon is not None:
        cfg_o = dataclasses.replace(cfg, use_kernel=False)
        dense_o = _select_apply_fn(cfg_o)
        band_o = dense_o if band is None else _select_apply_fn(cfg_o, band)
    seg_start = start
    o_snap = k_snap = None
    for r in range(start, cfg.rounds):
        if rung_hook is not None and (r == start or r % every == 0):
            rung_hook(r)
        if mon is not None and r == seg_start:
            # Rung-start carry snapshot for the key-chain probe and
            # (when this rung is sampled) the oracle shadow replay.
            o_snap = np.asarray(order)
            k_snap = np.asarray(key)
        key, sub = jax.random.split(key)
        order, loss = _outer_round(
            x, order, sub, jnp.float32(taus[r]), norm,
            hw=hw, cfg=cfg,
            apply_fn=band_fn if r >= switch else dense_fn)
        losses.append(float(loss))
        if check_finite:
            # Whole-segment sentinel (shared with the batched engines):
            # validates every round since the last rung edge, not just
            # the newest value, so the error pinpoints the FIRST bad
            # round even if a later one recovered to a finite loss.
            _check_finite(
                np.asarray(losses[seg_start:], np.float32)[:, None],
                seg_start, cfg, "sequential")
        if callback is not None:
            callback(r, np.asarray(order), losses[-1])
        if mon is not None and (r + 1) in edges:
            oracle_l = oracle_o = None
            if mon.wants_shadow(seg_start):
                o_sh, k_sh = jnp.asarray(o_snap), jnp.asarray(k_snap)
                shadow_losses = []
                for rr in range(seg_start, r + 1):
                    k_sh, sub_sh = jax.random.split(k_sh)
                    o_sh, l_sh = _outer_round(
                        x, o_sh, sub_sh, jnp.float32(taus[rr]), norm,
                        hw=hw, cfg=cfg_o,
                        apply_fn=band_o if rr >= switch else dense_o)
                    shadow_losses.append(float(l_sh))
                oracle_l = np.asarray(shadow_losses, np.float32)
                if mon.compare_orders():
                    oracle_o = np.asarray(o_sh)[None]
            mon.check_rung(
                start=seg_start,
                losses=np.asarray(losses[seg_start:], np.float32),
                orders=np.asarray(order)[None], n=n,
                keys_in=k_snap[None], keys_out=np.asarray(key)[None],
                seg_len=r + 1 - seg_start, tau=float(taus[seg_start]),
                oracle_losses=oracle_l, oracle_orders=oracle_o)
            seg_start = r + 1
        if ckpt is not None and (r + 1) in edges:
            ckpt.save(r + 1, {"order": np.asarray(order),
                              "key": np.asarray(key),
                              "losses": np.asarray(losses, np.float32)},
                      meta=dict(meta, round=r + 1))
    order = np.asarray(order)
    return order, np.asarray(x)[order], losses


# --------------------------------------------------------------------------
# Batched multi-problem / multi-restart engine.
# --------------------------------------------------------------------------

def _prep_instances(xs, hw, n_restarts, key, keys):
    """Normalize the batched-engine inputs into flattened instance arrays.

    Shared by ``shuffle_soft_sort_batched`` and ``restart_tournament``
    so both consume identical (BS, ...) instance layouts and identical
    PRNG streams — problem-major order, restart s of problem b at row
    ``b * S + s``.

    Returns (xs (B, N, d), B, S, N, keys (BS, 2), xs_t (BS, N, d),
    norms_t (BS,), orders (BS, N)).
    """
    xs = jnp.asarray(xs, jnp.float32)
    assert xs.ndim == 3, f"xs must be (B, N, d), got {xs.shape}"
    b, n, _ = xs.shape
    s = int(n_restarts)
    assert s >= 1, n_restarts
    assert n == hw[0] * hw[1], (n, hw)
    bs = b * s

    if keys is None:
        if key is None:
            key = jax.random.PRNGKey(0)
        keys = jax.random.split(key, bs)
    keys = jnp.asarray(keys)
    if jnp.issubdtype(keys.dtype, jax.dtypes.prng_key):
        # New-style typed keys (jax.random.key) — unwrap to raw uint32
        # data so both key flavours drive identical streams.
        keys = jax.random.key_data(keys)
    keys = keys.reshape(bs, 2)

    # Per-problem loss normalization, tiled over restarts.
    norms = jax.vmap(mean_pairwise_distance)(xs).astype(jnp.float32)
    xs_t = jnp.repeat(xs, s, axis=0)                     # (BS, N, d)
    norms_t = jnp.repeat(norms, s, axis=0)               # (BS,)
    orders = jnp.tile(jnp.arange(n, dtype=jnp.int32), (bs, 1))
    return xs, b, s, n, keys, xs_t, norms_t, orders


@dataclasses.dataclass(frozen=True)
class BatchedSortResult:
    """Result of ``shuffle_soft_sort_batched`` over B problems x S restarts.

    The per-problem fields (``order``/``sorted``/``losses``) report the
    winning restart — the seed whose final-round loss is lowest.  The
    ``all_*`` fields keep every restart so callers can audit seed
    variance (and tests can check bit-identity against sequential runs).
    """
    order: np.ndarray          # (B, N) int32 — best restart's permutation
    sorted: np.ndarray         # (B, N, d) — xs gathered by ``order``
    losses: np.ndarray         # (B, R) — per-round losses of the best restart
    best_restart: np.ndarray   # (B,) int — argmin_s all_losses[:, s, -1]
    all_orders: np.ndarray     # (B, S, N) int32 — every restart's permutation
    all_losses: np.ndarray     # (B, S, R) — every restart's loss trace
    # schedule="adaptive" only: rounds each restart actually executed
    # (None on the fixed schedule; loss traces are NaN past the stop,
    # and ``best_restart`` compares LAST-EXECUTED losses instead of
    # round R-1 losses).
    rounds_executed: np.ndarray | None = None   # (B, S) int64


def shuffle_soft_sort_batched(
    xs: jnp.ndarray,
    hw: tuple[int, int],
    cfg: ShuffleSoftSortConfig = ShuffleSoftSortConfig(),
    n_restarts: int = 1,
    key: jax.Array | None = None,
    keys: jax.Array | None = None,
    callback: Optional[Callable[[int, np.ndarray, np.ndarray], None]] = None,
    mesh=None,
    *,
    checkpoint_dir: str | None = None,
    resume: bool = False,
    checkpoint_every: int | None = None,
    rung_hook: Optional[Callable[[int], None]] = None,
    check_finite: bool = True,
    guardrail=None,
    mesh_hook=None,
) -> BatchedSortResult:
    """Sort B problems at once, S random restarts each.

    Runs B x S independent ShuffleSoftSort instances as a single vmapped
    program: one ``_outer_round_batched`` device call per round instead
    of B x S sequential calls, which amortizes dispatch overhead and
    lets XLA batch the (chunk, N) contractions — the throughput win the
    N-parameter footprint makes possible (an N^2-parameter method could
    not hold B x S instances in memory).

    With ``mesh`` (a 1-D "data" mesh from
    ``repro.launch.mesh.make_sort_mesh``) the flattened B x S instance
    axis is additionally sharded across devices via ``shard_map`` — the
    same per-instance program, split over the mesh, with the tail shard
    padded and the winning restart picked by a cross-device argmin.
    Measured devices x B x S scaling: EXPERIMENTS.md §Scaling.

    Each instance consumes exactly the PRNG stream the sequential API
    would: instance (b, s) with key ``keys[b, s]`` returns an order
    bit-identical to ``shuffle_soft_sort(xs[b], hw, cfg,
    key=keys[b, s])`` — on the vmap path AND on any mesh size.

    Args:
      xs: (B, N, d) batch of problems; all share N = hw[0] * hw[1].
      hw: target grid shape, shared by the batch.
      cfg: shared hyperparameters; ``cfg.use_kernel`` routes every
        instance through the batched Pallas path.
      n_restarts: S — independent seeds per problem; best final loss wins.
      key: base PRNG key, split into B x S instance keys (ignored when
        ``keys`` is given).
      keys: optional explicit instance keys, shape (B, S, 2) or (B*S, 2)
        uint32, ordered problem-major.
      callback: optional ``f(round, orders (B*S, N), losses (B*S,))``
        streamed per round (forces a host sync, like the sequential
        API).  Unsupported on the sharded path — streaming every round
        through the host would defeat the point of the mesh.
      mesh: optional jax Mesh with a "data" axis; shards the instance
        grid across its devices.
      checkpoint_dir / resume / checkpoint_every / rung_hook /
        check_finite: rung-boundary preemption safety, as in
        ``shuffle_soft_sort`` (EXPERIMENTS.md §Robustness).  Resumed
        runs are bit-identical per seed to uninterrupted runs on the
        vmap AND mesh paths — including resume under a different mesh
        size (the carry is stored in logical layout).
      guardrail: optional ``runtime.guardrails.GuardrailPolicy`` (or an
        existing monitor) — permutation-integrity probes at every rung
        boundary, raising ``IntegrityViolation`` on silent corruption.
        The fixed fast path reroutes through the rung-segmented runner
        (bit-identical by the segment-chaining contract) so probes see
        real rung boundaries.
      mesh_hook: optional ``f(start_round, mesh) -> mesh | None`` fired
        at each rung boundary; returning a mesh swaps the remaining
        rungs onto it — the elastic re-shard seam (device eviction /
        return at rung boundaries, EXPERIMENTS.md §Robustness "Elastic
        capacity").  Forces the rung-segmented runner on the fixed
        path, like ``rung_hook``.  The carry is layout-free, so a
        mid-run mesh swap keeps per-seed bit-identity
        (tests/test_elastic.py).

    Returns:
      ``BatchedSortResult`` — see its field docs.
    """
    _check_schedule(cfg)
    if mesh is not None and callback is not None:
        raise ValueError("callback streaming is not supported on the "
                         "sharded path; use mesh=None")
    ckpt = _open_checkpointer(checkpoint_dir, resume)
    mon = _open_guardrails(guardrail, cfg, "batched")
    if callback is not None and (ckpt is not None or rung_hook is not None
                                 or mon is not None
                                 or mesh_hook is not None):
        raise ValueError("checkpoint_dir/rung_hook/guardrail/mesh_hook are "
                         "incompatible with the per-round callback stream")
    if (cfg.schedule != "adaptive" and callback is None and ckpt is None
            and rung_hook is None and mon is None and mesh_hook is None):
        return _sort_batched_scanned(xs, hw, cfg, n_restarts, key, keys,
                                     mesh, check_finite)
    xs, b, s, n, keys, xs_t, norms_t, orders = _prep_instances(
        xs, hw, n_restarts, key, keys)
    bs = b * s
    if cfg.schedule == "adaptive":
        if callback is not None:
            raise ValueError(
                "callback streaming is not supported with "
                "schedule='adaptive' (decisions happen at rung "
                "boundaries, not per round)")
        ctrl = make_adaptive_controller(cfg, bs, n)
        orders, _, losses_bs, _ = _run_adaptive(
            xs_t, orders, keys, norms_t, hw=hw, cfg=cfg, mesh=mesh,
            controller=ctrl, ckpt=ckpt, resume=resume,
            meta=_engine_meta("adaptive", cfg, n, bs, hw),
            rung_hook=rung_hook, check_finite=check_finite, monitor=mon,
            mesh_hook=mesh_hook)
        all_losses = losses_bs.reshape(b, s, cfg.rounds)
        all_orders = np.asarray(orders).reshape(b, s, n)
        executed = ctrl.executed.reshape(b, s)
        # Winner by LAST-EXECUTED loss (the adaptive analogue of the
        # fixed path's round-(R-1) loss); host argmin on every path —
        # the device argmin shortcut reads round R-1, which an early
        # stop leaves NaN.
        final = losses_bs[np.arange(bs), ctrl.executed - 1].reshape(b, s)
        best = np.argmin(final, axis=1)
        order = all_orders[np.arange(b), best]
        xs_np = np.asarray(xs)
        return BatchedSortResult(
            order=order,
            sorted=np.take_along_axis(xs_np, order[:, :, None], axis=1),
            losses=all_losses[np.arange(b), best],
            best_restart=best,
            all_orders=all_orders,
            all_losses=all_losses,
            rounds_executed=executed,
        )
    dense_fn = _select_apply_fn(cfg)
    band = resolve_band(cfg, n)
    switch = _band_switch_round(cfg, n)
    band_fn = dense_fn if band is None else _select_apply_fn(cfg, band)
    taus = _tau_schedule(cfg)

    if callback is None:
        # Checkpointed path: the same schedule chained across rung
        # segments (bit-identical to the fast path by the
        # segment-chaining contract), publishing the carry at each
        # edge so a preempted run resumes instead of restarting.
        # Guardrail monitors ride the same seam: probes need rung-
        # boundary host syncs, which the fast path doesn't have.
        orders, _, losses_rb = _run_fixed_checkpointed(
            xs_t, orders, keys, taus, norms_t, switch=switch,
            hw=hw, cfg=cfg, dense_fn=dense_fn, band_fn=band_fn,
            mesh=mesh, ckpt=ckpt, resume=resume,
            every=checkpoint_every or max(1, cfg.rounds // 8),
            rung_hook=rung_hook,
            meta=_engine_meta("batched", cfg, n, bs, hw),
            check_finite=check_finite, band=band, monitor=mon,
            mesh_hook=mesh_hook)
        all_losses = np.asarray(losses_rb).T             # (BS, R)
    else:
        # Streaming path: one dispatch per round so the callback can
        # observe every intermediate state (same numerics as the scan).
        split_all = jax.vmap(jax.random.split)           # (BS,2) -> (BS,2,2)
        loss_rounds = []
        for r in range(cfg.rounds):
            pair = split_all(keys)
            keys, subs = pair[:, 0], pair[:, 1]
            orders, losses = _outer_round_batched(
                xs_t, orders, subs, jnp.float32(taus[r]), norms_t,
                hw=hw, cfg=cfg,
                apply_fn=band_fn if r >= switch else dense_fn)
            loss_rounds.append(losses)
            if check_finite:
                _check_finite(np.asarray(losses)[None], r, cfg, "batched")
            callback(r, np.asarray(orders), np.asarray(losses))
        all_losses = np.asarray(jnp.stack(loss_rounds, axis=-1))
        losses_rb = None
    return _batched_result(xs, orders, all_losses, losses_rb, b=b, s=s,
                           rounds=cfg.rounds, mesh=mesh)


def _sort_batched_scanned(xs, hw, cfg: ShuffleSoftSortConfig, n_restarts,
                          key, keys, mesh, check_finite: bool
                          ) -> BatchedSortResult:
    """``shuffle_soft_sort_batched``'s fast path: the whole fixed
    schedule as one scanned device program (two when the band switch
    splits the anneal), with no per-round host round trips.  With a
    mesh the same program runs per shard of the instance axis.

    Host spans in the profiler's trace split the call into its phases,
    on the device's clock: ``sort.call`` holds ``sort.prep`` (inputs,
    schedule, band), ``sort.launch`` (enqueueing the program),
    ``sort.wait`` (the host blocked on the device) and ``sort.collect``
    (finite check, fetch, winner).  ``TraceAnnotation`` records nothing
    when no trace runs.  The call also starts the process's compile
    counter (``repro.launch.compile_cache.compile_counter``).
    """
    from repro.launch.compile_cache import compile_counter
    compile_counter()
    span = jax.profiler.TraceAnnotation
    b, n = np.shape(xs)[:2]
    with span("sort.call", b=b, s=n_restarts, n=n, rounds=cfg.rounds):
        with span("sort.prep"):
            xs, b, s, n, keys, xs_t, norms_t, orders = _prep_instances(
                xs, hw, n_restarts, key, keys)
            dense_fn = _select_apply_fn(cfg)
            band = resolve_band(cfg, n)
            band_fn = dense_fn if band is None else _select_apply_fn(cfg,
                                                                      band)
            switch = _band_switch_round(cfg, n)
            taus = _tau_schedule(cfg)
        with span("sort.launch"):
            orders, _, losses_rb = _run_segments(
                xs_t, orders, keys, taus, norms_t, start=0, switch=switch,
                hw=hw, cfg=cfg, dense_fn=dense_fn, band_fn=band_fn,
                mesh=mesh)
        with span("sort.wait"):
            jax.block_until_ready((orders, losses_rb))
        with span("sort.collect"):
            losses_np = np.asarray(losses_rb)            # (R, BS)
            if check_finite:
                _check_finite(losses_np, 0, cfg, "batched")
            return _batched_result(xs, orders, losses_np.T, losses_rb,
                                   b=b, s=s, rounds=cfg.rounds, mesh=mesh)


def _batched_result(xs, orders, all_losses, losses_rb, *, b: int, s: int,
                    rounds: int, mesh) -> BatchedSortResult:
    """Pick each problem's winning restart (lowest final-round loss)
    from the engine's outputs and gather its sorted rows.

    ``all_losses`` is (BS, R) on the host; ``losses_rb`` the engine's
    (R, BS) device array, read only on the mesh path."""
    n = orders.shape[-1]
    all_losses = all_losses.reshape(b, s, rounds)        # (B, S, R)
    all_orders = np.asarray(orders).reshape(b, s, n)     # (B, S, N)
    if mesh is not None:
        # Winner selection as a cross-device argmin + gather over the
        # sharded restart axis (identical result to the host argmin
        # below — asserted in tests/test_sharded.py).
        best_dev, order_dev = _best_restart_device(orders, losses_rb,
                                                   b=b, s=s)
        best = np.asarray(best_dev)                      # (B,)
        order = np.asarray(order_dev)                    # (B, N)
    else:
        best = np.argmin(all_losses[:, :, -1], axis=1)   # (B,)
        order = all_orders[np.arange(b), best]           # (B, N)
    xs_np = np.asarray(xs)
    xs_sorted = np.take_along_axis(xs_np, order[:, :, None], axis=1)
    return BatchedSortResult(
        order=order,
        sorted=xs_sorted,
        losses=all_losses[np.arange(b), best],
        best_restart=best,
        all_orders=all_orders,
        all_losses=all_losses,
    )


# --------------------------------------------------------------------------
# Restart tournament: successive-halving over the restart axis.
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TournamentResult:
    """Result of ``restart_tournament`` — successive-halving restarts.

    ``rounds_run / rounds_full`` is the compute fraction the tournament
    spent vs. running every restart to completion; the quality cost of
    that saving (culling can drop a late-blooming seed) is measured in
    EXPERIMENTS.md §Scaling.
    """
    order: np.ndarray          # (B, N) int32 — winning restart's permutation
    sorted: np.ndarray         # (B, N, d) — xs gathered by ``order``
    final_loss: np.ndarray     # (B,) — winner's last-round loss
    best_restart: np.ndarray   # (B,) — winner's ORIGINAL restart index
    survivors: tuple           # per rung: (B, S_k) original restart indices
    all_losses: np.ndarray     # (B, S, R) — NaN after a restart is culled
    rounds_run: int            # instance-rounds executed on device, pad
                               # slots included (mesh path: each rung's
                               # live set rounds up to the mesh size)
    rounds_full: int           # B * S * R — the no-culling engine's cost


def _rung_boundaries(rounds: int, n_rungs: int) -> list[int]:
    """Split the R-round anneal into ``n_rungs`` contiguous segments;
    returns the (strictly increasing) end round of each rung, last == R."""
    assert n_rungs >= 1, n_rungs
    edges, prev = [], 0
    for k in range(n_rungs):
        end = round(rounds * (k + 1) / n_rungs)
        if end > prev:
            edges.append(end)
            prev = end
    assert edges[-1] == rounds, (edges, rounds)
    return edges


def _tournament_cull(final_losses: np.ndarray, keep: int) -> np.ndarray:
    """Pick the ``keep`` best restart slots per problem.

    Args:
      final_losses: (B, S_k) rung-end losses of the live restarts.
      keep: how many slots survive.

    Returns:
      (B, keep) int64 slot indices into the CURRENT live set, sorted
      ascending per problem so survivor bookkeeping stays problem-major
      and deterministic (stable sort — ties keep the lower slot).
    """
    b, s_k = final_losses.shape
    assert 1 <= keep <= s_k, (keep, s_k)
    sel = np.argsort(final_losses, axis=1, kind="stable")[:, :keep]
    sel.sort(axis=1)
    return sel


def _restart_tournament_adaptive(xs, b, s, n, keys_fl, xs_t, norms_t,
                                 orders, *, hw, cfg, cull_fraction,
                                 n_rungs, mesh, ckpt=None,
                                 resume=False, rung_hook=None,
                                 check_finite=True,
                                 monitor=None,
                                 mesh_hook=None) -> TournamentResult:
    """Adaptive-schedule tournament: the shared ``_run_adaptive`` loop
    with a cull hook at the rung edges.

    Edges are expressed in CONTROLLER steps (``_rung_boundaries`` over
    the R / seg_len decision points), so culls land on the same
    boundaries the plateau controller observes at.  Culling ranks every
    not-yet-culled restart by its LAST-EXECUTED loss — an early-stopped
    restart keeps competing with its final loss (it stopped because it
    converged, not because it lost), and a culled restart just leaves
    the winner set; either way the per-instance PRNG streams of the
    survivors never see a perturbation.

    The cross-rung cull state (current alive sets + the survivors log)
    lives in ``hstate``, which ``_run_adaptive`` persists alongside the
    controller at every committed rung — so a preempted adaptive
    tournament resumes with its culls intact, bit-identical to an
    uninterrupted run.
    """
    ctrl = make_adaptive_controller(cfg, b * s, n)
    n_steps = cfg.rounds // ctrl.seg_len
    edges = _rung_boundaries(n_steps, min(n_rungs, n_steps))
    interior = set(edges[:-1])
    edge_set = set(edges)
    # Checkpointed hook state: "alive" is the live (B, S_k) map;
    # "surv_<i>" entries are the per-edge survivors log (numbered keys
    # because the widths shrink — a ragged log can't be one array).
    hstate: dict[str, np.ndarray] = {
        "alive": np.tile(np.arange(s), (b, 1))}

    def hook(step, ctrl_, losses_mat):
        if step not in edge_set:
            return
        alive = hstate["alive"]
        s_k = alive.shape[1]
        keep = max(1, int(np.ceil(s_k * (1.0 - cull_fraction))))
        if step in interior and keep < s_k:
            rows = np.arange(b)[:, None] * s + alive     # flattened rows
            last = losses_mat[rows, ctrl_.executed[rows] - 1]
            sel = _tournament_cull(last, keep)           # (B, keep)
            kept_mask = np.zeros((b, s_k), bool)
            np.put_along_axis(kept_mask, sel, True, axis=1)
            ctrl_.mark_culled(rows[~kept_mask])
            alive = np.take_along_axis(alive, sel, axis=1)
            hstate["alive"] = alive
        n_logged = sum(1 for kk in hstate if kk.startswith("surv_"))
        hstate[f"surv_{n_logged:03d}"] = alive.copy()

    orders_f, _, losses_mat, device_rounds = _run_adaptive(
        xs_t, orders, keys_fl, norms_t, hw=hw, cfg=cfg, mesh=mesh,
        controller=ctrl, boundary_hook=hook, ckpt=ckpt, resume=resume,
        meta=_engine_meta("tournament-adaptive", cfg, n, b * s, hw),
        rung_hook=rung_hook, hook_state=hstate, check_finite=check_finite,
        monitor=monitor, mesh_hook=mesh_hook)
    # If every restart stopped before a late edge, its hook never fired;
    # the live set was already final, so log it for those rungs too.
    alive = hstate["alive"]
    survivors_log = [hstate[kk] for kk in
                     sorted(kk for kk in hstate if kk.startswith("surv_"))]
    while len(survivors_log) < len(edges):
        survivors_log.append(alive.copy())

    xs_np = np.asarray(xs)
    rows = np.arange(b)[:, None] * s + alive              # (B, S_fin)
    final = losses_mat[rows, ctrl.executed[rows] - 1]
    win = np.argmin(final, axis=1)
    best_restart = alive[np.arange(b), win]
    order = np.asarray(orders_f).reshape(b, s, n)[
        np.arange(b), best_restart]
    return TournamentResult(
        order=order,
        sorted=np.take_along_axis(xs_np, order[:, :, None], axis=1),
        final_loss=final[np.arange(b), win],
        best_restart=best_restart,
        survivors=tuple(survivors_log),
        all_losses=losses_mat.reshape(b, s, cfg.rounds),
        rounds_run=device_rounds,
        rounds_full=b * s * cfg.rounds,
    )


def restart_tournament(
    xs: jnp.ndarray,
    hw: tuple[int, int],
    cfg: ShuffleSoftSortConfig = ShuffleSoftSortConfig(),
    n_restarts: int = 8,
    key: jax.Array | None = None,
    keys: jax.Array | None = None,
    cull_fraction: float = 0.5,
    n_rungs: int = 3,
    mesh=None,
    *,
    checkpoint_dir: str | None = None,
    resume: bool = False,
    rung_hook: Optional[Callable[[int], None]] = None,
    check_finite: bool = True,
    guardrail=None,
    mesh_hook=None,
) -> TournamentResult:
    """Successive-halving restart scheduler over the batched engine.

    Runs S restarts per problem for the first ``1/n_rungs`` fraction of
    the anneal, then at each rung boundary culls the worst
    ``cull_fraction`` of the live restarts (per problem, by rung-end
    loss) and keeps annealing only the survivors — so the device batch
    physically shrinks and later rounds run proportionally faster.  The
    freed wall-clock is the reinvestment: at equal time budget a caller
    can afford a larger initial S than the run-everything-to-the-end
    engine (measured tradeoff: EXPERIMENTS.md §Scaling).

    Surviving restarts consume exactly the PRNG stream an uninterrupted
    run would (segment keys are carried across rungs), so a restart
    that survives every cull finishes bit-identical to the same (b, s)
    instance under ``shuffle_soft_sort_batched`` — culling never
    perturbs the survivors' trajectories, it only stops losers early.

    Args:
      xs, hw, cfg, n_restarts, key, keys: as in
        ``shuffle_soft_sort_batched``.
      cull_fraction: fraction of live restarts dropped at each rung
        boundary (0 disables culling; 0.5 halves).
      n_rungs: number of anneal segments; culls happen at the
        ``n_rungs - 1`` interior boundaries.
      mesh: optional 1-D "data" mesh — each rung's (shrinking) instance
        grid is shard_mapped across it.
      checkpoint_dir / resume / rung_hook / check_finite: rung-boundary
        preemption safety (EXPERIMENTS.md §Robustness).  The tournament
        checkpoints at its OWN rung edges (the cull boundaries) — the
        natural seam, so alive sets and survivor logs are always
        consistent with the stored orders; ``checkpoint_every`` does
        not apply here.
      mesh_hook: optional ``f(start_round, mesh) -> mesh | None`` fired
        at each rung boundary (after ``rung_hook``); returning a mesh
        re-shards the remaining rungs over it — the elastic
        eviction/return seam (EXPERIMENTS.md §Robustness, "Elastic
        capacity").  Bit-identity-preserving: the rung carry is
        layout-free.

    Returns:
      ``TournamentResult`` — see its field docs.
    """
    assert 0.0 <= cull_fraction < 1.0, cull_fraction
    _check_schedule(cfg)
    ckpt = _open_checkpointer(checkpoint_dir, resume)
    mon = _open_guardrails(guardrail, cfg, "tournament")
    xs, b, s, n, keys_fl, xs_t, norms_t, orders = _prep_instances(
        xs, hw, n_restarts, key, keys)
    if cfg.schedule == "adaptive":
        return _restart_tournament_adaptive(
            xs, b, s, n, keys_fl, xs_t, norms_t, orders, hw=hw, cfg=cfg,
            cull_fraction=cull_fraction, n_rungs=n_rungs, mesh=mesh,
            ckpt=ckpt, resume=resume, rung_hook=rung_hook,
            check_finite=check_finite, monitor=mon, mesh_hook=mesh_hook)
    dense_fn = _select_apply_fn(cfg)
    band = resolve_band(cfg, n)
    switch = _band_switch_round(cfg, n)
    band_fn = dense_fn if band is None else _select_apply_fn(cfg, band)
    taus = _tau_schedule(cfg)
    edges = _rung_boundaries(cfg.rounds, n_rungs)

    # Live-set state, always problem-major: restart s_live of problem b
    # at flattened row b * s_k + s_live.  ``alive`` maps live slots back
    # to original restart indices.
    alive = np.tile(np.arange(s), (b, 1))                 # (B, S_k)
    xs_np = np.asarray(xs)
    cur = dict(xs=xs_t, orders=orders, keys=keys_fl, norms=norms_t)
    all_losses = np.full((b, s, cfg.rounds), np.nan, np.float32)
    survivors_log: list[np.ndarray] = []
    rounds_run = 0
    start = 0
    k_done = 0
    meta = _engine_meta("tournament", cfg, n, b * s, hw)
    if resume and ckpt is not None:
        got = ckpt.restore_latest(_meta_expect(meta))
        if got is not None:
            state, _, m = got
            alive = np.asarray(state["alive"])
            all_losses = np.asarray(state["all_losses"], np.float32).copy()
            k_done = int(m["rung"])
            survivors_log = [np.asarray(state[f"surv_{i:03d}"])
                             for i in range(k_done)]
            # xs for the live set is a pure gather of the inputs — only
            # the carry (orders/keys/norms/alive) needs storage.
            cur = dict(xs=jnp.repeat(xs, alive.shape[1], axis=0),
                       orders=jnp.asarray(state["orders"]),
                       keys=jnp.asarray(state["keys"]),
                       norms=jnp.asarray(state["norms"]))
            start = int(m["start"])
            rounds_run = int(m["rounds_run"])
    d_mesh = 1 if mesh is None else mesh.shape["data"]
    if mon is not None:
        cfg_o = dataclasses.replace(cfg, use_kernel=False)
        dense_o = _select_apply_fn(cfg_o)
        band_o = dense_o if band is None else _select_apply_fn(cfg_o, band)
    for k, end in enumerate(edges):
        if k < k_done:
            continue
        if rung_hook is not None:
            rung_hook(start)
        if mesh_hook is not None:
            new_mesh = mesh_hook(start, mesh)
            if new_mesh is not None:
                mesh = new_mesh
                d_mesh = mesh.shape["data"]
                # Survivor gathers keep the tournament carry on the old
                # mesh's devices; pull every array through host numpy
                # so the next rung re-places it on the new mesh.
                for nm in ("xs", "orders", "keys", "norms"):
                    cur[nm] = jnp.asarray(np.asarray(cur[nm]))
        s_k = alive.shape[1]
        k_in = o_in = None
        if mon is not None:
            k_in = np.asarray(cur["keys"])
            if mon.wants_shadow(start):
                o_in = np.asarray(cur["orders"])
        orders_d, keys_d, losses_d = _run_segments(
            cur["xs"], cur["orders"], cur["keys"], taus[start:end],
            cur["norms"], start=start, switch=switch,
            hw=hw, cfg=cfg, dense_fn=dense_fn, band_fn=band_fn, mesh=mesh)
        # Device compute actually spent: padded instances burn rounds
        # too, so uneven shards don't let rounds_run overstate savings.
        bs_exec = -(-b * s_k // d_mesh) * d_mesh
        rounds_run += (end - start) * bs_exec
        seg = np.asarray(losses_d).T.reshape(b, s_k, end - start)
        if check_finite:
            _check_finite(np.asarray(losses_d), start, cfg, "tournament")
        if mon is not None:
            oracle_l = oracle_o = None
            if o_in is not None:
                o_sh, _, seg_sh = _run_segments(
                    cur["xs"], jnp.asarray(o_in), jnp.asarray(k_in),
                    taus[start:end], cur["norms"], start=start,
                    switch=switch, hw=hw, cfg=cfg_o, dense_fn=dense_o,
                    band_fn=band_o, mesh=mesh)
                oracle_l = np.asarray(seg_sh, np.float32)
                if mon.compare_orders():
                    oracle_o = np.asarray(o_sh)
            mon.check_rung(
                start=start, losses=np.asarray(losses_d, np.float32),
                orders=np.asarray(orders_d), keys_in=k_in,
                keys_out=np.asarray(keys_d), seg_len=end - start,
                tau=float(taus[start]), oracle_losses=oracle_l,
                oracle_orders=oracle_o)
        all_losses[np.arange(b)[:, None], alive, start:end] = seg

        keep = max(1, int(np.ceil(s_k * (1.0 - cull_fraction))))
        if k < len(edges) - 1 and keep < s_k:
            sel = _tournament_cull(seg[:, :, -1], keep)   # (B, keep)
            alive = np.take_along_axis(alive, sel, axis=1)
            # Survivor gather stays on device — only the (small) rung
            # losses crossed to the host for the cull decision above.
            rows = jnp.asarray(
                (np.arange(b)[:, None] * s_k + sel).reshape(-1))
            cur = dict(
                xs=jnp.repeat(xs, keep, axis=0),
                orders=jnp.take(orders_d, rows, axis=0),
                keys=jnp.take(keys_d, rows, axis=0),
                norms=jnp.take(cur["norms"], rows, axis=0),
            )
        else:
            cur = dict(xs=cur["xs"], orders=orders_d, keys=keys_d,
                       norms=cur["norms"])
        survivors_log.append(alive.copy())
        start = end
        if ckpt is not None:
            st = {"orders": np.asarray(cur["orders"]),
                  "keys": np.asarray(cur["keys"]),
                  "norms": np.asarray(cur["norms"]),
                  "alive": alive.copy(),
                  "all_losses": all_losses.copy()}
            for i, sv in enumerate(survivors_log):
                st[f"surv_{i:03d}"] = sv
            ckpt.save(end, st, meta=dict(meta, rung=k + 1, start=end,
                                         rounds_run=rounds_run))

    s_fin = alive.shape[1]
    final = all_losses[np.arange(b)[:, None], alive, -1]  # (B, S_fin)
    win = np.argmin(final, axis=1)                        # live slot
    best_restart = alive[np.arange(b), win]
    order = np.asarray(cur["orders"]).reshape(b, s_fin, n)[np.arange(b), win]
    xs_sorted = np.take_along_axis(xs_np, order[:, :, None], axis=1)
    return TournamentResult(
        order=order,
        sorted=xs_sorted,
        final_loss=final[np.arange(b), win],
        best_restart=best_restart,
        survivors=tuple(survivors_log),
        all_losses=all_losses,
        rounds_run=rounds_run,
        rounds_full=b * s * cfg.rounds,
    )


# --------------------------------------------------------------------------
# Plain SoftSort baseline (paper Table III row 3): one weight vector trained
# end-to-end with the same loss and tau annealing, no shuffling.
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("hw", "cfg", "apply_fn", "steps"))
def _softsort_train(x, norm, *, hw, cfg: ShuffleSoftSortConfig, apply_fn,
                    steps: int):
    n = x.shape[0]
    w0 = jnp.arange(n, dtype=jnp.float32)
    ident = jnp.arange(n, dtype=jnp.int32)
    grad_fn = jax.value_and_grad(_loss_fn)

    def body(i, carry):
        w, mu, nu, _ = carry
        # Same geometric anneal as _tau_schedule, but per inner step
        # (continuous frac) rather than per outer round — the baseline
        # has no rounds, so it cannot share the host-side (R,) array.
        frac = i.astype(jnp.float32) / steps
        tau = cfg.tau_start * (cfg.tau_end / cfg.tau_start) ** frac
        loss, g = grad_fn(w, x, ident, tau, hw, norm, cfg, apply_fn)
        t = i.astype(jnp.float32) + 1.0
        mu = 0.9 * mu + 0.1 * g
        nu = 0.999 * nu + 0.001 * jnp.square(g)
        mhat = mu / (1 - 0.9 ** t)
        nuhat = nu / (1 - 0.999 ** t)
        w = w - cfg.lr * mhat / (jnp.sqrt(nuhat) + 1e-8)
        return (w, mu, nu, loss)

    w, _, _, loss = jax.lax.fori_loop(
        0, steps, body, (w0, jnp.zeros_like(w0), jnp.zeros_like(w0),
                         jnp.float32(0.0)))
    return jnp.argsort(w), loss


def soft_sort_baseline(
    x: jnp.ndarray,
    hw: tuple[int, int],
    cfg: ShuffleSoftSortConfig = ShuffleSoftSortConfig(),
    steps: int | None = None,
) -> tuple[np.ndarray, np.ndarray, float]:
    """Pure SoftSort with the same budget (R*I steps by default).

    The baseline anneals tau continuously inside one ``fori_loop``, so
    there is no per-round boundary to segment at: ``cfg.band`` is
    honoured only when the switch model admits the band for the WHOLE
    schedule (switch round 0), otherwise the run stays dense.
    """
    x = jnp.asarray(x, jnp.float32)
    norm = jnp.float32(mean_pairwise_distance(x))
    band = resolve_band(cfg, x.shape[0])
    use_band = band is not None and _band_switch_round(cfg, x.shape[0]) == 0
    apply_fn = _select_apply_fn(cfg, band if use_band else None)
    steps = steps or cfg.rounds * cfg.inner_steps
    order, loss = _softsort_train(x, norm, hw=hw, cfg=cfg, apply_fn=apply_fn,
                                  steps=steps)
    order = np.asarray(order)
    return order, np.asarray(x)[order], float(loss)
