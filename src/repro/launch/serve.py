"""Batched serving drivers: continuous-batching loops on CPU scale.

Two workloads share this entrypoint:

* ``--workload lm`` (default) — LM decode serving.  Requests arrive with
  different prompt lengths; the scheduler right-pads into a fixed decode
  batch, prefills once, then decodes step-locked with per-request stop
  positions (the fixed-shape analogue of continuous batching — slot
  reuse keeps XLA shapes static, which is what a TPU serving stack
  needs).

      PYTHONPATH=src python -m repro.launch.serve \
          --arch qwen1.5-0.5b --preset tiny --requests 8 --max-new 32

* ``--workload sort`` — grid-sorting serving.  ``SortServer`` is a
  continuous-batching scheduler: concurrent ``submit()`` calls (e.g.
  one per user upload) join the annealing loop at the next ROUND
  boundary and leave at the boundary where they finish — the
  tournament's rung structure as the preemption point — so a slow
  large request no longer stalls the traffic coalesced behind it the
  way the old fixed-boundary drain loop did.  Requests carry optional
  deadlines and priorities; admission control sheds load past a
  bounded queue depth as a typed ``QueueFull`` raised by ``submit()``
  (backpressure, never a hang); mixed (N, d) traffic is batched per
  shape bucket with batch sizes padded to powers of two so the compile
  cache stays bounded; and a failed (or straggling) device dispatch
  re-queues its requests from their last committed round boundary
  under a retry budget with exponential backoff
  (``runtime.fault_tolerance.RetryPolicy``) instead of failing every
  coalesced future — semantics and measurements: EXPERIMENTS.md
  §Serving, fault-injection proofs: tests/test_serving.py.

      PYTHONPATH=src python -m repro.launch.serve \
          --workload sort --requests 8 --sort-n 256 --rounds 30

  Scale-out: ``--mesh-devices D`` shards each coalesced batch across a
  D-device "data" mesh, and ``--tournament-rungs K --restarts S`` runs
  the S seeds per request as a successive-halving tournament
  (EXPERIMENTS.md §Scaling).  ``--use-kernel`` routes every instance's
  SoftSort apply — forward AND backward — through the fused Pallas
  kernel tier (EXPERIMENTS.md §Perf) instead of the chunked-jnp stream,
  and ``--band K`` / ``--band auto`` additionally switches the apply to
  the O(N*K) banded tier once the anneal is cold enough for its tail
  bound (EXPERIMENTS.md §Perf) — both compose with the mesh and the
  tournament.  ``--dtype bfloat16`` (with ``--use-kernel``) selects the
  mixed-precision kernel tier: bf16 score/payload compute and half the
  payload HBM traffic, f32 keys/stats/Adam (EXPERIMENTS.md §Perf).

  Elastic capacity (EXPERIMENTS.md §Robustness, "Elastic capacity"):
  ``--device-health K`` classifies dispatch failures through a
  ``DeviceHealthMonitor`` — a device named by ``DeviceLost`` K times is
  evicted, the mesh re-shards over the survivors at the next rung
  boundary (bit-identical per seed; the carry is layout-free), and
  returning devices grow it back.  ``--brownout`` arms the overload
  brownout ladder: under capacity loss or queue pressure, new requests
  degrade culled → adaptive → banded → bf16 before anything is shed.
"""
from __future__ import annotations

import argparse
import dataclasses
import threading
import time
from concurrent.futures import Future

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import get_config, list_archs
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import PRESETS
from repro.models import (
    decode_step,
    init_model,
    make_caches,
    prefill,
    reduced_config,
)


# --------------------------------------------------------------------------
# Sort serving: continuous-batching scheduler over run_round_segment.
# --------------------------------------------------------------------------

class RequestRejected(RuntimeError):
    """Base of every typed SortServer rejection.  A request the server
    cannot serve resolves with a subclass of this — never a hang."""


class QueueFull(RequestRejected):
    """Admission control: the bounded queue is at depth.  Raised
    synchronously by ``submit()`` so callers see backpressure at the
    moment they offer load, not as a future that never resolves."""


class DeadlineExceeded(RequestRejected):
    """The request's deadline passed before its anneal finished; it was
    shed at a round boundary (or at admission)."""


class ServerClosed(RequestRejected):
    """The server was closed while this request was queued/in flight."""


class RequestFailed(RequestRejected):
    """Device dispatch failed more than the retry budget allows;
    ``__cause__`` carries the last device error."""


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


# Brownout ladder rungs, mildest first.  Level k applies rungs 1..k:
#   culled   — run the request's restarts as a maximally-culled
#              tournament (keep=1 at every interior rung boundary)
#   adaptive — force schedule="adaptive" so converged restarts exit at
#              the first plateaued boundary instead of running all R
#   banded   — snap the dense apply to the O(N*K) banded tier
#   bf16     — drop the kernel tier to bfloat16 compute
_BROWNOUT_LADDER = ("culled", "adaptive", "banded", "bf16")


@dataclasses.dataclass(frozen=True)
class BrownoutPolicy:
    """Overload brownout: degrade per-request quality BEFORE shedding.

    When measured capacity drops (a device eviction, the straggler
    monitor halving the batch bucket cap) or queue depth crosses the
    watermarks, the server walks a monotone degradation ladder
    (``_BROWNOUT_LADDER``) one level per scheduler tick — and walks it
    back down, one level per tick, as capacity returns (hysteresis:
    the low watermark must clear before pressure stops counting).

    Degradations are applied to a request ONCE, at first admission, so
    an in-flight anneal never changes config mid-run (bit-identity per
    admitted config is preserved); they are keyed to deadline slack —
    a request with more than ``slack_full_s`` of slack (or no deadline
    at all) is degraded one level more gently, since shedding risk is
    what the ladder exists to avoid.  Every applied rung is recorded in
    ``stats["degradations"]`` and the per-request ``degraded`` tuple.

    ``high_watermark`` / ``low_watermark`` are fractions of
    ``queue_depth``.
    """
    high_watermark: float = 0.5
    low_watermark: float = 0.25
    slack_full_s: float = 2.0


@dataclasses.dataclass(eq=False)      # identity semantics: requests are
class _SortRequest:                   # tracked in lists via `is`, and the
                                      # generated field-wise __eq__ would
                                      # compare numpy arrays
    """One in-flight request: its problem, bookkeeping, and — once
    admitted — its per-restart engine state.  The state committed at
    each round boundary (orders/keys/losses) doubles as the restart
    checkpoint: a failed dispatch re-queues the request and it resumes
    from here, TrainSupervisor-style."""
    x: np.ndarray                      # (N, d)
    hw: tuple[int, int]
    d: int
    key: np.ndarray                    # (2,) uint32 base PRNG key
    future: Future
    priority: int
    seq: int
    deadline: float | None             # absolute monotonic, None = none
    submitted: float
    progress: int = 0                  # rounds executed so far
    attempts: int = 0                  # failed dispatches so far
    eligible_at: float = 0.0           # backoff gate for re-admission
    norm: float = 0.0
    orders: np.ndarray | None = None   # (S_live, N) int32
    keys: np.ndarray | None = None     # (S_live, 2) uint32 chained keys
    alive: np.ndarray | None = None    # (S_live,) original restart idx
    losses: np.ndarray | None = None   # (S, R) f32, NaN where culled
    # Guardrail state (runtime.guardrails): the request's probe policy
    # (None = server default at admission), its stateful monitor, the
    # integrity-strike count, and the self-healed config override a
    # DivergencePolicy rung installed (None = serve with server cfg).
    guardrail: object | None = None    # GuardrailPolicy
    monitor: object | None = None      # GuardrailMonitor (lazy, not saved)
    strikes: int = 0
    cfg_override: object | None = None  # ShuffleSoftSortConfig
    # adaptive mode only: the request's plateau controller (indexed by
    # ORIGINAL restart id) and which alive rows have already left the
    # anneal (converged early; frozen, but still winner candidates).
    ctrl: object | None = None
    done_mask: np.ndarray | None = None  # (S_live,) bool
    # Brownout bookkeeping: which ladder rungs were applied to this
    # request at admission (monotone: never grows after admission), and
    # whether the "culled" rung forces keep=1 at tournament boundaries.
    degraded: tuple = ()
    brownout_cull: bool = False

    @property
    def n_live(self) -> int:
        """Instances the next dispatch must carry (pre-admission: 1)."""
        if self.alive is None:
            return 1
        if self.done_mask is not None:
            return int((~self.done_mask).sum())
        return len(self.alive)


@dataclasses.dataclass
class WarmHandoff:
    """In-flight state a preempted ``SortServer`` hands its successor.

    ``close(drain=False)`` returns one: every unresolved request — with
    its future, committed round-boundary engine state, and controller —
    plus the server-owned PRNG stream position and sequence counter.  A
    new server constructed with ``resume=handoff`` adopts the requests
    and finishes them from their last committed rung: the original
    futures resolve from the new server, exactly once, bit-identical to
    what the first server would have produced (tests/test_serving.py,
    EXPERIMENTS.md §Robustness).  When the first server had a
    ``checkpoint_dir``, the same state is also persisted there, so a
    successor in a NEW process can ``resume=<dir>`` (fresh futures,
    exposed as ``server.resumed``)."""
    requests: list            # unresolved _SortRequests, seq order
    rng_state: dict           # np.random PCG64 bit-generator state
    seq: int                  # next submission sequence number
    # When the server's engine_fn is a FaultInjector (chaos tests), its
    # injection cursor/schedules ride along so a resumed chaos scenario
    # keeps exact fault accounting (FaultInjector.state_dict()).
    injector_state: dict | None = None
    # Elastic-capacity state: a successor preempted mid-brownout must
    # resume at the same ladder position, with the same evicted-device
    # set (its mesh rebuilt over the survivors) and the health
    # monitor's strike counts (DeviceHealthMonitor.state_dict()).
    brownout_level: int = 0
    evicted_devices: tuple = ()
    health_state: dict | None = None


class SortServer:
    """Continuous-batching scheduler for grid-sort requests.

    Requests join and leave the annealing loop at ROUND boundaries: the
    R-round schedule is split into ``sched_rungs`` equal rungs (the
    tournament's rung structure as the preemption quantum) and each
    scheduler tick advances every active instance by one rung via
    ``core.shufflesoftsort.run_round_segment`` — one scanned device
    call per (shape bucket, apply regime) in which instances at
    DIFFERENT anneal positions coexist, each consuming its own slice of
    the tau schedule.  A finished request leaves at its boundary while
    its batchmates keep annealing, and a newly admitted one joins at
    the next tick — no cohort barriers, so one slow large request no
    longer stalls everything coalesced behind it.

    Semantics (per seed, ``n_restarts == 1``, no culling): results are
    bit-identical to a sequential ``shuffle_soft_sort`` call with the
    same key, whatever traffic pattern interleaved the rounds — the
    per-instance tau promotion and chained keys/orders are exact
    (tests/test_serving.py).  With ``cfg.band`` the dense->banded
    switch snaps UP to the next rung boundary
    (``core.shufflesoftsort.rung_aligned_switch``) so no segment
    straddles regimes; a few extra rounds run dense, exactly.

    Production behaviors (EXPERIMENTS.md §Serving):

    * **Deadlines / priorities** — ``submit(..., deadline_s=,
      priority=)``; expired requests are shed at boundaries with a
      typed ``DeadlineExceeded``; admission is priority-then-FIFO.
    * **Backpressure** — at most ``queue_depth`` requests may wait for
      admission; past that ``submit()`` raises ``QueueFull``.
    * **Shape buckets** — mixed (N, d) traffic batches per ``(hw, d)``
      signature, with per-call batch sizes padded to the next power of
      two (capped at ``max_batch``), so compiled programs are bounded
      by |signatures| x |regimes| x log2(max_batch), not by the traffic.
    * **Fault tolerance** — a dispatch that raises re-queues its
      requests from their last committed boundary under
      ``retry: RetryPolicy`` (budget + exponential backoff); budget
      exhaustion resolves the future with ``RequestFailed``.  Every
      future resolves exactly once, result or typed rejection.
    * **Straggler rerouting** — per-dispatch wall time, normalized per
      instance-round, feeds a ``StragglerMonitor``; a flagged dispatch
      halves the batch bucket cap (restored after a healthy streak) so
      traffic reroutes into smaller batches around the slow path.
    * **Elastic capacity** (``device_health=DeviceHealthMonitor(...)``,
      EXPERIMENTS.md §Robustness "Elastic capacity") — a dispatch
      failure naming a device (``DeviceLost``) past the strike budget
      EVICTS it: the mesh is rebuilt over the survivors and the rung's
      requests replay from their last committed boundary on the next
      tick — a one-rung-boundary hiccup, bit-identical per seed to an
      uninterrupted run (the rung carry is layout-free).  Evicted
      devices that probe healthy again grow the mesh back at a tick
      boundary.  Counted in ``stats["evictions"]`` /
      ``stats["reshards"]`` / ``stats["device_returns"]``.
    * **Brownout ladder** (``brownout=BrownoutPolicy()``) — under
      capacity loss or queue pressure, newly admitted requests degrade
      through culled → adaptive → banded → bf16 (keyed to deadline
      slack) BEFORE anything is shed; the ladder steps one level per
      tick each way.  Counted per rung in ``stats["degradations"]``.
    * **Reproducibility** — requests submitted without a key draw from
      a server-owned PRNG stream seeded by ``seed``: same seed + same
      submission order = bit-identical results, end to end.

    Scale-out knobs (EXPERIMENTS.md §Scaling): ``mesh`` shard_maps every
    segment's instance axis across a 1-D "data" mesh;
    ``tournament_rungs > 1`` (with ``n_restarts > 1``) culls the worst
    ``cull_fraction`` of each request's restarts at its interior rung
    boundaries — successive halving, bit-identical survivors.

    Adaptive annealing (``cfg.schedule="adaptive"``, EXPERIMENTS.md
    §Adaptive): each request carries its own
    ``core.annealing.AdaptiveController`` (decision quantum == the
    scheduler rung), restarts jump to colder tau when their loss EWMA
    plateaus, the dense->banded switch comes from the MEASURED tail
    bound on their own keys, and the request resolves at the FIRST
    boundary where every surviving restart has converged — fewer
    rounds per request at equal final loss, counted in
    ``stats["adaptive_exits"]`` / ``stats["rounds_saved"]``.  With
    ``n_restarts == 1`` adaptive serving results are bit-identical to
    the adaptive engine paths per seed; controller state commits only
    on successful dispatches, so retries after a fault resume
    bit-exactly.
    """

    def __init__(self, hw, d, cfg=None, max_batch: int = 8,
                 max_wait_ms: float = 2.0, n_restarts: int = 1,
                 mesh=None, tournament_rungs: int = 1,
                 cull_fraction: float = 0.5, *,
                 queue_depth: int = 64, max_active: int | None = None,
                 sched_rungs: int | None = None, seed: int = 0,
                 default_deadline_s: float | None = None,
                 retry=None, straggler=None,
                 straggler_recovery: int = 8,
                 checkpoint_dir: str | None = None, resume=None,
                 engine_fn=None, autostart: bool = True,
                 guardrail=None, degrade=None,
                 brownout=None, device_health=None):
        from repro.core.shufflesoftsort import (
            ShuffleSoftSortConfig,
            _rung_boundaries,
            run_round_segment,
        )
        from repro.runtime.fault_tolerance import (
            DivergencePolicy,
            RetryPolicy,
        )
        from repro.runtime.guardrails import GuardrailPolicy
        from repro.runtime.straggler import StragglerMonitor

        self.hw = tuple(hw)
        self.n = self.hw[0] * self.hw[1]
        self.d = d
        self.cfg = cfg or ShuffleSoftSortConfig()
        self.max_batch = int(max_batch)
        self.max_wait_s = max_wait_ms / 1e3
        self.n_restarts = int(n_restarts)
        self.mesh = mesh
        self.tournament_rungs = int(tournament_rungs)
        self.cull_fraction = float(cull_fraction)
        self.queue_depth = int(queue_depth)
        self.max_active = (2 * self.max_batch if max_active is None
                           else int(max_active))
        self.default_deadline_s = default_deadline_s
        self.retry = retry or RetryPolicy()
        self.straggler = straggler or StragglerMonitor()
        self.straggler_recovery = int(straggler_recovery)
        self._engine = engine_fn or run_round_segment
        if guardrail is not None and not isinstance(guardrail,
                                                    GuardrailPolicy):
            raise TypeError(
                f"guardrail must be a GuardrailPolicy or None, "
                f"got {guardrail!r}")
        self.guardrail = guardrail          # server-default probe policy
        self.degrade = degrade or DivergencePolicy()
        if brownout is not None and not isinstance(brownout,
                                                   BrownoutPolicy):
            raise TypeError(
                f"brownout must be a BrownoutPolicy or None, "
                f"got {brownout!r}")
        self.brownout = brownout
        self.device_health = device_health  # DeviceHealthMonitor or None

        rounds = self.cfg.rounds
        self.adaptive = self.cfg.schedule == "adaptive"
        tournament = self.tournament_rungs > 1 and self.n_restarts > 1
        if sched_rungs is None:
            if self.adaptive:
                # Scheduler rung == controller decision quantum, so
                # every boundary the server preempts at is a boundary
                # the controller observed at — adaptive server results
                # stay bit-identical to the engine's adaptive runs.
                from repro.core.annealing import adaptive_seg_len
                sched_rungs = rounds // adaptive_seg_len(self.cfg)
            else:
                sched_rungs = (
                    self.tournament_rungs if tournament else
                    next(k for k in (4, 3, 2, 1) if rounds % k == 0))
        self.sched_rungs = int(sched_rungs)
        if not 1 <= self.sched_rungs <= rounds or rounds % self.sched_rungs:
            raise ValueError(
                f"sched_rungs={self.sched_rungs} must divide "
                f"cfg.rounds={rounds} (uniform preemption quantum)")
        if tournament and (rounds % self.tournament_rungs
                           or self.sched_rungs % self.tournament_rungs):
            raise ValueError(
                f"tournament_rungs={self.tournament_rungs} must divide "
                f"cfg.rounds={rounds} and sched_rungs={self.sched_rungs} "
                "so cull boundaries land on scheduler boundaries")
        self.seg_len = rounds // self.sched_rungs
        self._cull_edges = (
            set(_rung_boundaries(rounds, self.tournament_rungs)[:-1])
            if tournament else set())

        self._rng = np.random.Generator(np.random.PCG64(seed))
        self.stats = {
            "requests": 0, "batches": 0, "batch_sizes": [],
            "completed": 0, "failed": 0, "deadline_missed": 0,
            "queue_rejected": 0, "retries": 0, "recoveries": 0,
            "stragglers": 0, "culled": 0, "latencies_ms": [],
            "adaptive_exits": 0, "rounds_saved": 0, "resumed": 0,
            "integrity_violations": 0, "self_heals": 0,
            "integrity_incidents": [],
            "compile_keys": set(),
            "evictions": 0, "reshards": 0, "device_returns": 0,
            "brownouts": 0,
            "degradations": {r: 0 for r in _BROWNOUT_LADDER},
        }
        self.events: list[dict] = []
        self._cv = threading.Condition()
        self._pending: list[_SortRequest] = []
        self._active: list[_SortRequest] = []
        self._stop = False
        self._preempt = False
        self._seq = 0
        self._dispatch_idx = 0
        self._bucket_cap = self.max_batch
        self._healthy_streak = 0
        self._switch_cache: dict[tuple, int] = {}
        # Elastic-capacity state: the mesh as constructed (the full
        # device complement a returning device can grow back into),
        # the currently-evicted device ids, and the brownout ladder
        # position (0 = full quality).
        self._mesh_devices = (None if mesh is None
                              else list(mesh.devices.flat))
        self._evicted: list[int] = []
        self._brownout_level = 0
        self.checkpoint_dir = checkpoint_dir
        self.resumed: list[_SortRequest] = []
        if resume is not None:
            handoff = (resume if isinstance(resume, WarmHandoff)
                       else self._load_handoff(resume))
            self._adopt(handoff)
        self._worker = threading.Thread(target=self._run, daemon=True)
        self._started = False
        if autostart:
            self.start()

    def start(self):
        """Start the scheduler thread (no-op if already running).
        ``autostart=False`` + ``start()`` lets tests enqueue a batch of
        requests and observe one deterministic admission pass."""
        if not self._started:
            self._started = True
            self._worker.start()

    # ---- client API ------------------------------------------------------

    def submit(self, x: np.ndarray, key=None, *, hw=None,
               priority: int = 0, deadline_s: float | None = None,
               guardrail=None) -> Future:
        """Enqueue one (N, d) problem; returns a Future of
        ``(order (N,), sorted (N, d), losses (R,))``.

        ``hw`` defaults to the server's construction signature; passing
        a different grid (with a matching x) routes the request to its
        own shape bucket.  ``priority`` — higher admits first.
        ``deadline_s`` — relative seconds; past it the request is shed
        with ``DeadlineExceeded``.  Missing ``key`` draws from the
        server-owned seeded stream (reproducible per server seed).
        ``guardrail`` — a per-request ``GuardrailPolicy`` overriding the
        server default (``GuardrailPolicy(mode="off")`` opts a request
        out of a guarded server's probes).
        Raises ``QueueFull`` / ``ServerClosed`` synchronously.
        """
        from repro.runtime.guardrails import GuardrailPolicy
        if guardrail is not None and not isinstance(guardrail,
                                                    GuardrailPolicy):
            raise TypeError(
                f"guardrail must be a GuardrailPolicy or None, "
                f"got {guardrail!r}")
        x = np.asarray(x, np.float32)
        req_hw = self.hw if hw is None else tuple(hw)
        if x.ndim != 2 or x.shape[0] != req_hw[0] * req_hw[1]:
            raise ValueError(
                f"x shape {x.shape} does not fit grid {req_hw}")
        if hw is None and x.shape != (self.n, self.d):
            raise ValueError(
                f"x shape {x.shape} != server signature "
                f"{(self.n, self.d)}; pass hw= to use another bucket")
        if deadline_s is None:
            deadline_s = self.default_deadline_s
        now = time.monotonic()
        with self._cv:
            if self._stop:
                raise ServerClosed("SortServer is closed")
            if len(self._pending) >= self.queue_depth:
                self.stats["queue_rejected"] += 1
                raise QueueFull(
                    f"queue depth {self.queue_depth} reached; retry later")
            if key is None:
                key = jax.random.PRNGKey(
                    int(self._rng.integers(0, 2**31 - 1)))
            if jnp.issubdtype(jnp.asarray(key).dtype, jax.dtypes.prng_key):
                key = jax.random.key_data(key)
            fut: Future = Future()
            req = _SortRequest(
                x=x, hw=req_hw, d=x.shape[1],
                key=np.asarray(key, np.uint32).reshape(2),
                future=fut, priority=int(priority), seq=self._seq,
                deadline=None if deadline_s is None else now + deadline_s,
                submitted=now,
                guardrail=self.guardrail if guardrail is None
                else guardrail)
            self._seq += 1
            self.stats["requests"] += 1
            self._pending.append(req)
            self._cv.notify()
        return fut

    def close(self, drain: bool = True):
        """Stop the scheduler.

        ``drain=True`` (the default): every queued or in-flight future
        resolves with ``ServerClosed`` — no caller blocks forever.

        ``drain=False`` — **warm restart** (simulated preemption): stop
        WITHOUT rejecting.  Returns a ``WarmHandoff`` carrying every
        unresolved request at its last committed round boundary; a
        successor server built with ``resume=`` finishes them and the
        original futures resolve exactly once.  If ``checkpoint_dir``
        is set the handoff is also persisted there for cross-process
        resume (``resume=<dir>``).
        """
        with self._cv:
            self._stop = True
            if not drain:
                self._preempt = True
            self._cv.notify_all()
        if self._started:
            self._worker.join(timeout=120)
        if drain:
            self._reject_all(ServerClosed("SortServer closed"))
            return None
        with self._cv:
            inflight = self._pending + self._active
            self._pending, self._active = [], []
        inflight = sorted((r for r in inflight if not r.future.done()),
                          key=lambda r: r.seq)
        handoff = WarmHandoff(requests=inflight,
                              rng_state=self._rng.bit_generator.state,
                              seq=self._seq,
                              injector_state=(
                                  self._engine.state_dict()
                                  if hasattr(self._engine, "state_dict")
                                  else None),
                              brownout_level=self._brownout_level,
                              evicted_devices=tuple(self._evicted),
                              health_state=(
                                  self.device_health.state_dict()
                                  if self.device_health is not None
                                  else None))
        self.events.append({"event": "preempt",
                            "inflight": len(inflight)})
        if self.checkpoint_dir is not None:
            self._save_handoff(handoff)
        return handoff

    # ---- warm restart (preemption handoff) ------------------------------

    def _adopt(self, handoff: WarmHandoff):
        """Adopt a predecessor's in-flight requests: they re-enter the
        admission queue at their last committed round boundary (backoff
        gates cleared — the fault was the preemption, not the request)
        and their futures resolve from THIS server."""
        self._rng.bit_generator.state = handoff.rng_state
        self._seq = max(self._seq, int(handoff.seq))
        if (handoff.injector_state is not None
                and hasattr(self._engine, "load_state_dict")):
            self._engine.load_state_dict(handoff.injector_state)
        # Resume the elastic-capacity state: same brownout ladder
        # position, same evicted-device set (mesh rebuilt over the
        # survivors), same health-monitor strikes.
        self._brownout_level = int(handoff.brownout_level)
        evicted = [int(dv) for dv in (handoff.evicted_devices or ())]
        if evicted:
            self._evicted = evicted
            self._reshard()
        if (handoff.health_state is not None
                and self.device_health is not None):
            self.device_health.load_state_dict(handoff.health_state)
        for req in handoff.requests:
            if req.future.done():       # pragma: no cover - defensive
                continue
            req.eligible_at = 0.0
            self.stats["requests"] += 1
            self.stats["resumed"] += 1
            self.resumed.append(req)
            self._pending.append(req)
            self.events.append({"event": "adopt", "seq": req.seq,
                                "progress": req.progress})

    # ---- elastic capacity: eviction / re-shard / brownout ---------------

    def _reshard(self):
        """Rebuild ``self.mesh`` over the non-evicted devices of the
        construction-time complement.  No carry ever moves: request
        state lives host-side in logical layout between rungs, so the
        next dispatch simply re-pads onto the new mesh (``mesh=None``
        when every device is out — the vmap engine still serves)."""
        if self._mesh_devices is None:
            return
        from repro.launch.mesh import make_sort_mesh
        gone = set(self._evicted)
        survivors = [dv for dv in self._mesh_devices if dv.id not in gone]
        self.mesh = (make_sort_mesh(len(survivors), devices=survivors)
                     if survivors else None)

    def _device_failure(self, reqs: list[_SortRequest], exc) -> bool:
        """Classify a dispatch failure through the health monitor.  A
        LOST verdict evicts the device, re-shards, and re-queues the
        rung's requests WITHOUT consuming retry budget (the fault was
        the device, not the request) — the replay at the next tick runs
        on the survivor mesh, so the detection→re-shard gap is exactly
        one rung boundary.  Returns True when handled elastically."""
        if self.device_health is None:
            return False
        dev = self.device_health.classify(exc)
        if dev is None:
            return False
        self._evicted.append(int(dev))
        self.stats["evictions"] += 1
        self._reshard()
        self.stats["reshards"] += 1
        n_surv = (0 if self.mesh is None
                  else int(self.mesh.shape["data"]))
        self.events.append({"event": "evict", "device": int(dev),
                            "survivors": n_surv,
                            "requeued": len(reqs)})
        now = time.monotonic()
        for req in reqs:
            self._active.remove(req)
            req.eligible_at = now
            with self._cv:
                self._pending.append(req)
        return True

    def _poll_device_returns(self):
        """Grow the mesh back at a tick boundary when evicted devices
        probe healthy again (``DeviceHealthMonitor.poll_returns``)."""
        if self.device_health is None or not self._evicted:
            return
        back = self.device_health.poll_returns()
        grew = False
        for dev in back:
            dev = int(dev)
            if dev in self._evicted:
                self._evicted.remove(dev)
                self.stats["device_returns"] += 1
                grew = True
                self.events.append({"event": "device_return",
                                    "device": dev})
        if grew:
            self._reshard()

    def _update_brownout(self, queue_len: int):
        """Step the brownout ladder one level per tick toward the
        pressure target: +1 while capacity is down (eviction, straggler
        cap halving) or the queue is past a watermark, -1 as it
        returns.  One step per tick is the hysteresis — a transient
        spike cannot slam the ladder to bf16 and back within a rung."""
        if self.brownout is None:
            return
        qfrac = queue_len / max(1, self.queue_depth)
        pressure = (2 if qfrac >= self.brownout.high_watermark
                    else 1 if qfrac >= self.brownout.low_watermark else 0)
        target = min(len(_BROWNOUT_LADDER),
                     (1 if self._evicted else 0)
                     + (1 if self._bucket_cap < self.max_batch else 0)
                     + pressure)
        if target > self._brownout_level:
            self._brownout_level += 1
            self.events.append({"event": "brownout_up",
                                "level": self._brownout_level,
                                "target": target, "queue": queue_len})
        elif target < self._brownout_level:
            self._brownout_level -= 1
            self.events.append({"event": "brownout_down",
                                "level": self._brownout_level,
                                "target": target, "queue": queue_len})

    def _apply_brownout(self, req: _SortRequest, now: float):
        """Apply the current ladder level to a request at FIRST
        admission (never mid-anneal: an admitted request's config is
        immutable, so its results stay bit-identical to an unloaded
        server given the same admitted config).  Requests with more
        than ``slack_full_s`` of deadline slack — or no deadline — take
        one level less: the ladder exists to protect deadline-bound
        traffic from shedding."""
        if (self.brownout is None or self._brownout_level <= 0
                or req.orders is not None):
            return
        level = self._brownout_level
        slack = None if req.deadline is None else req.deadline - now
        if slack is None or slack > self.brownout.slack_full_s:
            level -= 1
        if level <= 0:
            return
        cfg = self._cfg_for(req)
        applied = []
        if (level >= 1 and self._cull_edges and self.n_restarts > 1
                and not req.brownout_cull):
            req.brownout_cull = True
            applied.append("culled")
        if level >= 2 and cfg.schedule != "adaptive":
            cfg = dataclasses.replace(cfg, schedule="adaptive")
            applied.append("adaptive")
        if level >= 3 and cfg.band is None:
            from repro.core.shufflesoftsort import resolve_band
            auto = dataclasses.replace(cfg, band="auto")
            if resolve_band(auto, req.x.shape[0]) is not None:
                cfg = auto
                applied.append("banded")
        if level >= 4 and cfg.use_kernel and cfg.compute_dtype == "float32":
            cfg = dataclasses.replace(cfg, compute_dtype="bfloat16")
            applied.append("bf16")
        if not applied:
            return
        if cfg is not self._cfg_for(req):
            req.cfg_override = cfg
        req.degraded = tuple(applied)
        for rung in applied:
            self.stats["degradations"][rung] += 1
        self.stats["brownouts"] += 1
        self.events.append({"event": "brownout_degrade", "seq": req.seq,
                            "level": self._brownout_level,
                            "applied": applied})

    def _save_handoff(self, handoff: WarmHandoff):
        """Persist the handoff to ``checkpoint_dir`` (atomic, via
        CheckpointManager): flat per-request arrays + a JSON manifest of
        the scalars, so a successor in a new process can resume."""
        from repro.core.annealing import AdaptiveController
        from repro.runtime.checkpoint import CheckpointManager
        now = time.monotonic()
        arrays: dict[str, np.ndarray] = {}
        metas = []
        for i, req in enumerate(handoff.requests):
            arrays[f"req{i}_x"] = req.x
            arrays[f"req{i}_key"] = req.key
            has_state = req.orders is not None
            if has_state:
                arrays[f"req{i}_orders"] = req.orders
                arrays[f"req{i}_keys"] = req.keys
                arrays[f"req{i}_alive"] = req.alive
                arrays[f"req{i}_losses"] = req.losses
                if req.done_mask is not None:
                    arrays[f"req{i}_done"] = req.done_mask
                if req.ctrl is not None:
                    for f in AdaptiveController._STATE_FIELDS:
                        arrays[f"req{i}_ctrl_{f}"] = getattr(req.ctrl, f)
            metas.append({
                "hw": list(req.hw), "d": int(req.d),
                "priority": int(req.priority), "seq": int(req.seq),
                "progress": int(req.progress),
                "attempts": int(req.attempts), "norm": float(req.norm),
                "deadline_left": (None if req.deadline is None
                                  else max(0.0, req.deadline - now)),
                "has_state": has_state,
                "has_ctrl": req.ctrl is not None,
                "has_done": req.done_mask is not None,
                "strikes": int(req.strikes),
                "guardrail": (None if req.guardrail is None
                              else dataclasses.asdict(req.guardrail)),
                "cfg_override": (None if req.cfg_override is None
                                 else dataclasses.asdict(req.cfg_override)),
                "degraded": list(req.degraded),
                "brownout_cull": bool(req.brownout_cull),
            })
        mgr = CheckpointManager(self.checkpoint_dir, keep=1,
                                async_save=False)
        mgr.save(0, arrays, extra={
            "kind": "sort-server-handoff",
            "rng_state": handoff.rng_state,
            "seq": int(handoff.seq),
            "requests": metas,
            "injector_state": handoff.injector_state,
            "brownout_level": int(handoff.brownout_level),
            "evicted_devices": [int(dv) for dv in
                                handoff.evicted_devices],
            "health_state": handoff.health_state,
        })

    def _load_handoff(self, path: str) -> WarmHandoff:
        """Rebuild a ``WarmHandoff`` persisted by ``_save_handoff``.
        Requests get FRESH futures (the writer's died with its process);
        they are exposed on ``self.resumed`` after adoption.  Adaptive
        controllers are reconstructed from this server's config and
        restored bit-exactly via ``load_state_dict``."""
        from repro.core.annealing import AdaptiveController
        from repro.runtime.checkpoint import CheckpointManager
        mgr = CheckpointManager(path, keep=1, async_save=False)
        step = mgr.latest_step()
        if step is None:
            raise FileNotFoundError(f"no server handoff in {path}")
        extra = mgr.manifest(step).get("extra", {})
        if extra.get("kind") != "sort-server-handoff":
            raise ValueError(
                f"{path} step {step} is not a SortServer handoff "
                f"(kind={extra.get('kind')!r})")
        metas = extra["requests"]
        names: list[str] = []
        for i, m in enumerate(metas):
            names += [f"req{i}_x", f"req{i}_key"]
            if m["has_state"]:
                names += [f"req{i}_orders", f"req{i}_keys",
                          f"req{i}_alive", f"req{i}_losses"]
                if m["has_done"]:
                    names.append(f"req{i}_done")
                if m["has_ctrl"]:
                    names += [f"req{i}_ctrl_{f}"
                              for f in AdaptiveController._STATE_FIELDS]
        # int placeholder leaves carry no dtype, so restore() returns
        # the arrays exactly as saved — no cast on the resume path.
        arrays, _ = mgr.restore({k: 0 for k in names}, step)
        now = time.monotonic()
        reqs = []
        for i, m in enumerate(metas):
            req = _SortRequest(
                x=arrays[f"req{i}_x"], hw=tuple(m["hw"]), d=int(m["d"]),
                key=arrays[f"req{i}_key"], future=Future(),
                priority=int(m["priority"]), seq=int(m["seq"]),
                deadline=(None if m["deadline_left"] is None
                          else now + float(m["deadline_left"])),
                submitted=now, progress=int(m["progress"]),
                attempts=int(m["attempts"]), norm=float(m["norm"]))
            req.strikes = int(m.get("strikes", 0))
            req.degraded = tuple(m.get("degraded", ()))
            req.brownout_cull = bool(m.get("brownout_cull", False))
            if m.get("guardrail") is not None:
                from repro.runtime.guardrails import GuardrailPolicy
                req.guardrail = GuardrailPolicy(**m["guardrail"])
            if m.get("cfg_override") is not None:
                from repro.core.shufflesoftsort import (
                    ShuffleSoftSortConfig,
                )
                req.cfg_override = ShuffleSoftSortConfig(
                    **m["cfg_override"])
            if m["has_state"]:
                req.orders = arrays[f"req{i}_orders"]
                req.keys = arrays[f"req{i}_keys"]
                req.alive = arrays[f"req{i}_alive"]
                req.losses = arrays[f"req{i}_losses"]
                if m["has_done"]:
                    req.done_mask = arrays[f"req{i}_done"].astype(bool)
                if m["has_ctrl"]:
                    from repro.core.shufflesoftsort import (
                        make_adaptive_controller,
                    )
                    # A brownout-forced-adaptive request on a fixed
                    # server carries the adaptive schedule in its
                    # cfg_override, not the server config.
                    ctrl_cfg = (req.cfg_override
                                if req.cfg_override is not None
                                else self.cfg)
                    ctrl = make_adaptive_controller(
                        ctrl_cfg, len(req.losses), req.x.shape[0],
                        seg_len=self.seg_len)
                    ctrl.load_state_dict(
                        {f: arrays[f"req{i}_ctrl_{f}"]
                         for f in AdaptiveController._STATE_FIELDS})
                    req.ctrl = ctrl
            reqs.append(req)
        return WarmHandoff(requests=reqs, rng_state=extra["rng_state"],
                           seq=int(extra["seq"]),
                           injector_state=extra.get("injector_state"),
                           brownout_level=int(
                               extra.get("brownout_level", 0)),
                           evicted_devices=tuple(
                               extra.get("evicted_devices", []) or []),
                           health_state=extra.get("health_state"))

    # ---- resolution bookkeeping (every future resolves exactly once) ----

    def _resolve_ok(self, req: _SortRequest, result):
        if req.future.done():       # pragma: no cover - defensive
            return
        self.stats["completed"] += 1
        if req.attempts > 0:
            self.stats["recoveries"] += 1
        latency_ms = (time.monotonic() - req.submitted) * 1e3
        self.stats["latencies_ms"].append(latency_ms)
        self.events.append({"event": "complete", "seq": req.seq,
                            "latency_ms": latency_ms,
                            "attempts": req.attempts})
        req.future.set_result(result)

    def _resolve_exc(self, req: _SortRequest, exc: Exception, counter: str):
        if req.future.done():       # pragma: no cover - defensive
            return
        self.stats[counter] += 1
        self.events.append({"event": counter, "seq": req.seq})
        req.future.set_exception(exc)

    def _reject_all(self, exc: Exception):
        with self._cv:
            doomed = self._pending + self._active
            self._pending, self._active = [], []
        for req in doomed:
            if not req.future.done():
                self._resolve_exc(req, exc, "failed")

    # ---- scheduler -------------------------------------------------------

    def _run(self):
        while True:
            with self._cv:
                while (not self._stop and not self._pending
                       and not self._active):
                    self._cv.wait(timeout=0.5)
                if self._stop:
                    break
                fresh_wake = not self._active
            if fresh_wake and self.max_wait_s > 0:
                time.sleep(self.max_wait_s)   # let a submit burst coalesce
            try:
                did_work = self._tick()
            except Exception as e:  # pragma: no cover - defensive
                # A scheduler bug must never strand futures: fail
                # everything in flight (typed) and keep serving.
                err = RequestFailed(f"scheduler error: {e!r}")
                err.__cause__ = e
                self._reject_all(err)
                continue
            if not did_work:
                time.sleep(0.02)              # pending all in backoff
        # Warm restart: a preempted server leaves its in-flight requests
        # intact for close(drain=False) to hand off.
        if not self._preempt:
            self._reject_all(ServerClosed("SortServer closed"))

    def _admit(self, req: _SortRequest):
        """First admission: derive restart keys + engine state.  Restart
        0 keeps the raw key so the single-restart result reproduces a
        sequential run; re-admissions after a fault keep their state."""
        if req.orders is not None:
            return
        from repro.core.losses import mean_pairwise_distance
        s, n = self.n_restarts, req.x.shape[0]
        base = jnp.asarray(req.key)
        if s == 1:
            keys = base[None]
        else:
            keys = jnp.concatenate(
                [base[None],
                 jax.random.split(jax.random.fold_in(base, 1), s - 1)])
        req.keys = np.array(keys, np.uint32).reshape(s, 2)  # writable copy
        req.norm = float(np.float32(
            mean_pairwise_distance(jnp.asarray(req.x))))
        req.orders = np.tile(np.arange(n, dtype=np.int32), (s, 1))
        req.alive = np.arange(s)
        req.losses = np.full((s, self.cfg.rounds), np.nan, np.float32)
        # Adaptivity is per REQUEST config, not per server: a brownout
        # rung (or a caller) can force schedule="adaptive" via
        # cfg_override on an otherwise fixed-schedule server.
        cfg_req = self._cfg_for(req)
        if cfg_req.schedule == "adaptive":
            from repro.core.shufflesoftsort import make_adaptive_controller
            req.ctrl = make_adaptive_controller(
                cfg_req, s, n, seg_len=self.seg_len)
            req.done_mask = np.zeros(s, bool)
        self.events.append({"event": "admit", "seq": req.seq})

    def _cfg_for(self, req: _SortRequest):
        """The config this request dispatches under: the server config,
        unless an integrity self-heal installed a per-request override
        (kernel retired, band widened, dtype promoted)."""
        return self.cfg if req.cfg_override is None else req.cfg_override

    def _regime(self, req: _SortRequest) -> str:
        from repro.core.shufflesoftsort import (
            resolve_band,
            rung_aligned_switch,
        )
        cfg = self._cfg_for(req)
        n = req.x.shape[0]
        if resolve_band(cfg, n) is None:
            return "dense"
        if req.ctrl is not None:
            # Measured switch, from the request's controller: the
            # request runs banded once EVERY live restart's own tail
            # bound has cleared (conservative — the laggard holds its
            # batchmates dense a rung longer, which is exact, just
            # costlier; with n_restarts == 1 this is exactly the
            # engine's per-instance rule, so single-restart serving
            # stays bit-identical to the adaptive engine paths).
            live = req.alive[~req.done_mask]
            return ("banded" if live.size and req.ctrl.banded[live].all()
                    else "dense")
        ck = (n, cfg)
        if ck not in self._switch_cache:
            self._switch_cache[ck] = rung_aligned_switch(
                cfg, n, self.seg_len)
        return "banded" if req.progress >= self._switch_cache[ck] else "dense"

    def _tick(self) -> bool:
        """One scheduler pass: grow back returned devices, step the
        brownout ladder, shed expired, admit (applying the ladder),
        dispatch one rung per (shape bucket, regime) group, cull,
        finalize."""
        now = time.monotonic()
        self._poll_device_returns()
        with self._cv:
            self._update_brownout(len(self._pending))
        admitted: list[_SortRequest] = []
        with self._cv:
            keep = []
            for req in self._pending:
                if req.deadline is not None and now > req.deadline:
                    self._resolve_exc(
                        req, DeadlineExceeded(
                            f"deadline passed while queued (seq {req.seq})"),
                        "deadline_missed")
                else:
                    keep.append(req)
            keep.sort(key=lambda r: (-r.priority, r.seq))
            active_inst = sum(r.n_live for r in self._active)
            rest = []
            for req in keep:
                need = req.n_live
                fits = (active_inst + need <= self.max_active
                        or (active_inst == 0 and not admitted))
                if now >= req.eligible_at and fits:
                    admitted.append(req)
                    active_inst += need
                else:
                    rest.append(req)
            self._pending = rest
        for req in admitted:
            self._apply_brownout(req, now)
            self._admit(req)
        self._active.extend(admitted)
        if not self._active:
            return False

        # shed expired active requests at the round boundary
        still = []
        for req in self._active:
            if req.deadline is not None and now > req.deadline:
                self._resolve_exc(
                    req, DeadlineExceeded(
                        f"deadline passed at round {req.progress} "
                        f"(seq {req.seq})"),
                    "deadline_missed")
            else:
                still.append(req)
        self._active = still

        groups: dict[tuple, list[_SortRequest]] = {}
        for req in self._active:
            # Guardrail policy and self-healed config extend the group
            # key: every request in one device call must share a config
            # (one compiled program) and a probe policy (uniform
            # verification of the call's slices).
            groups.setdefault(
                ((req.hw, req.d), self._regime(req),
                 req.guardrail, req.cfg_override), []).append(req)
        for (sig, regime, _pol, _ovr), reqs in groups.items():
            chunk: list[_SortRequest] = []
            size = 0
            for req in reqs:
                if chunk and size + req.n_live > self._bucket_cap:
                    self._dispatch(chunk, regime)
                    chunk, size = [], 0
                chunk.append(req)
                size += req.n_live
            if chunk:
                self._dispatch(chunk, regime)
        return True

    def _dispatch(self, reqs: list[_SortRequest], regime: str):
        """One coalesced device call advancing ``reqs`` by one rung.

        Adaptive mode dispatches only each request's LIVE restarts
        (early-stopped rows stay frozen at their converged state), at
        their controller's schedule positions — a plateau jump shows up
        here as a request whose next segment reads a colder slice of
        the tau schedule than its executed-round count suggests.
        """
        hw = reqs[0].hw
        cfg_use = self._cfg_for(reqs[0])   # uniform per group (key'd)
        # Adaptivity is a property of the GROUP's config (cfg_override
        # is in the group key), so brownout-forced-adaptive requests
        # dispatch adaptively on an otherwise fixed server.
        adaptive = cfg_use.schedule == "adaptive"
        pol = reqs[0].guardrail
        guarded = pol is not None and pol.mode != "off"
        # Per-request rows going into this call (adaptive: live only).
        sels = [np.flatnonzero(~r.done_mask) if adaptive
                else np.arange(len(r.alive)) for r in reqs]
        xs = np.concatenate(
            [np.repeat(r.x[None], len(sel), axis=0)
             for r, sel in zip(reqs, sels)])
        orders = np.concatenate(
            [r.orders[sel] for r, sel in zip(reqs, sels)])
        keys = np.concatenate([r.keys[sel] for r, sel in zip(reqs, sels)])
        norms = np.concatenate(
            [np.full(len(sel), r.norm, np.float32)
             for r, sel in zip(reqs, sels)])
        if adaptive:
            progress = np.concatenate(
                [r.ctrl.pos[r.alive[sel]] for r, sel in zip(reqs, sels)])
        else:
            progress = np.concatenate(
                [np.full(len(sel), r.progress, np.int64)
                 for r, sel in zip(reqs, sels)])
        bs = len(progress)
        # Guardrail probes need this rung's INPUT state after the
        # commit loop overwrites per-request state: alias the pre-pad
        # arrays (padding below reallocates, so these stay intact).
        xs_in, orders_in, keys_in = xs, orders, keys
        norms_in, progress_in = norms, progress
        # pad to the next power of two (capped at max_batch when the
        # chunk fits under it) so compiled programs stay bounded by
        # |signatures| x |regimes| x log2(max_batch), not traffic
        bucket = (min(_next_pow2(bs), self.max_batch)
                  if bs <= self.max_batch else _next_pow2(bs))
        if bucket > bs:
            pad = bucket - bs
            xs = np.concatenate([xs, np.repeat(xs[:1], pad, axis=0)])
            orders = np.concatenate(
                [orders, np.repeat(orders[:1], pad, axis=0)])
            keys = np.concatenate([keys, np.repeat(keys[:1], pad, axis=0)])
            norms = np.concatenate([norms, np.repeat(norms[:1], pad)])
            progress = np.concatenate(
                [progress, np.repeat(progress[:1], pad)])
        self.stats["compile_keys"].add(
            (hw, reqs[0].d, regime, bucket, self.seg_len))

        t0 = time.perf_counter()
        try:
            if adaptive:
                # regime= bypasses the model-based switch check (the
                # controller owns the grouping); with_w= feeds the
                # measured tail bound.
                o, k, l, w = self._engine(
                    xs, orders, keys, norms, progress, self.seg_len,
                    hw=hw, cfg=cfg_use, mesh=self.mesh,
                    regime=regime, with_w=True)
                w = np.asarray(w)
            else:
                w = None
                o, k, l = self._engine(xs, orders, keys, norms, progress,
                                       self.seg_len, hw=hw, cfg=cfg_use,
                                       mesh=self.mesh)
            o, k, l = np.asarray(o), np.asarray(k), np.asarray(l)
        except Exception as e:
            if not self._device_failure(reqs, e):
                self._on_failure(reqs, e)
            return
        if self.device_health is not None and self.mesh is not None:
            self.device_health.record_success(
                dv.id for dv in self.mesh.devices.flat)
        # Divergence sentinel: a non-finite loss (or soft-sort key) must
        # never commit into request state — route it through the retry
        # path as a typed NumericalDivergence BEFORE the commit below,
        # so the re-dispatch replays from the last finite boundary.
        # Guarded groups instead attribute non-finite state per request
        # slice (the monitor's "finite" probe), so one corrupted request
        # never fails its clean batchmates.
        if not guarded and (
                not np.isfinite(l).all()
                or (adaptive and not np.isfinite(w).all())):
            from repro.core.shufflesoftsort import NumericalDivergence
            self._on_failure(reqs, NumericalDivergence(
                f"non-finite loss in serving dispatch (regime {regime})",
                round=int(progress_in.min()),
                dtype=str(cfg_use.compute_dtype), context="serving"))
            return
        dt = time.perf_counter() - t0
        self._record_timing(dt, self.seg_len * bucket)
        self.stats["batches"] += 1
        self.stats["batch_sizes"].append(bs)

        bad: list = []
        if guarded:
            bad = self._verify_slices(
                reqs, sels, regime, cfg_use, hw,
                xs_in, orders_in, keys_in, norms_in, progress_in,
                o, k, l, w)
        bad_set = {id(r) for r, _ in bad}
        off = 0
        for req, sel in zip(reqs, sels):
            nl = len(sel)
            if id(req) in bad_set:
                off += nl           # corrupted: do NOT commit; the
                continue            # retry replays this rung exactly
            if adaptive:
                orig = req.alive[sel]
                exec0 = int(req.ctrl.executed[orig[0]])
                req.orders[sel] = o[off:off + nl]
                req.keys[sel] = k[off:off + nl]
                seg_losses = l[:, off:off + nl].T        # (nl, seg)
                req.losses[orig, exec0:exec0 + self.seg_len] = seg_losses
                # Controller state commits only on a SUCCESSFUL
                # dispatch (we are past the except above), so a retried
                # request re-observes nothing and resumes bit-exactly.
                req.ctrl.observe(orig, seg_losses, w[off:off + nl])
                req.done_mask[sel] = req.ctrl.done[orig]
            else:
                req.orders = o[off:off + nl]
                req.keys = k[off:off + nl]
                req.losses[req.alive,
                           req.progress:req.progress + self.seg_len] = (
                    l[:, off:off + nl].T)
            req.progress += self.seg_len
            off += nl
            self._post_rung(req)
        for req, exc in bad:
            self._integrity_failure(req, exc)

    # ---- guardrails: per-request probe verification + self-healing ------

    def _verify_slices(self, reqs, sels, regime, cfg_use, hw,
                       xs_in, orders_in, keys_in, norms_in, progress_in,
                       o, k, l, w):
        """Run this dispatch's guardrail probes per request slice,
        BEFORE any commit.  Returns ``[(req, IntegrityViolation), ...]``
        for the slices that failed — only those requests re-queue; their
        clean batchmates commit normally (the committed request state is
        the last *verified* rung, so the retry replays exactly the
        corrupted segment).

        The shadow recompute calls ``run_round_segment`` directly (not
        ``self._engine`` — chaos tests wrap the engine in a
        ``FaultInjector``; the oracle must stay clean) with the kernel
        tier retired, on the request's own input slice.
        """
        from repro.core.shufflesoftsort import (
            _tau_schedule,
            run_round_segment,
        )
        from repro.runtime.guardrails import (
            GuardrailMonitor,
            IntegrityViolation,
        )
        taus = _tau_schedule(cfg_use)
        bad = []
        off = 0
        for req, sel in zip(reqs, sels):
            nl = len(sel)
            sl = slice(off, off + nl)
            off += nl
            if nl == 0:         # pragma: no cover - defensive
                continue
            mon = req.monitor
            if mon is None or mon.policy is not req.guardrail:
                mon = req.monitor = GuardrailMonitor(
                    req.guardrail, context="serving",
                    dtype=cfg_use.compute_dtype)
            start = int(progress_in[sl].min())
            try:
                # Adaptive w rows must be finite before ctrl.observe —
                # the unguarded global sentinel is skipped for guarded
                # groups, so attribute it here, per slice.
                if w is not None and not np.isfinite(w[sl]).all():
                    mon._fail("finite",
                              "non-finite soft-sort keys in serving "
                              f"dispatch at round {start}",
                              round=start)
                oracle_l = oracle_o = None
                if mon.wants_shadow(start):
                    ocfg = dataclasses.replace(cfg_use, use_kernel=False)
                    if w is not None:
                        sh = run_round_segment(
                            xs_in[sl], orders_in[sl], keys_in[sl],
                            norms_in[sl], progress_in[sl], self.seg_len,
                            hw=hw, cfg=ocfg, regime=regime)
                    else:
                        sh = run_round_segment(
                            xs_in[sl], orders_in[sl], keys_in[sl],
                            norms_in[sl], progress_in[sl], self.seg_len,
                            hw=hw, cfg=ocfg)
                    oracle_l = np.asarray(sh[2], np.float32)
                    if mon.compare_orders():
                        oracle_o = np.asarray(sh[0])
                band = None
                if regime == "banded" and req.ctrl is not None:
                    band = req.ctrl.band
                mon.check_rung(
                    start=start,
                    losses=l[:, sl],
                    orders=o[sl],
                    n=req.x.shape[0],
                    keys_in=keys_in[sl], keys_out=k[sl],
                    seg_len=self.seg_len,
                    ws=None if w is None else w[sl],
                    tau=taus[np.asarray(progress_in[sl], np.int64)],
                    band=band,
                    oracle_losses=oracle_l, oracle_orders=oracle_o)
            except IntegrityViolation as e:
                bad.append((req, e))
        return bad

    def _integrity_failure(self, req: _SortRequest, exc):
        """Remediation for a probe failure on one request: record the
        structured incident, count a strike, and past the policy's
        ``heal_after`` budget consume a ``DivergencePolicy`` rung as a
        per-request config override (kernel→oracle, band widening,
        dtype promotion) — then re-queue the request from its last
        verified boundary through the normal retry path."""
        rec = exc.incident() if hasattr(exc, "incident") else {
            "probe": None, "message": str(exc)}
        rec["seq"] = int(req.seq)
        self.stats["integrity_violations"] += 1
        self.stats["integrity_incidents"].append(rec)
        self.events.append({"event": "integrity", "seq": req.seq,
                            "probe": getattr(exc, "probe", None),
                            "round": getattr(exc, "round", None)})
        req.strikes += 1
        if req.strikes > req.guardrail.heal_after:
            cfg_use = self._cfg_for(req)
            step = self.degrade.apply(cfg_use, exc)
            if step is not None:
                healed, note = step
                req.cfg_override = healed
                req.monitor = None      # dtype/config may have changed
                req.strikes = 0
                self.stats["self_heals"] += 1
                self.events.append({"event": "self_heal",
                                    "seq": req.seq, "action": note})
        self._on_failure([req], exc)

    def _post_rung(self, req: _SortRequest):
        """Rung-boundary bookkeeping: tournament cull, then finalize.

        Adaptive mode ranks every not-yet-culled restart (including
        early-stopped ones — they converged, they still compete) by its
        LAST-EXECUTED loss, and finalizes the request at the first
        boundary where no restart is still annealing — the adaptive
        early exit the ``adaptive_exits`` / ``rounds_saved`` counters
        measure.
        """
        from repro.core.shufflesoftsort import _tournament_cull
        s_k = len(req.alive)
        if req.progress in self._cull_edges and s_k > 1:
            # The brownout "culled" rung degrades the tournament to its
            # floor: keep only the current best restart at every
            # interior boundary.
            keep = (1 if req.brownout_cull else
                    max(1, int(np.ceil(s_k * (1.0 - self.cull_fraction)))))
            if keep < s_k:
                if req.ctrl is not None:
                    last = req.ctrl.executed[req.alive] - 1
                    final = req.losses[req.alive, last][None, :]
                else:
                    final = req.losses[req.alive, req.progress - 1][None, :]
                sel = _tournament_cull(final, keep)[0]
                if req.ctrl is not None:
                    kept = np.zeros(s_k, bool)
                    kept[sel] = True
                    req.ctrl.mark_culled(req.alive[~kept])
                    req.done_mask = req.done_mask[sel]
                req.alive = req.alive[sel]
                req.orders = req.orders[sel]
                req.keys = req.keys[sel]
                self.stats["culled"] += s_k - keep
                self.events.append({"event": "cull", "seq": req.seq,
                                    "kept": keep, "of": s_k})
        if req.ctrl is not None:
            if req.done_mask.all():
                last = req.ctrl.executed[req.alive] - 1
                final = req.losses[req.alive, last]
                win = int(np.argmin(final))
                order = req.orders[win]
                saved = self.cfg.rounds - int(
                    req.ctrl.executed[req.alive].max())
                if saved > 0:
                    self.stats["adaptive_exits"] += 1
                    self.stats["rounds_saved"] += saved
                    self.events.append(
                        {"event": "adaptive_exit", "seq": req.seq,
                         "round": self.cfg.rounds - saved,
                         "saved": saved})
                self._active.remove(req)
                self._resolve_ok(
                    req, (order, req.x[order], req.losses[req.alive[win]]))
            return
        if req.progress >= self.cfg.rounds:
            final = req.losses[req.alive, -1]
            win = int(np.argmin(final))
            order = req.orders[win]
            self._active.remove(req)
            self._resolve_ok(
                req, (order, req.x[order], req.losses[req.alive[win]]))

    def _on_failure(self, reqs: list[_SortRequest], exc: Exception):
        """TrainSupervisor-style restart semantics for a failed
        dispatch: each request re-queues from its last committed round
        boundary with exponential backoff, until its budget runs out."""
        now = time.monotonic()
        for req in reqs:
            req.attempts += 1
            self._active.remove(req)
            if req.attempts > self.retry.max_retries:
                self._resolve_exc(
                    req,
                    RequestFailed(
                        f"dispatch failed {req.attempts} times "
                        f"(budget {self.retry.max_retries}): {exc}"),
                    "failed")
                continue
            backoff = self.retry.backoff(req.attempts)
            req.eligible_at = now + backoff
            self.stats["retries"] += 1
            self.events.append({"event": "retry", "seq": req.seq,
                                "attempt": req.attempts,
                                "backoff_s": backoff,
                                "error": str(exc)})
            with self._cv:
                self._pending.append(req)
        # exception chains into RequestFailed via ``from`` semantics:
        for req in reqs:
            if req.future.done():
                exc_set = req.future.exception()
                if isinstance(exc_set, RequestFailed):
                    exc_set.__cause__ = exc

    def _record_timing(self, dt: float, instance_rounds: int):
        """Feed the straggler monitor (per instance-round, so batch and
        rung sizes don't masquerade as stragglers) and adapt the bucket
        cap: flag -> halve (reroute traffic into smaller batches),
        healthy streak -> restore toward max_batch."""
        flagged = self.straggler.record(
            self._dispatch_idx, dt / max(instance_rounds, 1))
        self._dispatch_idx += 1
        if flagged:
            self.stats["stragglers"] += 1
            self._bucket_cap = max(1, self._bucket_cap // 2)
            self._healthy_streak = 0
            self.events.append({"event": "straggler", "dt_s": dt,
                                "bucket_cap": self._bucket_cap})
        else:
            self._healthy_streak += 1
            if (self._healthy_streak >= self.straggler_recovery
                    and self._bucket_cap < self.max_batch):
                self._bucket_cap = min(self.max_batch,
                                       self._bucket_cap * 2)
                self._healthy_streak = 0


def _parse_band(value):
    """CLI ``--band`` -> ShuffleSoftSortConfig.band: "none" (or unset) =
    always dense, "auto" = tau-adaptive auto-sized band, an integer =
    explicit band half-width K."""
    if value is None or value == "none":
        return None
    if value == "auto":
        return "auto"
    return int(value)


def serve_sorts(args):
    """CLI driver: fire concurrent sort requests at a SortServer.
    CLI validation (grid divisibility, dtype/kernel coupling) lives in
    ``main()`` as argparse errors — survives ``python -O``, unlike the
    bare asserts it replaced."""
    from repro.core.metrics import mean_neighbor_distance
    from repro.core.shufflesoftsort import ShuffleSoftSortConfig
    from repro.launch.mesh import make_sort_mesh
    from repro.runtime.guardrails import GuardrailPolicy
    from repro.runtime.straggler import DeviceHealthMonitor

    guardrail = (None if args.guardrail == "off" else
                 GuardrailPolicy(mode=args.guardrail,
                                 shadow_rate=args.shadow_rate,
                                 seed=args.seed))
    brownout = BrownoutPolicy() if args.brownout else None
    device_health = (DeviceHealthMonitor(lost_after=args.device_health)
                     if args.device_health else None)
    hw = (args.sort_hw, args.sort_n // args.sort_hw)
    cfg = ShuffleSoftSortConfig(rounds=args.rounds,
                                chunk=min(256, args.sort_n),
                                use_kernel=args.use_kernel,
                                band=_parse_band(args.band),
                                compute_dtype=args.dtype,
                                schedule=args.schedule)
    mesh = make_sort_mesh(args.mesh_devices) if args.mesh_devices else None
    server = SortServer(hw, d=args.sort_d, cfg=cfg,
                        max_batch=args.max_batch, max_wait_ms=args.wait_ms,
                        n_restarts=args.restarts, mesh=mesh,
                        tournament_rungs=args.tournament_rungs,
                        cull_fraction=args.cull_fraction,
                        queue_depth=args.queue_depth,
                        sched_rungs=args.sched_rungs or None,
                        seed=args.seed, guardrail=guardrail,
                        brownout=brownout, device_health=device_health)
    rng = np.random.RandomState(0)
    xs = rng.rand(args.requests, args.sort_n, args.sort_d).astype(np.float32)

    t0 = time.time()
    futs = [server.submit(xs[i], key=jax.random.PRNGKey(i))
            for i in range(args.requests)]
    results = [f.result(timeout=600) for f in futs]
    wall = time.time() - t0
    server.close()

    improved = sum(
        mean_neighbor_distance(r[1], hw) < mean_neighbor_distance(x, hw)
        for r, x in zip(results, xs))
    sps = args.requests / max(wall, 1e-9)
    sizes = server.stats["batch_sizes"]
    lat = np.asarray(server.stats["latencies_ms"], np.float64)
    p50 = float(np.percentile(lat, 50)) if lat.size else 0.0
    p99 = float(np.percentile(lat, 99)) if lat.size else 0.0
    adaptive_note = ""
    if cfg.schedule == "adaptive":
        adaptive_note = (
            f"; adaptive: {server.stats['adaptive_exits']} early exits, "
            f"{server.stats['rounds_saved']} rounds saved")
    guard_note = ""
    if guardrail is not None:
        guard_note = (
            f"; guardrail {guardrail.mode}: "
            f"{server.stats['integrity_violations']} violations, "
            f"{server.stats['self_heals']} self-heals")
    elastic_note = ""
    deg = server.stats["degradations"]
    if brownout is not None or device_health is not None:
        deg_str = " ".join(f"{r}={deg[r]}" for r in _BROWNOUT_LADDER)
        elastic_note = (
            f"; elastic: {server.stats['evictions']} evictions, "
            f"{server.stats['reshards']} reshards, "
            f"{server.stats['device_returns']} returns; "
            f"degradations {deg_str}")
    print(f"served {args.requests} sort requests in {wall:.2f}s "
          f"({sps:.2f} sorts/s) across {server.stats['batches']} device "
          f"batches (sizes {sizes}); p50 {p50:.1f}ms p99 {p99:.1f}ms; "
          f"{improved}/{args.requests} layouts improved"
          f"{adaptive_note}{guard_note}{elastic_note}")
    return {"sorts_per_s": sps, "batches": server.stats["batches"],
            "improved": int(improved), "p50_ms": p50, "p99_ms": p99,
            "adaptive_exits": server.stats["adaptive_exits"],
            "rounds_saved": server.stats["rounds_saved"],
            "integrity_violations": server.stats["integrity_violations"],
            "self_heals": server.stats["self_heals"],
            "completed": server.stats["completed"],
            "failed": server.stats["failed"],
            "retries": server.stats["retries"],
            "evictions": server.stats["evictions"],
            "reshards": server.stats["reshards"],
            "device_returns": server.stats["device_returns"],
            "degradations": dict(deg)}


# --------------------------------------------------------------------------
# LM decode serving.
# --------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", choices=("lm", "sort"), default="lm")
    ap.add_argument("--arch", choices=list_archs(), default="qwen1.5-0.5b")
    ap.add_argument("--preset", choices=PRESETS, default="tiny")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--prompt-len", type=int, default=24)
    # sort-workload knobs
    ap.add_argument("--sort-n", type=int, default=256)
    ap.add_argument("--sort-hw", type=int, default=16,
                    help="grid height; width = sort-n / sort-hw")
    ap.add_argument("--sort-d", type=int, default=3)
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--restarts", type=int, default=1)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--wait-ms", type=float, default=5.0)
    ap.add_argument("--mesh-devices", type=int, default=0,
                    help="shard coalesced batches over this many devices "
                         "(0 = single-device vmap engine)")
    ap.add_argument("--tournament-rungs", type=int, default=1,
                    help=">1 runs restarts as a successive-halving "
                         "tournament (needs --restarts > 1)")
    ap.add_argument("--cull-fraction", type=float, default=0.5)
    ap.add_argument("--use-kernel", action="store_true",
                    help="run the SoftSort apply (fwd+bwd) through the "
                         "fused Pallas kernel tier instead of chunked jnp")
    ap.add_argument("--dtype", choices=("float32", "bfloat16"),
                    default="float32",
                    help="kernel-tier compute precision (with "
                         "--use-kernel): bfloat16 halves the kernels' "
                         "payload HBM traffic; keys, stats, and Adam "
                         "math stay f32 (EXPERIMENTS.md §Perf)")
    ap.add_argument("--band", default=None,
                    help="banded O(N*K) apply: an integer half-width K, "
                         "'auto' to size it from N and the tau schedule, "
                         "or 'none' (default) for the dense apply; hot "
                         "early rounds stay dense until the tail bound "
                         "clears (EXPERIMENTS.md §Perf)")
    ap.add_argument("--queue-depth", type=int, default=64,
                    help="admission-control bound: submits past this many "
                         "waiting requests raise QueueFull")
    ap.add_argument("--sched-rungs", type=int, default=0,
                    help="scheduler preemption quantum: split the round "
                         "schedule into this many rungs (0 = auto)")
    ap.add_argument("--schedule", choices=("fixed", "adaptive"),
                    default="fixed",
                    help="'adaptive' runs the plateau-driven controller: "
                         "requests leave the anneal at the first "
                         "converged rung boundary (EXPERIMENTS.md "
                         "§Adaptive)")
    ap.add_argument("--seed", type=int, default=0,
                    help="server-owned PRNG seed for requests submitted "
                         "without a key (reproducible serving runs)")
    ap.add_argument("--guardrail", choices=("off", "invariants", "shadow"),
                    default="off",
                    help="permutation-integrity probes at every rung "
                         "boundary: 'invariants' runs the free host-side "
                         "checks (valid permutation, loss sanity, PRNG "
                         "key chain), 'shadow' adds sampled pure-jnp "
                         "oracle recompute (EXPERIMENTS.md §Robustness, "
                         "'Silent corruption')")
    ap.add_argument("--shadow-rate", type=float, default=None,
                    help="fraction of rungs to shadow-recompute under "
                         "--guardrail shadow (default 1/32; overhead "
                         "scales with the rate)")
    ap.add_argument("--brownout", action="store_true",
                    help="arm the overload brownout ladder: under "
                         "capacity loss or queue pressure, degrade new "
                         "requests culled -> adaptive -> banded -> bf16 "
                         "before shedding (EXPERIMENTS.md §Robustness, "
                         "'Elastic capacity')")
    ap.add_argument("--device-health", type=int, default=0,
                    metavar="STRIKES",
                    help="evict a device after this many DeviceLost "
                         "dispatch failures and re-shard the mesh over "
                         "the survivors at the next rung boundary "
                         "(0 = off; needs --mesh-devices)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.workload == "sort":
        # CLI validation as argparse errors (not asserts: those vanish
        # under ``python -O`` and print bare tracebacks).
        if args.sort_hw <= 0 or args.sort_n % args.sort_hw != 0:
            ap.error(f"--sort-hw {args.sort_hw} must be a positive "
                     f"divisor of --sort-n {args.sort_n} (grid height)")
        # compute_dtype is a kernel-tier knob; without --use-kernel the
        # chunked-jnp apply runs f32 regardless, so a bare --dtype
        # bfloat16 would silently do nothing — refuse instead.
        if args.dtype != "float32" and not args.use_kernel:
            ap.error("--dtype bfloat16 requires --use-kernel (the jnp "
                     "apply tier has no bf16 mode)")
        # --shadow-rate only modulates the shadow tier; a rate with the
        # probes off (or invariants-only) would silently do nothing.
        if args.shadow_rate is not None and args.guardrail != "shadow":
            ap.error("--shadow-rate requires --guardrail shadow (the "
                     f"'{args.guardrail}' tier runs no shadow "
                     "recompute)")
        if args.shadow_rate is not None and not (
                0.0 <= args.shadow_rate <= 1.0):
            ap.error(f"--shadow-rate {args.shadow_rate} must be in "
                     "[0, 1]")
        if args.shadow_rate is None:
            args.shadow_rate = 0.03125
        if args.device_health < 0:
            ap.error(f"--device-health {args.device_health} must be "
                     ">= 0 (strike budget; 0 disables)")
        if args.device_health and not args.mesh_devices:
            ap.error("--device-health needs --mesh-devices (eviction "
                     "re-shards a device mesh; the vmap engine has no "
                     "devices to lose)")
        return serve_sorts(args)

    cfg = reduced_config(get_config(args.arch), **PRESETS[args.preset])
    params, _ = init_model(jax.random.PRNGKey(0), cfg)

    rng = np.random.RandomState(0)
    lens = rng.randint(4, args.prompt_len + 1, size=args.requests)
    max_len = int(lens.max())
    total = max_len + args.max_new
    prompts = rng.randint(0, cfg.vocab_size,
                          size=(args.requests, max_len)).astype(np.int32)

    ctx = None
    if cfg.family == "vlm":
        ctx = jnp.zeros((args.requests, cfg.vision_tokens, cfg.vision_d))
    if cfg.is_encdec:
        ctx = jnp.zeros((args.requests, cfg.audio_frames, cfg.d_model))

    jit_prefill = jax.jit(lambda p, t, c: prefill(p, cfg, t, c))
    jit_decode = jax.jit(
        lambda p, t, caches, pos, c: decode_step(p, cfg, t, caches, pos, c))

    t0 = time.time()
    logits, caches = jit_prefill(params, jnp.asarray(prompts), ctx)
    # pad caches to the full decode horizon
    def pad_cache(a):
        if a.ndim >= 4 and a.shape[2] == max_len:
            pad = [(0, 0)] * a.ndim
            pad[2] = (0, args.max_new)
            return jnp.pad(a, pad)
        return a
    caches = jax.tree.map(pad_cache, caches)
    prefill_s = time.time() - t0

    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)     # (B, 1)
    out_tokens = [np.asarray(tok)]
    t0 = time.time()
    for i in range(args.max_new - 1):
        tok_logits, caches = jit_decode(params, tok, caches,
                                        jnp.int32(max_len + i), ctx)
        tok = jnp.argmax(tok_logits, axis=-1).astype(jnp.int32)
        out_tokens.append(np.asarray(tok))
    decode_s = time.time() - t0
    gen = np.concatenate(out_tokens, axis=1)

    tps = args.requests * (args.max_new - 1) / max(decode_s, 1e-9)
    print(f"served {args.requests} requests (prompt<= {max_len}): "
          f"prefill {prefill_s:.2f}s, decode {decode_s:.2f}s "
          f"({tps:.1f} tok/s), output shape {gen.shape}")
    assert gen.shape == (args.requests, args.max_new)
    assert np.all(gen >= 0) and np.all(gen < cfg.vocab_size)
    return {"tok_per_s": tps, "prefill_s": prefill_s}


if __name__ == "__main__":
    main()
