"""Repeated whole-anneal calls of ``shuffle_soft_sort_batched``.

Each call sorts B collections, S restarts each, as one scanned device
program (sharded over a 1-D mesh when the mix asks for chips), and
returns its results on the host.  The collections come from a pool
made in set-up; every call draws fresh keys.

Traffic parameters: ``side``, ``collections`` (B), ``restarts`` (S),
``mesh_devices`` (0: no mesh), ``pool``, ``sampled_calls``,
``check_rounds``.
"""
from __future__ import annotations

import time

import numpy as np

import cell
import check
from traffic import catalog


class Driver:
    seg_len = 1

    def __init__(self, spec, seed: int, device: dict):
        self.spec = spec
        self.seed = int(seed)
        self.device = device
        self.traffic = spec.traffic
        self.sort = spec.config["sort"]
        self.data = spec.config["data"]
        self.rng = np.random.default_rng([self.seed, 3])
        self.log = cell.CallLog()
        self.samples = cell.Reservoir(self.rng, self.traffic["sampled_calls"])

    def setup(self, seconds: float) -> None:
        from repro.core.shufflesoftsort import shuffle_soft_sort_batched
        from repro.launch.mesh import make_sort_mesh

        self.cfg = cell.sort_config(self.spec)
        self._batched = shuffle_soft_sort_batched
        side = int(self.traffic["side"])
        self.hw = (side, side)
        b, d = int(self.traffic["collections"]), int(self.data["d"])
        self.pool = [np.stack([catalog.catalog(side * side, d,
                                               self.data["clusters"],
                                               self.data["spread"], self.rng)
                               for _ in range(b)])
                     for _ in range(int(self.traffic["pool"]))]
        chips = int(self.traffic["mesh_devices"])
        self.mesh = (make_sort_mesh(chips, devices=self.device["devices"])
                     if chips else None)
        self.results = []
        self._call()               # compiles; not in the window

    def _keys(self) -> np.ndarray:
        bs = int(self.traffic["collections"]) * int(self.traffic["restarts"])
        return self.rng.integers(0, 2**32, size=(bs, 2), dtype=np.uint32)

    def _call(self):
        import reference
        idx = self.log.next_index()
        xs = self.pool[idx % len(self.pool)]
        keys = self._keys()
        with cell.span("bench.engine_call", call=idx):
            t0 = time.perf_counter()
            res = self._batched(xs, self.hw, self.cfg,
                                n_restarts=int(self.traffic["restarts"]),
                                keys=keys, mesh=self.mesh)
            t1 = time.perf_counter()
        b, n, d = xs.shape
        slots = b * int(self.traffic["restarts"])
        switch = reference.banded_from_round(self.sort, n)
        k = reference.band_width(self.sort, n)
        flops, nbytes = 0.0, 0.0
        banded = k if k < n - 1 else None
        for rounds, kk in ((switch, None), (self.cfg.rounds - switch, banded)):
            if rounds > 0:
                f, by = cell.apply_work(n, kk, d,
                                        slots, rounds, self.cfg.inner_steps,
                                        self.cfg.compute_dtype)
                flops, nbytes = flops + f, nbytes + by
        self.log.calls.append(cell.Call(idx, t0, t1, flops, nbytes, slots))
        self.results.append((idx, keys, res))

    @staticmethod
    def trace_stretch(seconds: float, length: float) -> tuple[float, float]:
        """Trace the middle of the window, between calls."""
        lo = (seconds - length) / 2
        return lo, lo + length

    def run(self, seconds: float, tracer: cell.Tracer) -> None:
        self.first = len(self.results)
        self.t_start = time.perf_counter()
        while True:
            now = time.perf_counter() - self.t_start
            tracer.poll(now)
            if now >= seconds:
                break
            self._call()
        tracer.finish()
        self.t_close = time.perf_counter()
        for i, _ in enumerate(self.results[self.first:]):
            self.samples.offer(lambda i=i: self.first + i)

    def end_to_end(self) -> dict:
        calls = self.log.calls[self.first:]
        sorts = sum(c.slots for c in calls) / int(self.traffic["restarts"])
        span = calls[-1].t1 - self.t_start
        return {"sorts_per_h": 3600.0 * sorts / span}

    def counts(self) -> dict:
        n = len(self.results) - self.first
        return {"attempted": n * int(self.traffic["collections"]),
                "failed": 0}

    def counters(self) -> dict:
        return {"window_s": self.t_close - self.t_start}

    def calls(self) -> list:
        return self.log.calls

    def release(self) -> None:
        self.mesh = None

    def readings(self, ref) -> dict:
        bad = 0
        for idx, _, res in self.results[self.first:]:
            xs = self.pool[idx % len(self.pool)]
            for b in range(xs.shape[0]):
                bad += check.answer_bad(xs[b], res.order[b], res.sorted[b],
                                        res.all_losses[b])
        j = int(self.traffic["check_rounds"])
        s = int(self.traffic["restarts"])
        n = self.pool[0].shape[1]
        first, final = [], []
        for r in sorted(self.samples.items):
            idx, keys, res = self.results[r]
            xs = self.pool[idx % len(self.pool)]
            for b in range(xs.shape[0]):
                for si in range(s):
                    key = keys[b * s + si]
                    first.append((xs[b], np.arange(n, dtype=np.int32), key,
                                  0, res.all_losses[b, si, :j]))
                    final.append((xs[b], res.all_orders[b, si], key,
                                  res.all_losses[b, si, -1]))
        return {"bad_answers": float(bad),
                "round_loss_gap": check.round_loss_gap(
                    check.replay_gaps(ref, first)),
                "final_layout_gap": max(check.final_layout_gaps(ref, final),
                                        default=None)}
