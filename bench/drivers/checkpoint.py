"""Repeated ``compress_group`` calls: a checkpoint compression job.

Each call compresses one group of G same-shape tensors (D, F), one
projection of one layer's local experts: one ``shuffle_soft_sort_batched``
call sorts the G on their whole columns, then each sorted tensor is
int8-quantized, delta-coded and deflated on the host.  The caller waits
for the blobs, so the encode is timed with the sort, and the loop is
closed: the next group starts when the last one's blobs are out.  The
groups come from a pool made in set-up and are cycled; every call draws
fresh keys.

Traffic parameters: ``tensors`` (G), ``restarts`` (1: the codec sorts
each tensor once), ``pool``, ``sampled_calls``, ``check_rounds``.
"""
from __future__ import annotations

import concurrent.futures
import os
import time
import zlib

import numpy as np

import cell
import check
from traffic import experts


def _decodes_bad(x: np.ndarray, order: np.ndarray, blob: dict) -> bool:
    """Whether ``blob`` fails to decode to the int8 quantization of the
    rows ``x[order]``: max-abs scale over 127, rounded, clipped to
    +-127, delta-coded along the rows mod 256 and deflated."""
    rows = x[order]
    scale = float(np.max(np.abs(rows))) / 127.0 + 1e-12
    q = np.clip(np.round(rows / scale), -127, 127).astype(np.int8)
    raw = np.frombuffer(zlib.decompress(blob["payload"]), np.int8)
    if raw.size != q.size or blob["scale"] != scale:
        return True
    got = np.cumsum(raw.reshape(q.shape).astype(np.int32), axis=0)
    return not np.array_equal(got.astype(np.int8), q)


class Driver:
    seg_len = 1

    def __init__(self, spec, seed: int, device: dict):
        self.spec = spec
        self.seed = int(seed)
        self.traffic = spec.traffic
        self.sort = spec.config["sort"]
        self.data = spec.config["data"]
        self.rng = np.random.default_rng([self.seed, 5])
        self.log = cell.CallLog()
        self.samples = cell.Reservoir(self.rng, self.traffic["sampled_calls"])

    def setup(self, seconds: float) -> None:
        from repro.runtime.sog_compress import _grid_hw, compress_group

        self.cfg = cell.sort_config(self.spec)
        self._compress = compress_group
        rows, cols = int(self.data["rows"]), int(self.data["cols"])
        hw = _grid_hw(cols)
        assert hw[0] * hw[1] == cols == int(round(cols ** 0.5)) ** 2, hw
        g = int(self.traffic["tensors"])
        self.pool = [[experts.low_rank(rows, cols, int(self.data["rank"]),
                                       float(self.data["noise"]),
                                       float(self.data["std"]), self.rng)
                      for _ in range(g)]
                     for _ in range(int(self.traffic["pool"]))]
        self.results = []
        self._call()               # compiles; not in the window

    def _call(self):
        import reference
        idx = self.log.next_index()
        ws = self.pool[idx % len(self.pool)]
        keys = self.rng.integers(0, 2**32, size=(len(ws), 2),
                                 dtype=np.uint32)
        with cell.span("bench.engine_call", call=idx):
            t0 = time.perf_counter()
            blobs = self._compress(ws, cfg=self.cfg, keys=keys)
            t1 = time.perf_counter()
        d, n = ws[0].shape
        switch = reference.banded_from_round(self.sort, n)
        k = reference.band_width(self.sort, n)
        banded = k if k < n - 1 else None
        flops, nbytes = 0.0, 0.0
        for rounds, kk in ((switch, None), (self.cfg.rounds - switch, banded)):
            if rounds > 0:
                f, by = cell.apply_work(n, kk, d, len(ws), rounds,
                                        self.cfg.inner_steps,
                                        self.cfg.compute_dtype)
                flops, nbytes = flops + f, nbytes + by
        self.log.calls.append(cell.Call(idx, t0, t1, flops, nbytes, len(ws)))
        self.results.append((idx, keys, blobs))

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
        sorts = sum(c.slots for c in calls)
        span = calls[-1].t1 - self.t_start
        return {"sorts_per_h": 3600.0 * sorts / span}

    def counts(self) -> dict:
        n = len(self.results) - self.first
        return {"attempted": n * int(self.traffic["tensors"]), "failed": 0}

    def counters(self) -> dict:
        return {"window_s": self.t_close - self.t_start}

    def calls(self) -> list:
        return self.log.calls

    def release(self) -> None:
        pass

    def readings(self, ref) -> dict:
        answers = [(w.T, blob) for idx, _, blobs in self.results[self.first:]
                   for w, blob in zip(self.pool[idx % len(self.pool)], blobs)]

        def bad(item):
            x, blob = item
            order = np.asarray(blob["perm"])
            return (check.answer_bad(x, order, None, blob["losses"])
                    or _decodes_bad(x, order, blob))

        with concurrent.futures.ThreadPoolExecutor(os.cpu_count()) as pool:
            n_bad = sum(pool.map(bad, answers))
        j = int(self.traffic["check_rounds"])
        n = self.pool[0][0].shape[1]
        first, final = [], []
        for r in sorted(self.samples.items):
            idx, keys, blobs = self.results[r]
            for w, key, blob in zip(self.pool[idx % len(self.pool)], keys,
                                    blobs):
                first.append((w.T, np.arange(n, dtype=np.int32), key, 0,
                              blob["losses"][:j]))
                final.append((w.T, blob["perm"], key, blob["losses"][-1]))
        return {"bad_answers": float(n_bad),
                "round_loss_gap": check.round_loss_gap(
                    check.replay_gaps(ref, first)),
                "final_layout_gap": max(check.final_layout_gaps(ref, final),
                                        default=None)}
