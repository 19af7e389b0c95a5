"""The numbers that decide ``correct``, computed against the reference.

* ``bad_answers``: answers that are not a permutation of 0..N-1, whose
  sorted rows are not ``x[order]``, or whose loss trace is not finite.
  Exact; the limit is 0.
* ``round_loss_gap``: the program's loss of each round against the
  reference's, replayed from the order and key the program held at the
  round where a sampled engine call started, for the first rounds of
  that call.  The reference continues from its own orders, so a round
  whose committed order the program got wrong (or never committed)
  shows in the next round's loss.  Each sampled instance reads its
  largest relative gap; the number is the median over the instances.
  A single instance can read up to ~3e-4 on a sound run: a near-tie in
  a committed order that the program and the reference break the other
  way swaps two rows, and the rounds after it differ by that swap.  The
  median is steady across seeds; a lower precision moves every
  instance.
* ``final_layout_gap``: the loss of the program's last round against
  the reference's round replayed on the program's final order with the
  same key.  At the end of an anneal a round barely moves the layout,
  so a final order that is not the one the anneal reached (unsorted,
  another instance's, altered) shows here.  Largest relative gap over
  the answers, so one wrong answer fails it.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp


def answer_bad(x: np.ndarray, order, sorted_rows, losses) -> bool:
    order = np.asarray(order)
    n = x.shape[0]
    if order.shape != (n,) or not np.array_equal(np.sort(order),
                                                 np.arange(n)):
        return True
    if sorted_rows is not None and not np.array_equal(
            np.asarray(sorted_rows), x[order]):
        return True
    return not np.isfinite(np.asarray(losses)).all()


def chained_key(key, rounds: int) -> np.ndarray:
    """The chained key an instance holds after ``rounds`` rounds."""
    def step(k, _):
        return jax.random.split(k)[0], None
    k, _ = jax.lax.scan(step, jnp.asarray(key, jnp.uint32), None,
                        length=rounds)
    return np.asarray(k)


def _rel(a, b) -> np.ndarray:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return np.abs(a - b) / np.maximum(np.abs(b), 1e-30)


# Every gap behind the last readings, per item and round, for
# bench/calibrate.py to record beside the readings.
DETAIL: dict = {}


def replay_gaps(ref, items) -> list:
    """``items``: (x, order, key, start, program losses of rounds
    start.. start+J-1), with ``order``/``key`` as the program held them
    before round ``start``.  Returns each item's relative gap per
    replayed round."""
    gaps = []
    for x, order, key, start, prog in items:
        prog = np.asarray(prog)
        _, ref_l = ref.rounds(x, order, key, int(start), len(prog))
        gaps.append(_rel(prog, ref_l).tolist())
    DETAIL["round_loss_gap"] = gaps
    return gaps


def round_loss_gap(gaps: list) -> float | None:
    """Median over the items of each item's largest gap."""
    return float(np.median([max(g) for g in gaps])) if gaps else None


def final_layout_gaps(ref, items) -> list:
    """``items``: (x, final order, instance key at round 0, program loss
    of the last round).  Returns each answer's relative gap."""
    last = int(ref.sort["rounds"]) - 1
    gaps = []
    for x, order, key0, prog_last in items:
        _, ref_l = ref.rounds(x, order, chained_key(key0, last), last, 1)
        gaps.append(float(_rel(prog_last, ref_l[0])))
    DETAIL["final_layout_gap"] = gaps
    return gaps
