"""Faults planted under the timed path, to show that ``correct`` reads
false when the engine is broken.  Each patches the engine functions the
program looks up at call time (its scanned engines: homogeneous, ragged
and their mesh-sharded forms), so the harness, the scheduler and the
public entry points run unchanged on top.

* ``state_unchanged``: the engine returns the orders it was given.
* ``half_batch``: the engine keeps the first half of the instances'
  results and copies them over the second half.
* ``exchange_left_out``: on a mesh, only the first chip's shard comes
  back; its rows stand in for every other chip's.
* ``answer_altered``: instance 0's order is replaced by another
  permutation where the engine produces it.

Used by ``bench/tests/test_correctness.py`` and ``bench/calibrate.py``.
"""
from __future__ import annotations

import contextlib

import numpy as np

import jax
import jax.numpy as jnp

ENGINES = ("_run_rounds_batched", "_run_rounds_sharded",
           "_run_rounds_ragged", "_run_rounds_ragged_sharded")
KINDS = ("state_unchanged", "half_batch", "exchange_left_out",
         "answer_altered")


def _broken(kind: str, orig, sharded: bool):
    def run(xs, orders, keys, taus, norms, **kw):
        o_in = jnp.array(orders, copy=True)
        o, k, l = orig(xs, orders, keys, taus, norms, **kw)
        bs = o.shape[0]
        if kind == "state_unchanged":
            o = o_in
        elif kind == "half_batch" and bs >= 2:
            src = np.arange(bs) % -(-bs // 2)
            o, k, l = o[src], k[src], l[:, src]
        elif kind == "exchange_left_out" and sharded:
            per = bs // kw["mesh"].shape["data"]
            src = np.arange(bs) % per
            o, k, l = o[src], k[src], l[:, src]
        elif kind == "answer_altered":
            n = o.shape[1]
            alt = jax.random.permutation(jax.random.PRNGKey(7), n)
            o = o.at[0].set(o[0][alt])
        return o, k, l
    return run


@contextlib.contextmanager
def planted(kind: str | None):
    """Run the body with fault ``kind`` planted (None: no fault)."""
    if kind is None:
        yield
        return
    if kind not in KINDS:
        raise ValueError(f"unknown fault {kind!r}")
    from repro.core import shufflesoftsort as sss
    saved = {name: getattr(sss, name) for name in ENGINES}
    try:
        for name, orig in saved.items():
            setattr(sss, name, _broken(kind, orig, "sharded" in name))
        yield
    finally:
        for name, orig in saved.items():
            setattr(sss, name, orig)
