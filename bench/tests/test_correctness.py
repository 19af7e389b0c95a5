"""The comparison that decides ``correct``.

On the CPU, at a small size: a sound run of every cell is correct, and
each fault the cell can have, planted under the timed path with the
rest of the run unchanged, reads ``correct`` false.  The harness's look
for a chip is skipped (``chip=False``); everything else is the cell's
own: driver, reference, limits.

On a TPU, at the cell's own size: the control (the reference at the
next precision below, in the program's place) fails the cell's limits.
On the CPU the three-pass products of the control agree with float32
to rounding (the readings in PERF.md section 6), so there the control
test does not run.
"""
import jax
import pytest

import cell
import run
import small

WORKLOADS = ("photo_grid.batch",)
SECONDS = 4.0


def _faults():
    return [(w, kind) for w in WORKLOADS
            for kind in cell.load_spec(w).limits["faults"]]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_sound_run_correct(workload):
    r = run.run_cell(workload, 2**31 + 101, SECONDS, False, chip=False,
                     spec=small.small_spec(workload))
    assert r["correct"], r["compared"]


@pytest.mark.parametrize("workload,fault", _faults())
def test_fault_reads_incorrect(workload, fault):
    r = run.run_cell(workload, 2**31 + 202, SECONDS, False, chip=False,
                     spec=small.small_spec(workload), fault=fault)
    assert not r["correct"], (r["compared"], r["readings"])


@pytest.mark.parametrize("fault", [None, "exchange_left_out"])
def test_batch_on_a_mesh(fault):
    """The batch driver's mesh path (``mesh_devices`` in a traffic file)
    on four host devices: sound, and with one chip's shard standing in
    for every other chip's."""
    spec = small.small_spec("photo_grid.batch")
    spec.traffic.update(mesh_devices=4, collections=4)
    spec.workload = dict(spec.workload, chips=4)
    r = run.run_cell("photo_grid.batch", 2**31 + 404, SECONDS, False,
                     chip=False, spec=spec, fault=fault)
    assert r["correct"] == (fault is None), (r["compared"], r["readings"])


@pytest.mark.parametrize("workload", WORKLOADS)
def test_control_fails_on_chip(workload):
    spec = cell.load_spec(workload)
    if jax.default_backend() != "tpu" or jax.device_count() < spec.chips:
        pytest.skip("the control is read on a TPU, at the cell's size")
    r = run.run_cell(workload, 2**31 + 303, 10.0, False, control=True)
    assert r["correct"], r["compared"]
    ok, compared = cell.compare(r["control_readings"], spec.limits)
    assert not ok, compared
