"""The checkpoint cell's comparison on the CPU, at a small size: a sound
run reads ``correct`` true, and each of its faults, planted under the
timed path, reads false.  The driver, the reference and the limits are
the cell's own; the tensors shrink to 64 x 256 (16x16 grids, K 64: the
banded kernels in interpret mode), 2 per group, 32 rounds."""
import copy

import pytest

import cell
import run

WORKLOAD = "command_a_plus_experts.ckpt"
SECONDS = 4.0


def small_spec():
    spec = copy.deepcopy(cell.load_spec(WORKLOAD))
    spec.config["sort"]["rounds"] = 32
    spec.config["data"].update(rows=64, cols=256, rank=8)
    spec.traffic.update(tensors=2, pool=2, sampled_calls=1)
    return spec


def test_sound_run_correct():
    r = run.run_cell(WORKLOAD, 2**31 + 111, SECONDS, False, chip=False,
                     spec=small_spec())
    assert r["correct"], (r["compared"], r["readings"])
    assert r["metrics"]["sorts_per_h"]["value"] > 0


@pytest.mark.parametrize("fault", cell.load_spec(WORKLOAD).limits["faults"])
def test_fault_reads_incorrect(fault):
    r = run.run_cell(WORKLOAD, 2**31 + 222, SECONDS, False, chip=False,
                     spec=small_spec(), fault=fault)
    assert not r["correct"], (r["compared"], r["readings"])


def test_altered_blob_is_a_bad_answer(monkeypatch):
    """A blob whose payload is not the tensor in its order counts."""
    from repro.runtime import sog_compress
    encode = sog_compress._encode

    def off_by_one(q):
        q = q.copy()
        q[0, 0] += 1
        return encode(q)

    monkeypatch.setattr(sog_compress, "_encode", off_by_one)
    r = run.run_cell(WORKLOAD, 2**31 + 333, SECONDS, False, chip=False,
                     spec=small_spec())
    assert r["readings"]["bad_answers"] > 0 and not r["correct"]
