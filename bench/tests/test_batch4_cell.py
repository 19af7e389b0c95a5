"""photo_grid.batch4's own files on the CPU's four host devices, at the
small size of ``small.py``: a sound run reads ``correct`` true, and one
chip's shard standing in for every other chip's reads false."""
import pytest

import run
import small

WORKLOAD = "photo_grid.batch4"


@pytest.mark.parametrize("fault", [None, "exchange_left_out"])
def test_batch4_small(fault):
    spec = small.small_spec(WORKLOAD)
    spec.traffic.update(collections=4)
    r = run.run_cell(WORKLOAD, 2**31 + 505, 4.0, False, chip=False,
                     spec=spec, fault=fault)
    assert r["correct"] == (fault is None), (r["compared"], r["readings"])
