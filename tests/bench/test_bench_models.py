"""RGCN, found by name from ``models/rgcn.py``, reads as it did when the
shared code held it: the same step FLOPs and bytes at the cells' shapes,
the same reference losses."""
import numpy as np
import pytest

from bench_small import graph_of_counts, reference_run, small_cell
from benchmarks.chip import harness, spec

# step_work at the cells' shapes and the reference's first loss
# (``bench_small.reference_run``), as the shared code computed them
# before RGCN moved into its own file
PINNED = {
    "mag-nc.train": ({"flops": 261363335168.0, "bytes": 1814019628.0},
                     7.383634567260742),
    "citation2-lp.train": ({"flops": 258757361664.0, "bytes": 712084480.0},
                           4.993190288543701),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_rgcn_step_work_is_pinned(name):
    cell = spec.find_cell(name)
    assert spec.gnn_model(cell).__file__ == str(
        spec.HERE / "models" / "rgcn.py")
    assert harness.step_work(cell, graph_of_counts(cell)) == PINNED[name][0]


@pytest.mark.parametrize("name", sorted(PINNED))
def test_rgcn_reference_loss_is_pinned(name):
    loss = reference_run(small_cell(name))["losses"][0]
    np.testing.assert_allclose(loss, PINNED[name][1], rtol=1e-6)
