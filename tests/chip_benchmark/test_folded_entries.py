"""The fold of the 41 `ecomm.`, `simprod.` and `churn.` copies of the
quantities every cell has left `per_layer`, and each cell joined the shared
entry that the same reader file serves (`manifest_rules.FOLDED`). One case
a folded name: `layer_metrics.read` takes the same file for the old name as
for the shared one, the shared entry lists the cell in the repository's
manifest and in the grown one, and on the cell's hand-made run, as its
control test builds it, the two names read the same value."""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHIP = os.path.join(ROOT, "benchmarks", "chip")
sys.path[:0] = [CHIP, ROOT, os.path.dirname(os.path.abspath(__file__))]

import layer_metrics  # noqa: E402
import made_up  # noqa: E402
import manifest_rules as rules  # noqa: E402
import test_control_reference_churn as churn  # noqa: E402
import test_control_reference_ecomm as ecomm  # noqa: E402
import test_control_reference_simprod as simprod  # noqa: E402

BOTH = made_up.manifests(ROOT)
HAND_MADE_RUN = {
    ecomm.CELL: ecomm.hand_made_run,
    simprod.CELL: simprod.hand_made_run,
    churn.CELL: churn.hand_made_run,
}


@pytest.mark.parametrize("folded,cell", [
    pytest.param(name, cell, id=name)
    for cell, names in rules.FOLDED.items() for name in names
])
def test_a_folded_name_reads_what_it_read_before(folded, cell):
    shared = rules.shared_name(folded)
    path = rules.reader_file(BOTH["tree"], ROOT, folded)
    assert path and path == rules.reader_file(BOTH["tree"], ROOT, shared)
    for which, bench in BOTH.items():
        listed = {m["name"]: m for m in bench["per_layer"]}
        assert folded not in listed, which
        assert cell in listed[shared]["workloads"], which
    run = HAND_MADE_RUN[cell]()
    assert layer_metrics.read(folded, run) == layer_metrics.read(shared, run)
