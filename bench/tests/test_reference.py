"""The reference takes each step's gradient over blocks of rows so that
it fits on one chip at the cells' batches; the blocks must give what the
whole batch gives."""

import pytest

from train_cell import reference


def test_blocks_of_rows_give_the_whole_batch(tiny):
    assert tiny.traffic["batch"] == 2
    whole = reference(tiny, 2**31 + 7, 2, block_rows=2)
    rows = reference(tiny, 2**31 + 7, 2, block_rows=1)
    assert rows["losses"] == pytest.approx(whole["losses"], rel=1e-5)
    for kind in ("grad", "update"):
        for leaf, n in whole[kind].items():
            assert rows[kind][leaf] == pytest.approx(n, rel=1e-4, abs=1e-9), \
                (kind, leaf)
