"""A run with its timed path broken underneath reads `correct` false: the
one-chip cells. See `faultkit` for the faults."""
import pytest

from faultkit import cells, check_faults


@pytest.mark.parametrize("cell", cells(1))
def test_one_chip_faults_read_not_correct(cell):
    check_faults(cell, 1)
