"""A run with its timed path broken underneath reads `correct` false: the
four-chip mixes, on four forced CPU devices. Their cells wait for chip
time (`benchkit.WAITING`): the compressed two_phase mix and the exact-mean
mix. See `faultkit` for the faults."""
import pytest

from faultkit import check_faults


@pytest.mark.parametrize("cell", ["dcgan32.q8.b64.w4"])
def test_four_chip_faults_read_not_correct(cell):
    check_faults(cell, 4)


def test_exact_mix_faults_read_not_correct():
    check_faults("dcgan32.exact.b64.w4", 4)
