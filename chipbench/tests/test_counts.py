"""FLOP and byte counts against hand-worked values."""

import pytest

from chipbench.counts import rbpf as rbpf_counts


@pytest.mark.parametrize("n, blocks, nbytes", [
    # n 10, 125 blocks, record 6: state 2*10*7*4 = 560; weights 2*10*4 = 80;
    # append 10*6*4 = 240; tables 2*10*125*4 = 10000 -> 10880 B
    (10, 125, 10880),
    # the cell's N 2048 over 125 blocks: 114688 + 16384 + 49152 + 2048000
    (2048, 125, 2228224),
])
def test_generation_work_by_hand(n, blocks, nbytes):
    flops, got = rbpf_counts.generation_work(n, blocks, 6)
    assert got == nbytes
    assert flops == pytest.approx(n * rbpf_counts.FLOPS_PER_PARTICLE)
