"""The prefill warm-up reaches every padded prefill shape that groups of
the cell's prompt lengths can make."""
import numpy as np
import pytest

from bench import harness
from repro.core.backend import bucket


def _key(lens, page, slots):
    pages = sum(-(-min(n, slots) // page) for n in lens)
    return (bucket(len(lens), 1),
            min(bucket(max(lens), 8), slots),
            bucket(pages, 1))


@pytest.mark.parametrize("lo,hi,page,slots,max_n", [
    (32, 768, 32, 1024, 16), (960, 1280, 32, 1408, 8), (8, 48, 16, 96, 4)])
def test_groups_cover_every_key(lo, hi, page, slots, max_n):
    groups = harness.prefill_groups(lo, hi, page, slots, max_n)
    warmed = {_key(g, page, slots) for g in groups}
    assert len(warmed) == len(groups)
    for g in groups:
        assert lo <= min(g) and max(g) <= hi and len(g) <= max_n
    rng = np.random.default_rng(0)
    for _ in range(3000):
        n = int(rng.integers(1, max_n + 1))
        lens = rng.integers(lo, hi + 1, n)
        if rng.random() < 0.5:
            lens = np.minimum(lens, rng.integers(lo, hi + 1))
        assert _key(list(lens), page, slots) in warmed
