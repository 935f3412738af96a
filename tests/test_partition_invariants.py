"""Property-style invariants of the partitioning/planning layer.

Covers the paper's Eqs. 1–7 contract that the capacity-balanced runtime
relies on:

  * ``weighted_partition``/``uniform_partition`` sizes are a partition of n
    (non-negative, contiguous, sum to n) for any weights/m;
  * chunk 0 respects the multiple-of-m constraint (Eq. 2: the exact chunk is
    ~m x a speculative chunk under equal weights);
  * equal capacities with m = 1 degrade ``weighted_partition`` (and the
    planner's ``ChunkLayout.weighted``) to ``uniform_partition`` exactly;
  * ``capacity_weights`` is Eq. 1 (mean-normalized, rejects non-positive);
  * ``layout_device_work`` is conserved and proportional to capacities on
    full-width input.

Seeded random sweeps stand in for hypothesis (absent in the image); when
hypothesis is available the same properties also run fuzzed.
"""

import numpy as np
import pytest

from repro.core import (capacity_weights, profile_workers, synthetic_capacities,
                        uniform_partition, weighted_partition)
from repro.core.engine import ChunkLayout, Planner, layout_device_work

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - image has no hypothesis
    HAVE_HYPOTHESIS = False


def _check_is_partition(part, n):
    sizes = part.sizes
    assert (sizes >= 0).all()
    assert int(sizes.sum()) == n
    # contiguous, ordered spans covering [0, n)
    assert part.start[0] == 0 and part.end[-1] == n
    assert (part.start[1:] == part.end[:-1]).all()


def test_weighted_partition_is_a_partition_sweep():
    rng = np.random.default_rng(0)
    for trial in range(200):
        n = int(rng.integers(0, 50_000))
        p = int(rng.integers(1, 33))
        m = int(rng.integers(1, 65))
        w = capacity_weights(rng.uniform(0.25, 4.0, size=p))
        _check_is_partition(weighted_partition(n, w, m), n)
        _check_is_partition(uniform_partition(n, p, m), n)


def test_equal_capacities_m1_degrades_to_uniform_sweep():
    rng = np.random.default_rng(1)
    for trial in range(100):
        n = int(rng.integers(0, 50_000))
        p = int(rng.integers(1, 33))
        got = weighted_partition(n, np.ones(p), 1)
        want = uniform_partition(n, p, 1)
        np.testing.assert_array_equal(got.start, want.start)
        np.testing.assert_array_equal(got.end, want.end)


def test_equal_capacities_work_balanced_any_m():
    """Eqs. 2–7 with equal weights: per-processor scalar work (speculative
    chunks match m states) is balanced up to rounding."""
    rng = np.random.default_rng(2)
    for trial in range(50):
        p = int(rng.integers(2, 25))
        m = int(rng.integers(1, 33))
        n = int(rng.integers(64 * p * m, 128 * p * m))
        work = weighted_partition(n, np.ones(p), m).work()
        assert work.min() > 0
        assert float(work.max() / work.min()) < 1.1


def test_chunk0_multiple_of_m_constraint():
    """Eq. 2 under equal weights: the exact chunk 0 is ~m x a speculative
    chunk, so its one-state scan matches the m-state speculative lanes."""
    rng = np.random.default_rng(3)
    n = 200_000
    for trial in range(50):
        p = int(rng.integers(2, 25))
        m = int(rng.integers(1, 33))
        part = weighted_partition(n, np.ones(p), m)
        spec = part.sizes[1:]
        assert spec.min() > 0
        ratio = part.sizes[0] / spec.astype(np.float64).mean()
        assert ratio == pytest.approx(m, rel=0.1)


def test_capacity_weights_eq1():
    w = capacity_weights(np.array([2.0, 1.0, 1.0]))
    assert w.mean() == pytest.approx(1.0)
    assert w[0] == pytest.approx(2.0 * 3 / 4.0)
    np.testing.assert_allclose(profile_workers([3.0, 1.0]), [1.5, 0.5])
    with pytest.raises(ValueError):
        capacity_weights(np.array([1.0, 0.0]))
    with pytest.raises(ValueError):
        capacity_weights(np.array([-1.0, 2.0]))


def test_layout_device_work_conserved_sweep():
    rng = np.random.default_rng(4)
    for trial in range(100):
        d = int(rng.integers(1, 9))
        cpd = int(rng.integers(1, 5))
        lc = int(rng.integers(1, 257))
        c = d * cpd
        width = c * lc
        caps = rng.uniform(0.5, 2.0, size=d)
        layout = ChunkLayout.weighted(width, c, d, capacity_weights(caps))
        assert layout.num_chunks == c and layout.num_devices == d
        lengths = rng.integers(0, width + 1, size=7)
        work = layout_device_work(layout, lengths)
        assert work.shape == (d,)
        assert int(work.sum()) == int(lengths.sum())  # every symbol assigned
        # equal capacities degrade the layout to uniform exactly
        uni = ChunkLayout.weighted(width, c, d, np.ones(d))
        ref = ChunkLayout.uniform(width, c, d)
        np.testing.assert_array_equal(uni.starts, ref.starts)
        np.testing.assert_array_equal(uni.ends, ref.ends)


def test_weighted_layout_proportional_to_capacity():
    """Full-width input: per-device work tracks the skewed capacity profile
    (the load-balancing mechanism the sharded executor inherits)."""
    d, cpd, width = 8, 2, 65_536
    caps = synthetic_capacities(d)  # 1.41x fast half
    layout = ChunkLayout.weighted(width, d * cpd, d, profile_workers(caps))
    work = layout_device_work(layout, np.array([width]))
    util = work / caps
    assert float(util.max() / util.mean()) < 1.02
    # uniform layout on the same profile leaves the paper's 1.41 skew
    uni = ChunkLayout.uniform(width, d * cpd, d)
    uutil = layout_device_work(uni, np.array([width])) / caps
    assert float(uutil.max() / uutil.mean()) > 1.15


def test_planner_rounds_chunks_and_validates():
    pl = Planner(num_chunks=6, devices=4)
    assert pl.num_chunks == 8  # rounded up to a device multiple
    with pytest.raises(ValueError):
        Planner(num_chunks=0)
    with pytest.raises(ValueError):
        Planner(num_chunks=8, max_buckets=0)
    with pytest.raises(ValueError):
        Planner(num_chunks=8, devices=2, weights=np.ones(3))


def test_planner_bucket_plan_matches_sticky_policy():
    pl = Planner(num_chunks=8, max_buckets=2)
    lengths = np.array([0, 3, 31, 32, 100, 255, 513, 1024, 2000])
    plan = pl.plan(lengths)
    # short docs (< 4 * C = 32) are sequential
    np.testing.assert_array_equal(plan.spec_mask, lengths >= 32)
    kinds = [b.kind for b in plan.buckets]
    assert kinds.count("seq") == 1
    assert 1 <= kinds.count("spec") <= 2
    assert len(pl.spec_keys) <= 2
    covered = np.concatenate([b.doc_idx for b in plan.buckets])
    assert sorted(covered.tolist()) == list(range(len(lengths)))
    # sticky: a second batch inside the compiled range adds no keys
    keys = list(pl.spec_keys)
    pl.plan(np.array([40, 700, 1800]))
    assert pl.spec_keys == keys


if HAVE_HYPOTHESIS:
    @settings(max_examples=60, deadline=None)
    @given(n=st.integers(0, 50_000), p=st.integers(1, 32),
           m=st.integers(1, 64), seed=st.integers(0, 2**31 - 1))
    def test_weighted_partition_is_a_partition_fuzzed(n, p, m, seed):
        rng = np.random.default_rng(seed)
        w = capacity_weights(rng.uniform(0.25, 4.0, size=p))
        _check_is_partition(weighted_partition(n, w, m), n)


def _tile_max_sum(lengths, order, tile):
    return sum(int(lengths[order[lo:lo + tile]].max())
               for lo in range(0, order.size, tile))


@pytest.mark.parametrize("lengths,num_chunks", [
    pytest.param(np.random.default_rng(7).integers(0, 2000, 300), 1,
                 id="ragged-seq"),
    pytest.param(np.random.default_rng(8).integers(0, 3000, 300), 8,
                 id="ragged-seq-and-spec"),
    pytest.param(np.array([5, 9, 5, 20, 9, 9, 1, 20, 5]), 1, id="ties"),
    pytest.param(np.array([40, 100, 40, 100, 900, 40, 3, 3, 900]), 4,
                 id="ties-spec"),
    pytest.param(np.full(150, 333), 1, id="equal-seq"),
    pytest.param(np.full(150, 333), 8, id="equal-spec"),
])
def test_planner_emits_buckets_in_tile_order(lengths, num_chunks):
    plan = Planner(num_chunks=num_chunks).plan(lengths)
    covered = np.concatenate([b.doc_idx for b in plan.buckets])
    assert sorted(covered.tolist()) == list(range(lengths.size))
    for b in plan.buckets:
        arrival = np.sort(b.doc_idx)
        # a permutation of the bucket's documents, longest first, ties in
        # arrival order
        want = arrival[np.lexsort((arrival, -lengths[arrival]))]
        np.testing.assert_array_equal(b.doc_idx, want)
        assert (np.diff(lengths[b.doc_idx]) <= 0).all()
        if np.unique(lengths[b.doc_idx]).size == 1:
            np.testing.assert_array_equal(b.doc_idx, arrival)
        # grouping sorted lengths never raises the tiles' longest rows
        for tile in (1, 4, 64):
            assert (_tile_max_sum(lengths, b.doc_idx, tile)
                    <= _tile_max_sum(lengths, arrival, tile))
