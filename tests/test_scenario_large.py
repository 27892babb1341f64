"""make_large_scenario and reach_index_map: determinism, density bounds,
cluster invariants, and the no-zero-reach-device guarantee the compacted
association engine depends on."""

import numpy as np
import pytest

from repro.core.scenario import (make_large_scenario, make_scenario,
                                 reach_index_map)


def test_seed_determinism():
    a = make_large_scenario(300, 12, seed=7)
    b = make_large_scenario(300, 12, seed=7)
    np.testing.assert_array_equal(a.avail, b.avail)
    np.testing.assert_array_equal(a.dist, b.dist)
    np.testing.assert_array_equal(np.asarray(a.dev.channel_gain),
                                  np.asarray(b.dev.channel_gain))
    np.testing.assert_array_equal(np.asarray(a.dev.cycles_per_iter),
                                  np.asarray(b.dev.cycles_per_iter))
    c = make_large_scenario(300, 12, seed=8)
    assert not np.array_equal(a.dist, c.dist)


@pytest.mark.parametrize("n,k", [(250, 10), (1000, 20), (2000, 50)])
def test_reach_density_bounds_and_reachability(n, k):
    sc = make_large_scenario(n, k, seed=0)
    assert sc.n_devices == n and sc.n_servers == k
    assert sc.avail.shape == (k, n)
    # every device must reach >= 1 server (constraint 17e; a zero-reach
    # device would also break compacted slot indexing)
    assert sc.avail.any(axis=0).all()
    # restricted-reach regime: sparse but not degenerate
    density = sc.avail.mean()
    assert 0.0 < density < 0.6
    # availability is distance-consistent up to the nearest-server fallback
    reach = 3.0 * 120.0
    by_dist = sc.dist <= reach
    extra = sc.avail & ~by_dist
    fallback_devices = np.flatnonzero(~by_dist.any(axis=0))
    assert set(np.flatnonzero(extra.any(axis=0))) <= set(fallback_devices)
    for dev in fallback_devices:
        # exactly the nearest server was force-enabled
        assert sc.avail[:, dev].sum() == 1
        assert sc.avail[np.argmin(sc.dist[:, dev]), dev]


def test_cluster_size_invariants():
    """Devices drop as clusters around anchor servers: area scales with the
    server count, positions stay in-bounds, and most devices sit within a
    few cluster widths of their nearest server."""
    n, k, spread = 1000, 20, 120.0
    sc = make_large_scenario(n, k, seed=3, spread_m=spread)
    area = 500.0 * np.sqrt(k / 5.0)
    nearest = sc.dist.min(axis=0)
    # Gaussian clusters of width `spread` around a server: the nearest
    # server is at most ~the anchor distance away, so the 99th percentile
    # stays within a few sigma (clipping to the area can only reduce it)
    assert np.quantile(nearest, 0.99) < 4.0 * spread
    assert nearest.max() < area
    assert (sc.dist >= 0).all()


def test_custom_area_and_reach_override():
    sc = make_large_scenario(100, 5, seed=0, area_m=400.0, reach_m=1e6)
    assert sc.avail.all(), "unbounded reach must make everything available"
    assert sc.dist.max() <= np.sqrt(2) * 400.0 + 1e-6


# ---------------------------------------------------------------------------
# reach_index_map
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,k,reach", [(40, 4, 300.0), (250, 10, None)])
def test_reach_index_map_roundtrip(n, k, reach):
    sc = (make_scenario(n, k, seed=1, reach_m=reach) if reach
          else make_large_scenario(n, k, seed=1))
    ri = reach_index_map(sc.avail)
    counts = sc.avail.sum(axis=1)
    # allocated with headroom: at least an eighth more than the widest
    # count, in whole 128-lane tiles, never more than N
    assert ri.widest == counts.max()
    assert ri.r_max == min(n, 128 * -(-(counts.max() * 9 // 8 + 1) // 128))
    assert 0.0 < ri.density <= 1.0
    for srv in range(k):
        devices = np.flatnonzero(sc.avail[srv])
        # forward map: ascending reachable devices, then padding
        np.testing.assert_array_equal(ri.idx[srv, :devices.size], devices)
        assert ri.valid[srv].sum() == devices.size
        assert not ri.valid[srv, devices.size:].any()
        # inverse map: slot[srv, idx[srv, r]] == r on valid slots,
        # sentinel r_max everywhere else
        np.testing.assert_array_equal(
            ri.slot[srv, devices], np.arange(devices.size))
        off = np.ones(sc.n_devices, bool)
        off[devices] = False
        assert (ri.slot[srv, off] == ri.r_max).all()


def test_reach_index_map_rejects_zero_reach_device():
    avail = np.ones((3, 5), dtype=bool)
    avail[:, 2] = False
    with pytest.raises(ValueError, match="reach"):
        reach_index_map(avail)
