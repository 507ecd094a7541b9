"""The stratified client profiles: one set for every seed, in the seed's
order."""

import collections

import pytest

from fedbench import cohorts, harness, spec


def _fleet(seed, n=256):
    config = spec.load_json("configs", "mnist_mlp_fleet256")
    traffic = spec.load_json("traffic", "sync_topk_int8")
    config = dict(config, n_clients=n)
    fc, _ = harness.fleet_configs(config, traffic, seed)
    return fc


def _set(profiles):
    return sorted((p.cohort, p.up_rate_bps, p.down_rate_bps, p.delay_ns,
                   p.jitter_ns, p.loss_p, p.train_time_ns, p.weight,
                   p.cadence_ns) for p in profiles)


@pytest.mark.parametrize("n", [1, 16, 255, 256])
def test_every_seed_draws_the_same_set(n):
    a = cohorts.profiles(_fleet(3, n))
    b = cohorts.profiles(_fleet(3_300_000_011, n))
    assert len(a) == len(b) == n
    assert _set(a) == _set(b)
    assert [p.addr for p in a] == [p.addr for p in b]
    if n > 16:
        assert [p.cohort for p in a] != [p.cohort for p in b]


def test_counts_follow_the_mix_by_largest_remainder():
    mix = [("fiber", 0.3), ("lte", 0.5), ("congested-edge", 0.2)]
    assert cohorts.counts(mix, 256) == {"fiber": 77, "lte": 128,
                                        "congested-edge": 51}
    assert cohorts.counts(mix, 1) == {"fiber": 0, "lte": 1,
                                      "congested-edge": 0}
    assert sum(cohorts.counts(mix, 1001).values()) == 1001


def test_profiles_stay_inside_their_cohort_ranges():
    fc = _fleet(7)
    ps = cohorts.profiles(fc)
    assert collections.Counter(p.cohort for p in ps)["congested-edge"] == 51
    for p in ps:
        s = fc.cohort_specs()[p.cohort]
        for field in cohorts.RANGES:
            lo, hi = getattr(s, field)
            assert lo <= getattr(p, field) <= hi
        assert p.down_rate_bps == p.up_rate_bps * s.down_up_ratio
        assert p.bursty == s.bursty
    assert len({p.seed for p in ps}) == len(ps)


def test_harness_fleet_uses_the_stratified_draw():
    from repro.core import fleet
    drawn = fleet.sample_profiles
    config = spec.load_json("configs", "cnn1m_silo16")
    traffic = spec.load_json("traffic", "sync_int8")
    config = dict(config, model_args=dict(config["model_args"], n_params=64),
                  n_params=64)
    fb = harness.build_fleet(config, traffic, 9)
    assert fleet.sample_profiles is drawn
    fc, _ = harness.fleet_configs(config, traffic, 9)
    assert fb.profiles == cohorts.profiles(fc)
