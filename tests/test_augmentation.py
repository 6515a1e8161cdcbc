"""Algorithm 2 tests: plan math, client rebalance, global-KLD reduction."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import augmentation as aug
from repro.core import distribution as dist


def test_plan_majority_classes_not_augmented():
    counts = np.array([100, 90, 10, 5])
    plan = aug.augmentation_plan(counts, 0.67)
    c_bar = counts.mean()
    assert all(plan[counts >= c_bar] == 0)
    assert all(plan[counts < c_bar] > 0)


@given(st.floats(0.1, 1.0), st.floats(1.1, 3.0))
@settings(max_examples=25, deadline=None)
def test_plan_alpha_monotone(a_small, a_big):
    counts = np.array([1000, 500, 100, 20, 4])
    p_small = aug.augmentation_plan(counts, a_small)
    p_big = aug.augmentation_plan(counts, a_big)
    assert np.all(p_big >= p_small)


def test_alpha_two_overshoots():
    """The paper's failure mode: alpha=2 re-imbalances the dataset."""
    counts = np.array([1000.0, 500.0, 200.0, 50.0, 10.0])
    c_bar = counts.mean()
    good = aug.planned_counts(counts, 0.67)
    bad = aug.planned_counts(counts, 2.0)
    assert good.max() <= counts.max() * 1.01         # stays bounded
    assert bad[-1] > 10 * c_bar                      # minority explodes past mean
    kld_before = float(dist.kld_to_uniform(jnp.asarray(counts)))
    kld_good = float(dist.kld_to_uniform(jnp.asarray(good)))
    assert kld_good < kld_before


def test_random_affine_shapes_and_finite(key):
    img = jnp.ones((20, 20, 3))
    out = aug.random_affine(key, img)
    assert out.shape == img.shape
    assert np.isfinite(np.asarray(out)).all()


def test_rebalance_client_counts(key):
    rng = np.random.default_rng(0)
    images = rng.normal(size=(30, 12, 12, 1)).astype(np.float32)
    labels = np.array([0] * 20 + [1] * 8 + [2] * 2)
    plan = np.array([0, 2, 5])
    x, y = aug.rebalance_client(key, images, labels, plan)
    assert (y == 0).sum() == 20                       # untouched
    assert (y == 1).sum() == 8 * 3                    # 8 + 2 copies each
    assert (y == 2).sum() == 2 * 6                    # 2 + 5 copies each
    assert x.shape[0] == y.shape[0]


def test_plan_empty_class_explicit():
    """Alg. 2 line 3 edge case: an empty class is below the mean (it joins
    the augmentation set) but holds nothing to warp -- its plan entry must
    be 0 by construction, while C_bar still averages over ALL classes."""
    counts = np.array([0, 100, 90, 10, 0])
    plan = aug.augmentation_plan(counts, 0.67)
    assert plan[0] == 0 and plan[4] == 0                 # empty: explicit 0
    assert plan[3] > 0                                   # minority: augmented
    c_bar = counts.mean()                                # 40, over 5 classes
    assert all(plan[(counts >= c_bar)] == 0)
    # planned counts keep empty classes empty -- augmentation cannot invent
    # samples for a class nobody holds
    planned = aug.planned_counts(counts, 0.67)
    assert planned[0] == 0 and planned[4] == 0
    # all-empty federation degenerates to the zero plan, not an error
    assert np.all(aug.augmentation_plan(np.zeros(4), 0.67) == 0)
    with pytest.raises(ValueError, match="1-D"):
        aug.augmentation_plan(np.zeros((2, 2)), 0.67)


def _mc_class_freqs(counts, alpha, *, n_batches=48, seed=0):
    """Monte Carlo class frequencies of online draws from a client whose
    local counts equal ``counts`` (pad = sum counts, all slots valid)."""
    counts = np.asarray(counts, int)
    labels = np.repeat(np.arange(counts.size), counts).astype(np.int32)
    rng = np.random.default_rng(seed)
    images = rng.normal(size=(labels.size, 6, 6, 1)).astype(np.float32)
    plan = jnp.asarray(aug.augmentation_plan(counts, alpha))
    mask = jnp.ones(labels.size, jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(seed), n_batches)
    fn = jax.jit(jax.vmap(lambda k: aug.online_augment_batch(
        k, jnp.asarray(images), jnp.asarray(labels), mask, plan)[1]))
    drawn = np.asarray(fn(keys)).ravel()
    return np.bincount(drawn, minlength=counts.size) / drawn.size


def test_online_expected_counts_match_planned(key):
    """The Alg. 2 consistency contract of online mode: the expected class
    mixture of the in-round draws is exactly planned_counts(counts, alpha)
    normalized (seeded Monte Carlo, tolerance ~3 sigma)."""
    counts = np.array([40, 20, 8, 4])
    alpha = 0.67
    freqs = _mc_class_freqs(counts, alpha)
    planned = aug.planned_counts(counts, alpha)
    expect = planned / planned.sum()
    np.testing.assert_allclose(freqs, expect, atol=0.03)
    np.testing.assert_allclose(np.asarray(aug.online_mixture(counts, alpha)),
                               expect)


def test_online_alpha_two_overshoot_reproduced():
    """The paper's alpha=2 failure mode, in ONLINE mode: the very-minority
    class overshoots past the mean and re-imbalances the drawn mixture."""
    counts = np.array([100, 50, 20, 5, 1])
    f_good = _mc_class_freqs(counts, 0.67, seed=1)
    f_bad = _mc_class_freqs(counts, 2.0, seed=1)
    # at alpha=2 the rarest class dominates the draws outright
    assert f_bad[-1] > 1.0 / counts.size            # overshot uniform share
    assert f_bad[-1] == f_bad.max()                 # ...and every class
    kld = lambda f: float(dist.kld_to_uniform(jnp.asarray(f * 1000.0)))
    assert kld(f_bad) > kld(f_good)                 # re-imbalanced


def test_online_zero_plan_is_pure_resample(key):
    """With an all-zero plan no draw is ever warped: every output slot is a
    bitwise copy of some input sample (determinism anchor for the engine's
    no-op guarantees)."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(20, 6, 6, 1)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 3, 20).astype(np.int32))
    mask = jnp.ones(20, jnp.float32)
    ax, ay = aug.online_augment_batch(key, x, y, mask,
                                      jnp.zeros(3, jnp.int32))
    ax, ay = np.asarray(ax), np.asarray(ay)
    xs = np.asarray(x)
    for i in range(ax.shape[0]):
        src = np.flatnonzero((xs == ax[i]).all(axis=(1, 2, 3)))
        assert src.size >= 1 and np.asarray(y)[src[0]] == ay[i]


def test_online_dummy_slot_stays_noop(key):
    """An all-padding client (mask 0 everywhere) must not produce NaNs or
    out-of-range gathers -- the engine relies on masked no-ops."""
    x = jnp.ones((10, 6, 6, 1), jnp.float32)
    y = jnp.zeros(10, jnp.int32)
    ax, ay = aug.online_augment_batch(key, x, y, jnp.zeros(10, jnp.float32),
                                      jnp.asarray([3, 0], jnp.int32))
    assert np.isfinite(np.asarray(ax)).all()
    assert set(np.asarray(ay).tolist()) <= {0}


def test_rebalance_federation_reduces_global_kld(key, tiny_federation):
    fed = tiny_federation
    before = float(dist.kld_to_uniform(
        jnp.asarray(fed.client_counts().sum(0))))
    new_x, new_y, plan, extra = aug.rebalance_federation(
        key, fed.client_images, fed.client_labels, fed.num_classes, alpha=0.67)
    counts = np.zeros(fed.num_classes)
    for y in new_y:
        counts += np.bincount(y, minlength=fed.num_classes)
    after = float(dist.kld_to_uniform(jnp.asarray(counts)))
    assert after < before
    assert extra > 0


def _flagged(rng, n, count):
    """``is_aug`` and ``mask`` over ``n`` slots whose conjunction flags
    exactly ``count`` slots, with augmented masked-out slots and masked-in
    plain slots besides."""
    flags = np.zeros(n, bool)
    flags[rng.choice(n, count, replace=False)] = True
    rest = np.flatnonzero(~flags)
    is_aug, mask = flags.copy(), flags.copy()
    is_aug[rest[: rest.size // 2]] = True      # augmented, masked out
    mask[rest[rest.size // 2:]] = True         # masked in, not augmented
    return jnp.asarray(is_aug), jnp.asarray(mask, jnp.float32)


_N = 1100                                       # ladder (1024, 1100)


@pytest.mark.parametrize("count", [0, 1, 1024, 1025, _N],
                         ids=["none", "one", "rung", "rung+1", "all"])
@pytest.mark.parametrize("h,c", [(28, 1), (32, 3)])
def test_compact_warp_matches_full_warp(count, h, c):
    """The compacted warp of ``is_aug & mask`` equals the full warp of
    every slot wherever the slot is masked in, bitwise: warped where
    flagged, the drawn image where not. It counts the flags and warps
    them at the smallest rung that holds them."""
    assert aug.warp_ladder(_N) == (1024, _N)
    rng = np.random.default_rng(count)
    sx = jnp.asarray(rng.normal(size=(_N, h, h, c)).astype(np.float32))
    mats, trans = aug.warp_params(jax.random.PRNGKey(count), _N)
    is_aug, mask = _flagged(rng, _N, count)
    flags = is_aug & (mask > 0)
    out, slots, cap = jax.jit(
        lambda *a: aug.compact_warp(*a, impl="reference"))(sx, mats, trans,
                                                           flags)
    want = jax.jit(lambda sx, mats, trans, flags: jnp.where(
        flags[:, None, None, None],
        aug.affine_warp(sx, mats, trans, impl="reference"), sx))(
            sx, mats, trans, flags)
    live = np.asarray(mask) > 0
    out, want, sx = np.asarray(out), np.asarray(want), np.asarray(sx)
    np.testing.assert_array_equal(out[live], want[live])
    # masked-out slots keep the drawn image instead of a warped copy
    np.testing.assert_array_equal(out[~live], sx[~live])
    assert int(slots) == count
    assert int(cap) == (1024 if count <= 1024 else _N)


def test_warp_ladder_rungs():
    """Powers of 4 from 1,024 below the slot count, then the count."""
    assert aug.warp_ladder(135_000) == (1024, 4096, 16384, 65536, 135_000)
    assert aug.warp_ladder(1024) == (1024,)
    assert aug.warp_ladder(300) == (300,)
