"""The copied federation generator: structure from the configuration's
fixed seed, pixels from ``--seed``."""
import numpy as np

from bench import federation, registry
from bench.tests.conftest import tiny_cell


def test_structure_is_the_same_at_every_pixel_seed():
    cfg = tiny_cell("emnist-ltrf.astraea").config
    a = federation.generate(cfg, 1)
    b = federation.generate(cfg, 2 ** 31 + 17)
    np.testing.assert_array_equal(a.counts, b.counts)
    assert [len(y) for y in a.client_labels] == \
        [len(y) for y in b.client_labels]
    for y, row in zip(a.client_labels, a.counts):
        np.testing.assert_array_equal(np.bincount(y, minlength=8), row)
    assert not np.array_equal(a.client_images[0], b.client_images[0])
    c = federation.generate(cfg, 1)
    np.testing.assert_array_equal(a.client_images[3], c.client_images[3])


def test_paper_federations_at_structure_seed_0():
    """The pads the cells' memory rests on (host reckoning)."""
    e = federation.pack_reckoning(registry.config("emnist-ltrf"), 20)
    assert (e["largest_client"], e["median_client"]) == (2685, 119.0)
    assert e["pad"] == 2700 and e["pack_bytes"] == 500 * 2700 * 3144
    c = federation.pack_reckoning(registry.config("cinic-normal"), 20)
    assert c["largest_client"] == 1343 and c["pad"] == 1360
    assert c["pack_bytes"] == 500 * 1360 * (32 * 32 * 3 * 4 + 8)


def test_counts_match_the_programs_partition():
    """The copy draws the same client sizes and class counts as
    ``repro.data.federated.partition`` at the same seed."""
    from repro.data.federated import EMNIST_LIKE, partition
    import dataclasses
    cfg = tiny_cell("emnist-ltrf.astraea").config
    spec = dataclasses.replace(EMNIST_LIKE, num_classes=8, image_size=16)
    fed = partition(spec, num_clients=12, total_samples=600,
                    test_samples=40, sizes="instagram",
                    global_dist="letterfreq", local="random",
                    seed=cfg["structure_seed"])
    np.testing.assert_array_equal(fed.client_counts(),
                                  federation.class_counts(cfg))


def test_pack_reckoning_matches_the_store(cpu_device):
    """The host reckoning printed by every run equals the bytes the
    replicated store holds on the device."""
    import jax
    from bench import program, reference
    cell = tiny_cell("emnist-ltrf.astraea")
    fed = federation.generate(cell.config, 5)
    pack = federation.pack_reckoning(cell.config,
                                     cell.traffic["batch_size"], fed.counts)
    p0 = reference.make_init(cell.config)(jax.random.PRNGKey(5))
    tr = program.build_trainer(cell.config, cell.traffic, fed, 5, p0,
                               devices=cpu_device)
    stats = tr.engine.store.stats()
    assert stats["per_device_bytes"] == pack["pack_bytes"]
