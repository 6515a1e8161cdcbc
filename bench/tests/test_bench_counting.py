"""The benchmark's FLOP, parameter and warp counters against hand counts."""
import pytest

from bench import counting, registry


@pytest.mark.parametrize("name, fwd, params", [
    # conv1 12x12x12x25 + conv2 5x5x18x108 + conv3 4x4x24x72
    # + dense 384x150 + 150x47 multiply-adds, times 2
    ("emnist-ltrf", 368_196, 68_873),
    # 32x32x32x27 + 32x32x32x288 + 16x16x64x288 + 16x16x64x576
    # + 4096x512 + 512x10 multiply-adds, times 2
    ("cinic-normal", 53_159_936, 2_168_362),
])
def test_forward_flops_and_params(name, fwd, params):
    cfg = registry.config(name)
    assert counting.forward_flops(cfg) == fwd
    assert counting.train_flops(cfg) == 3 * fwd
    assert counting.param_count(cfg) == params


@pytest.mark.parametrize("name", ["emnist-ltrf", "cinic-normal"])
def test_layer_table_matches_the_model(name):
    """The table the counters and the reference walk has the program's
    parameter tree, leaf for leaf."""
    import jax
    from repro.models import cnn
    cfg = registry.config(name)
    model = getattr(cnn, cfg["model_builder"])(**cfg["model_kwargs"])
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda s: tuple(s.shape), shapes)
    assert got == counting.param_shapes(cfg)


@pytest.mark.parametrize("h, w, c, flops, nbytes", [
    # 784 pixels: read 784 + write 784 f32 pixels + 2x784 f32 coordinates
    (28, 28, 1, 8 * 784, 4 * (2 * 784 + 2 * 784)),
    (32, 32, 3, 8 * 1024 * 3, 4 * (2 * 1024 * 3 + 2 * 1024)),
])
def test_warp_work(h, w, c, flops, nbytes):
    assert counting.warp_work(h, w, c) == {"flops": flops, "bytes": nbytes}


def test_warp_bytes_are_about_12_5_kb_per_emnist_image():
    assert counting.warp_work(28, 28, 1)["bytes"] == 12_544


def test_unknown_layer_kind_is_refused():
    with pytest.raises(ValueError, match="unknown layer kind"):
        counting.layer_walk({"input_shape": [4, 4, 1],
                             "layers": [{"kind": "attention"}]})
