"""Tiny cells for CPU tests of the harness: the real cells' configuration
and traffic files, cut to 12 clients, 8 classes and small images, with
the real cells' metric lists and correctness limits."""
import copy

import pytest

from bench import registry


def tiny_cell(name: str, traffic: str | None = None,
              config: str | None = None) -> registry.Cell:
    """Cell ``name`` cut to test size; ``traffic`` and ``config`` swap in
    another mix or configuration (ones that no cell runs yet)."""
    real = registry.cell(name)
    cfg = copy.deepcopy(registry.config(config) if config else real.config)
    mix = copy.deepcopy(registry.traffic(traffic) if traffic
                        else real.traffic)
    image = 16 if cfg["input_shape"][0] == 28 else 8
    cfg.update(num_clients=12, total_samples=600, num_classes=8,
               test_per_class=5)
    cfg["model_kwargs"].update(num_classes=8, image_size=image)
    cfg["input_shape"] = [image, image, cfg["input_shape"][2]]
    cfg["layers"][-1]["units"] = 8
    mix.update(clients_per_round=6, gamma=min(mix["gamma"], 3))
    return registry.Cell(name=real.name, config_name=real.config_name,
                         traffic_name=real.traffic_name, chips=1,
                         config=cfg, traffic=mix,
                         end_to_end=real.end_to_end,
                         per_layer=real.per_layer)


@pytest.fixture
def cpu_device():
    import jax
    return jax.devices("cpu")[:1]
