"""The system under test, built from a configuration and a traffic mix.

The benchmark hands the program its inputs -- the federation and the
initial weights, both made from ``--seed`` -- and drives the trainer's own
``run_round``. Everything else (cohort draws, Alg. 3, Alg. 2, the round
program, Eq. 6) is the program's.
"""
from __future__ import annotations

import jax
import numpy as np


def build_trainer(cfg: dict, traffic: dict, fed, seed: int, params0, *,
                  devices):
    """``AstraeaTrainer`` or ``FedAvgTrainer`` over the generated
    federation on a mediator mesh of ``devices``, holding ``params0`` as
    its initial weights."""
    from repro import optim
    from repro.core import LocalSpec
    from repro.data.federated import FederatedDataset
    from repro.launch.mesh import make_mediator_mesh
    from repro.models import cnn

    if traffic["row_exec"] != "vmap" \
            or traffic["mediator_mesh"] != len(devices):
        raise ValueError("the trainers run vmapped rows on a mediator mesh "
                         "of the cell's devices; other mixes need another "
                         "driver")
    data = FederatedDataset(list(fed.client_images), list(fed.client_labels),
                            fed.test_images, fed.test_labels,
                            cfg["num_classes"], name=cfg.get("name", "fed"))
    model = getattr(cnn, cfg["model_builder"])(**cfg["model_kwargs"])
    spec = dict(cfg["optimizer"])
    opt = getattr(optim, spec.pop("name"))(**spec)
    local = LocalSpec(traffic["batch_size"], traffic["epochs"])
    common = dict(clients_per_round=traffic["clients_per_round"],
                  local=local, store=traffic["store"],
                  mesh=make_mediator_mesh(len(devices)), seed=seed)
    if traffic["trainer"] == "astraea":
        from repro.core.astraea import AstraeaTrainer
        tr = AstraeaTrainer(
            model, opt, data, gamma=traffic["gamma"],
            mediator_epochs=traffic["mediator_epochs"],
            alpha=traffic["alpha"], aug_mode=traffic["aug_mode"],
            reschedule_every_round=traffic["reschedule_every_round"],
            **common)
    elif traffic["trainer"] == "fedavg":
        from repro.core.fedavg import FedAvgTrainer
        if traffic["gamma"] != 1 or traffic["mediator_epochs"] != 1 \
                or not traffic["reschedule_every_round"]:
            raise ValueError("FedAvg trains one client per row, once, on "
                             "a fresh cohort every round")
        tr = FedAvgTrainer(model, opt, data, alpha=traffic["alpha"],
                           aug_mode=traffic["aug_mode"], **common)
    else:
        raise ValueError(f"unknown trainer {traffic['trainer']!r}")
    old = tr.params
    if jax.tree.structure(old) != jax.tree.structure(params0) or any(
            a.shape != b.shape for a, b in zip(jax.tree.leaves(old),
                                               jax.tree.leaves(params0))):
        raise ValueError("the configuration's layer table does not match "
                         "the model's parameters")
    tr.params = jax.tree.map(lambda new, o: jax.device_put(new, o.sharding),
                             params0, old)
    return tr


def host_params(tr):
    """A numpy copy of the trainer's weights (waits for the device)."""
    return jax.tree.map(lambda a: np.array(a, np.float32, copy=True),
                        tr.params)
