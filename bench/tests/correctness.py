"""Shared body of the whole-run correctness tests (one file per
configuration, so that the runs spread over the test workers)."""
import time

import jax.numpy as jnp

from bench import check, federation, registry, reference
from bench import run as bench_run
from bench.tests.conftest import tiny_cell
from bench.tests.faults import FAULTS

FAULT_NAMES = sorted(FAULTS)
SEED = 2 ** 31 + 11          # larger than 32 signed bits hold


def check_run(name, fault, devices, traffic=None, config=None):
    cell = tiny_cell(name, traffic, config)
    res = bench_run.run_cell(cell, SEED, 0.05, False, devices,
                             t0=time.perf_counter(),
                             limits=registry.limits(name),
                             fault=FAULTS[fault] if fault else None)
    assert list(res)[-1] == "checks"
    assert res["correct"] is (fault is None), res["checks"]


def check_control(name, config=None):
    cell = tiny_cell(name, config=config)
    cfg, mix = cell.config, cell.traffic
    fed = federation.generate(cfg, SEED)
    pseed = SEED % (2 ** 31 - 2)
    import jax
    import numpy as np
    p0 = jax.tree.map(np.asarray, reference.make_init(cfg)(
        jax.random.PRNGKey(pseed)))

    def ref(**kw):
        return reference.run_rounds(cfg, mix, fed.client_images,
                                    fed.client_labels, fed.counts, p0,
                                    pseed, mix["steps_checked"], **kw)

    rp, rs = ref()
    cp, cs = ref(dtype=jnp.bfloat16)
    numbers = check.compare(cp, cs, rp, rs, p0,
                            reference.loss_on(cfg, fed.test_images,
                                             fed.test_labels))
    ok, table = check.verdict(numbers, registry.limits(name))
    assert not ok, table
