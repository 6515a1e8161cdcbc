"""The harness on the CINIC configuration, which no cell runs yet (its
cells are deferred), at a size a test can hold, judged by the EMNIST
cells' limits of the same mix: sound, it is correct; with each fault
planted underneath the timed path, ``correct`` comes out false; the
control fails the limits."""
import pytest

from bench.tests import correctness

CELL = "emnist-ltrf.astraea"
CONFIG = "cinic-normal"


@pytest.mark.parametrize("fault", [None, *correctness.FAULT_NAMES])
def test_run_is_correct_only_when_sound(fault, cpu_device):
    correctness.check_run(CELL, fault, cpu_device, config=CONFIG)


def test_control_fails():
    correctness.check_control(CELL, config=CONFIG)


@pytest.mark.parametrize("fault", [None, "half_the_batch"])
def test_fedavg_mix(fault, cpu_device):
    correctness.check_run("emnist-ltrf.fedavg", fault, cpu_device,
                          config=CONFIG)
