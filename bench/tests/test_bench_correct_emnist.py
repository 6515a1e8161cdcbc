"""A whole run of the EMNIST cell without the chip, at a size a test can
hold: sound, it is correct; with each fault the cell can have planted
underneath the timed path, ``correct`` comes out false; the control (the
reference in bfloat16, in the program's place) fails the cell's limits.
The same for the FedAvg cell, under its own limits."""
import pytest

from bench.tests import correctness

CELL = "emnist-ltrf.astraea"
FEDAVG = "emnist-ltrf.fedavg"


@pytest.mark.parametrize("fault", [None, *correctness.FAULT_NAMES])
def test_run_is_correct_only_when_sound(fault, cpu_device):
    correctness.check_run(CELL, fault, cpu_device)


def test_control_fails():
    correctness.check_control(CELL)


@pytest.mark.parametrize("fault", [None, *correctness.FAULT_NAMES])
def test_fedavg_mix(fault, cpu_device):
    correctness.check_run(FEDAVG, fault, cpu_device)


def test_fedavg_control_fails():
    correctness.check_control(FEDAVG)
