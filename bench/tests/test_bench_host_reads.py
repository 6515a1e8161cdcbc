"""The ``host_reads`` reader: on a hand-made run record, and on the
``--trace 1`` path of both tiny cells."""
import time
from types import SimpleNamespace

import pytest

from bench import registry


def read(spans):
    return registry.metric_reader("host_reads")(SimpleNamespace(spans=spans))


def test_host_reads_per_round_from_the_round_spans():
    rounds = [{"kind": "span", "name": "round", "dur_us": 9e5,
               "attrs": {"host_reads": n}} for n in (11, 11, 12)]
    other = [{"kind": "span", "name": "reschedule", "dur_us": 3e3,
              "attrs": {"host_reads": 5}},
             {"kind": "instant", "name": "round", "dur_us": 0.0,
              "attrs": {"host_reads": 5}}]
    assert read(rounds + other) == pytest.approx(34 / 3)
    # round spans without the count (a program that makes none) read
    # nothing; the other spans and instants never count
    assert read([dict(e, attrs={}) for e in rounds] + other) is None
    assert read([]) is None


@pytest.mark.parametrize("traffic,want", [("astraea", 3.0), ("fedavg", 0.0)])
def test_traced_run_reports_host_reads(traffic, want, cpu_device,
                                       monkeypatch):
    """``bench/run.py``'s traced path at test size. The tiny Astraea cell
    packs 6 clients into 2 mediators of 3: the Alg. 3 picks and one
    Fig. 7 KLD per mediator; FedAvg reads nothing back."""
    from bench import peaks
    from bench import run as bench_run
    from bench.tests.conftest import tiny_cell
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    cell = tiny_cell(f"emnist-ltrf.{traffic}")
    res = bench_run.run_cell(cell, 7, 0.05, True, cpu_device,
                             t0=time.perf_counter(),
                             limits=registry.limits(cell.name))
    assert res["correct"] is True
    assert res["metrics"]["host_reads"] == {"value": want, "unit": "count"}
