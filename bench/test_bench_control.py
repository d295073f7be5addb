"""``correct`` comes out false for the control and for each fault the
cells can have.

The control (bench/control.py) runs at the cells' own size: the reference
on float32 timestamps in the program's place.  The faults are planted in
the program underneath a whole run of the harness at a small size on the
CPU, with the look for a chip skipped.
"""

import copy
import dataclasses
import time

import pytest

from bench import control, harness

SMALL = {"email-eu.batch": {"n_edges": 3000},
         "sms-a.stream": {"n_edges": 4000, "n_nodes": 400}}


@pytest.mark.parametrize("workload", sorted(SMALL))
@pytest.mark.parametrize("seed", [0, 2**31 + 11])
def test_control_is_not_correct_at_the_cells_size(workload, seed):
    out = control.readings(harness.resolve(workload), seed)
    assert not out["passed"]
    assert out["check"]["count_l1_max"][0] > 0


def small_cell(workload):
    cell = harness.resolve(workload)
    cfg = copy.deepcopy(cell.config)
    cfg["generator"]["params"].update(SMALL[workload])
    mix = dict(cell.mix)
    mix["warmup_" + ("mines" if "batch" in workload else "passes")] = 1
    return dataclasses.replace(cell, config=cfg, mix=mix)


def run(workload, seed=5):
    return harness.run_cell(small_cell(workload), seed=seed, seconds=0.2,
                            trace=False, t0=time.perf_counter(),
                            look_for_chips=False, log=lambda msg: None)


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: "off")


def alter(counts):
    """Move one process from the commonest code to a new one."""
    counts = dict(counts)
    top = max(counts, key=counts.get)
    counts[top] -= 1
    counts["0101010101"] = counts.get("0101010101", 0) + 1
    return counts


def discover_altered(real):
    def discover(self, graph):
        res = real(self, graph)
        return dataclasses.replace(res, counts=alter(res.counts))
    return discover


def discover_half(real):
    def discover(self, graph):
        h = graph.n_edges // 2
        return real(self, type(graph)(u=graph.u[:h], v=graph.v[:h],
                                      t=graph.t[:h], n_nodes=graph.n_nodes))
    return discover


def ingest_nothing(real):
    def ingest(self, u, v, t):
        return None
    return ingest


def ingest_half(real):
    def ingest(self, u, v, t):
        h = (len(u) + 1) // 2
        return real(self, u[:h], v[:h], t[:h])
    return ingest


def snapshot_altered(real):
    def snapshot(self, *, final=False):
        res = real(self, final=final)
        return dataclasses.replace(res, counts=alter(res.counts))
    return snapshot


FAULTS = [
    ("email-eu.batch", "repro.core.engine.PTMTEngine.discover",
     discover_altered),
    ("email-eu.batch", "repro.core.engine.PTMTEngine.discover",
     discover_half),
    ("sms-a.stream", "repro.core.streaming.StreamingMiner.ingest",
     ingest_nothing),
    ("sms-a.stream", "repro.core.streaming.StreamingMiner.ingest",
     ingest_half),
    ("sms-a.stream", "repro.core.streaming.StreamingMiner.snapshot",
     snapshot_altered),
]


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_a_sound_run_is_correct(workload):
    out = run(workload)
    assert out["correct"] and out["failed"] == 0
    assert out["check"]["count_l1_max"] == [0, "<=", 0]
    assert list(out)[-1] == "check"


@pytest.mark.parametrize("workload, target, fault", FAULTS,
                         ids=[f[2].__name__ for f in FAULTS])
def test_a_planted_fault_is_not_correct(monkeypatch, workload, target,
                                        fault):
    import importlib

    mod, cls, attr = target.rsplit(".", 2)
    klass = getattr(importlib.import_module(mod), cls)
    monkeypatch.setattr(klass, attr, fault(getattr(klass, attr)))
    out = run(workload)
    assert not out["correct"]
    assert out["failed"] == out["attempted"] >= 1
    assert out["check"]["count_l1_max"][0] > 0


def test_compare_reads_the_widest_gap_and_counts_wrong_answers():
    from bench import check

    want = {"01": 5, "0112": 2}
    answers = [dict(want), {"01": 4, "0112": 2, "0102": 1}, {"01": 5}]
    numbers, failed = check.compare(answers, want)
    assert numbers == {"count_l1_max": [2, "<=", 0],
                       "answers_checked": [3, ">=", 1]}
    assert failed == 2 and not check.passed(numbers)
    numbers, failed = check.compare([dict(want)], want)
    assert check.passed(numbers) and failed == 0
    numbers, _ = check.compare([], want)
    assert not check.passed(numbers)
