"""The per-layer metrics read from the program's spans and counters, on a
traced run of each cell at a small size on the CPU.

Every ``program_span`` and ``program_counter`` metric of a cell is
reported, and the spans inside the program split the layers the older
metrics time whole: the batch decode (``engine.d2h`` + ``engine.decode``)
lies inside ``decode_ms.batch``, and the three parts of a stream pair's
finalization cover ``stream.finalize``.
"""

import dataclasses
import time

import pytest

from bench import harness
from bench.test_bench_control import SMALL, small_cell

#: a stretch of calls that the small windows reach
TRACE_CALLS = {"email-eu.batch": [0, 1], "sms-a.stream": [1, 2]}

_runs = {}


def traced(workload):
    if workload not in _runs:
        cell = small_cell(workload)
        cell = dataclasses.replace(
            cell, mix={**cell.mix, "trace_calls": TRACE_CALLS[workload]})
        _runs[workload] = harness.run_cell(
            cell, seed=2**31 + 7, seconds=0.2, trace=True,
            t0=time.perf_counter(), look_for_chips=False,
            log=lambda msg: None)
    return _runs[workload]


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: "off")


def value(out, name):
    return out["metrics"][name]["value"]


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_every_program_metric_is_reported(workload):
    spec = harness.load_spec()
    want = {m["name"] for m in spec["per_layer"]
            if m["source"] in ("program_span", "program_counter")
            and workload in m.get("workloads", [workload])}
    new = ({"count_d2h_ms.batch", "code_decode_ms.batch"}
           if "batch" in workload else
           {"pair_layout_ms.stream", "pair_mine_ms.stream",
            "pair_merge_ms.stream", "launches_per_ingest.stream"})
    assert new <= want
    out = traced(workload)
    assert out["correct"]
    assert want <= set(out["metrics"])
    for name in want:
        assert value(out, name) > 0, name


def test_batch_decode_split_lies_inside_decode_ms():
    out = traced("email-eu.batch")
    split = value(out, "count_d2h_ms.batch") + value(
        out, "code_decode_ms.batch")
    assert split <= value(out, "decode_ms.batch")


def test_pair_split_covers_stream_finalization():
    out = traced("sms-a.stream")
    split = sum(value(out, f"pair_{part}_ms.stream")
                for part in ("layout", "mine", "merge"))
    finalize = value(out, "finalize_ms.stream")
    assert 0.9 * finalize <= split <= finalize


def test_stream_launches_per_ingest_call():
    """One dense launch per finalized pair, and the final snapshot's tail
    mine, over the window's ingest calls."""
    out = traced("sms-a.stream")
    per_call = value(out, "launches_per_ingest.stream")
    assert 0 < per_call < 3
