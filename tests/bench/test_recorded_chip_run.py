"""A traced 4 s window of ``tablev-2res-fused.backlog`` on one v5e, reduced
and kept with what its result line printed (``chip_trace_backlog.json``).

The accepted readers and the breakdown give that run's printed numbers
again, and the record pins the names the chip gives: the kernel op the
roofline matches, and the program's own host spans and device scopes,
which the reduction that recorded it also kept (``program``: ``repro.*``
spans; ``scopes``: each op's ``op_name`` path from the compiled step).
"""

import json
import pathlib
import types

import _bench_path  # noqa: F401
import pytest

from bench import spec
from bench.metrics import _trace

RECORDED = pathlib.Path(__file__).with_name("chip_trace_backlog.json")
ACCEPTED = ("host_gather_ms", "host_finish_ms", "device_step_ms",
            "fused_deliver_roofline", "device_idle", "step_mfu")
STEP_SPANS = ("repro.pool.gather", "repro.pool.dispatch",
              "repro.pool.readback", "repro.pool.readout")


@pytest.fixture(scope="module")
def rec():
    return json.loads(RECORDED.read_text())


def _run(rec):
    trace = _trace.Trace.from_json(json.dumps(rec["trace"]))
    return types.SimpleNamespace(decided=[], window_s=rec["window_s"], steps=rec["steps"],
                                 cell=spec.Cell(rec["workload"]), spans=rec["spans"],
                                 trace_data=trace, device=rec["device"])


def test_the_cell_reports_the_recorded_metrics(rec):
    assert {m["name"] for m in spec.Cell(rec["workload"]).per_layer} == set(ACCEPTED)


@pytest.mark.parametrize("name", ACCEPTED)
def test_reader_gives_the_printed_number(rec, name):
    value = spec.reader(name).read(_run(rec))
    assert value is not None
    assert value == rec["metrics"][name]


def test_breakdown_is_the_printed_one(rec):
    run = _run(rec)
    assert _trace.breakdown(run.trace_data) == rec["breakdown"]
    # the kernel is traced under its pallas_call's own name
    kernel = [op for op, _ in rec["breakdown"]["device_ops"] if op.startswith("fused_deliver")]
    assert kernel == ["fused_deliver.1"]


def test_program_spans_nest_under_their_step(rec):
    """Every pool step in the window holds one gather, dispatch, readback
    and readout, in that order, inside its ``repro.pool.step``."""
    program = sorted(rec["trace"]["program"], key=lambda s: s[1])
    steps = [s for s in program if s[0] == "repro.pool.step"]
    assert len(steps) >= rec["steps"] - 1
    for name, start, dur in steps:
        inner = [s[0] for s in program
                 if s[0] in STEP_SPANS and start <= s[1] and s[1] + s[2] <= start + dur]
        assert inner == list(STEP_SPANS)


def test_device_ops_carry_the_programs_scopes(rec):
    """The compaction loop and the kernel run under ``deliver``; the
    compaction's ops under ``compact``, the neuron update under its own."""
    scopes = rec["trace"]["scopes"]
    parts = {p for path in scopes.values() for p in path.split("/")}
    assert {"deliver", "compact", "stage1", "neuron_update"} <= parts
    assert "compact" in scopes["while.4"].split("/")
    assert "deliver" in scopes["fused_deliver.1"].split("/")
