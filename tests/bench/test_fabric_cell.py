"""The fabric cell ``tablev-3x3-fabric.backlog``: a traced 4 s window of it on
one v5e, reduced and kept with what its run printed
(``chip_trace_fabric.json``, written by ``record_chip_trace.py``).

The cell reports the accepted metrics and its own kernel's roofline, and
neither delivery kernel's roofline leaks into the other's cell. The record
also pins what the mesh adds: the pool's ``delivered`` and ``mesh_hops``
counters over the window, and the scope of the chip's kernel op.
"""

import json
import pathlib
import types

import _bench_path  # noqa: F401
import pytest

from bench import spec
from bench.metrics import _trace

HERE = pathlib.Path(__file__).parent
FABRIC, FUSED = "tablev-3x3-fabric.backlog", "tablev-2res-fused.backlog"
LAYERS = ("host_gather_ms", "host_finish_ms", "device_step_ms",
          "fabric_deliver_roofline", "device_idle", "step_mfu")


def _load(name: str) -> dict:
    return json.loads((HERE / name).read_text())


@pytest.fixture(scope="module")
def rec():
    return _load("chip_trace_fabric.json")


def _run(rec):
    trace = _trace.Trace.from_json(json.dumps(rec["trace"]))
    return types.SimpleNamespace(decided=[], window_s=rec["window_s"], steps=rec["steps"],
                                 cell=spec.Cell(rec["workload"]), spans=rec["spans"],
                                 trace_data=trace, device=rec["device"])


def test_the_cell_reports_its_metrics(rec):
    assert rec["workload"] == FABRIC
    cell = spec.Cell(FABRIC)
    assert {m["name"] for m in cell.end_to_end} == {"sessions_per_s", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == set(LAYERS)
    assert rec["compared"]["decision_mismatches"]["value"] == 0


@pytest.mark.parametrize("name", LAYERS)
def test_reader_gives_the_printed_number(rec, name):
    value = spec.reader(name).read(_run(rec))
    assert value is not None
    assert value == rec["metrics"][name]


def test_fabric_roofline_reads_the_chips_kernel_op(rec):
    run = _run(rec)
    assert _trace.breakdown(run.trace_data) == rec["breakdown"]
    calls = _trace.kernel_events(run.trace_data, "fabric_deliver")
    assert {e[0] for e in calls} == {"fabric_deliver.1"}
    assert len(calls) == rec["steps"]  # one kernel call a pool step
    assert "deliver" in rec["trace"]["scopes"]["fabric_deliver.1"].split("/")
    assert 0 < spec.reader("fabric_deliver_roofline").read(run) <= 100


def test_fused_roofline_is_not_reported_for_the_fabric_cell(rec):
    assert "fused_deliver_roofline" not in {m["name"] for m in spec.Cell(FABRIC).per_layer}
    assert spec.reader("fused_deliver_roofline").read(_run(rec)) is None


def test_fabric_roofline_is_not_reported_for_the_fused_cell():
    assert "fabric_deliver_roofline" not in {m["name"] for m in spec.Cell(FUSED).per_layer}
    fused = _load("chip_trace_backlog.json")
    assert spec.reader("fabric_deliver_roofline").read(_run(fused)) is None


def test_mesh_counters_count_the_window(rec):
    """Every delivered SRAM entry of the window is counted, and those from
    the conv layer's cores (chip (0,0)) to the pooling core (chip (1,0))
    cross one chip boundary each; no link drops at the cell's rates."""
    c = rec["counters"]
    assert c["steps"] == rec["steps"]
    assert 0 < c["mesh_hops"] < c["delivered"]
    assert c["link_dropped"] == c["queue_dropped"] == 0
