"""Metric arithmetic: rates over all the work and all the window, tails
over all sessions, the required-work count, and the trace reduction on a
small trace recorded on the chip."""

import json
import types

import _bench_path
import numpy as np
import pytest

from bench import spec
from bench.harness import Decided
from bench.metrics import _trace, _work



def _run(decided, window_s=10.0, steps=100, cell="tablev-2res-fused.backlog", **kw):
    return types.SimpleNamespace(decided=decided, window_s=window_s, steps=steps,
                                 cell=_bench_path.cell(*cell.split(".")), spans=kw.get("spans", {}),
                                 trace_data=kw.get("trace"), setup_s=kw.get("setup_s", 0.0),
                                 device=kw.get("device", {}))


def _decided(n, rng):
    due = np.sort(rng.uniform(0, 10, n))
    lat = rng.gamma(4.0, 0.3, n)
    return [Decided(i, due[i], due[i], due[i] + 0.01, due[i] + lat[i], 0, None)
            for i in range(n)]


def test_rate_is_all_decisions_over_the_whole_window():
    rng = np.random.default_rng(0)
    run = _run(_decided(257, rng), window_s=10.25)
    assert spec.reader("sessions_per_s").read(run) == pytest.approx(257 / 10.25)


def test_tails_are_over_all_sessions_not_chunks():
    rng = np.random.default_rng(1)
    dec = _decided(400, rng)
    run = _run(dec, cell="tablev-2res-fused.poisson")
    lat = np.array([(d.done - d.due) * 1e3 for d in dec])
    p95 = spec.reader("decision_ms_p95").read(run)
    assert p95 == pytest.approx(np.percentile(lat, 95))
    chunks = np.median([np.percentile(c, 95) for c in np.array_split(lat, 8)])
    assert p95 != pytest.approx(chunks)
    assert spec.reader("decision_ms_p50").read(run) == pytest.approx(np.median(lat))
    wait = np.array([(d.admitted - d.due) * 1e3 for d in dec])
    assert spec.reader("queue_wait_ms_p95").read(run) == pytest.approx(np.percentile(wait, 95))


def test_knee_sweep_line_by_hand():
    from bench import knee

    lat = np.arange(1.0, 201.0)  # 200 sessions, 1..200 ms
    line = knee.summary(24.0, 15.0, 60, 300, 3, 9, lat)
    assert line["completed_per_s"] == pytest.approx(20.0)
    assert line["step_ms"] == pytest.approx(250.0)
    assert line["queue_grew"] and not knee.summary(24.0, 15.0, 60, 300, 9, 3, lat)["queue_grew"]
    assert line["decision_ms_p50"] == pytest.approx(100.5)
    assert line["decision_ms_p95"] == pytest.approx(190.05)
    empty = knee.summary(24.0, 15.0, 0, 0, 0, 0, np.array([]))
    assert empty["decision_ms_p95"] is None and empty["completed_per_s"] == 0.0


def test_nothing_to_read_reads_nothing():
    run = _run([], window_s=10.0, steps=0)
    for name in ("decision_ms_p50", "pool_step_ms.open", "host_gather_ms", "device_step_ms",
                 "device_idle", "step_mfu", "fused_deliver_roofline"):
        assert spec.reader(name).read(run) is None, name


def test_required_work_matches_a_hand_count():
    cfg = json.loads(json.dumps(_bench_path.cell("tablev-3x3-fabric", "backlog").cfg))
    cfg["pool_size"] = 2
    d = _work.dims(cfg)
    assert (d["neurons"], d["clusters"], d["ring"]) == (1536, 6, 2)
    ops, nbytes = _work.deliver(cfg)
    assert ops == 2 * 1536 * 16 + 2 * 1536 * 64
    tables = 1536 * (16 + 64) * 2 * 4
    per_slot = 6 * 1024 * 4 + 2 * 2 * 6 * 1024 * 4 + 1536 * 4 + 1536 * 4 * 4
    assert nbytes == tables + 2 * per_slot
    assert _work.neuron(cfg) == (2 * 1536 * 30, 2 * 1536 * 19 * 4)
    fused = json.loads(json.dumps(_bench_path.cell("tablev-2res-fused", "backlog").cfg))
    assert _work.dims(fused)["neurons"] == 3072 and _work.dims(fused)["ring"] == 0
    peak = spec.peaks("TPU v5 lite")
    assert _work.bound_s((197e12, 1.0), peak) == pytest.approx(1.0)
    assert _work.bound_s((1.0, 819e9), peak) == pytest.approx(1.0)


def test_unknown_device_is_an_error():
    with pytest.raises(SystemExit):
        spec.peaks("TPU v9 imaginary")


def test_trace_reduction_by_hand():
    ms = 1_000_000
    tr = _trace.Trace(
        (0, 100 * ms),
        {0: [("fused_deliver_pallas.1", 10 * ms, 30 * ms), ("fusion.1", 40 * ms, 10 * ms),
             ("fused_deliver_pallas.1", 60 * ms, 30 * ms)]},
        [("bench.gather_inputs", 0, 10 * ms), ("bench.finish_step", 10 * ms, 45 * ms),
         ("bench.gather_inputs", 50 * ms, 10 * ms), ("bench.finish_step", 60 * ms, 40 * ms)],
    )
    assert _trace.busy_ns(tr, 0) == 70 * ms
    assert _trace.mean_busy_s(tr) == pytest.approx(0.07)
    bd = _trace.breakdown(tr)
    assert bd["device_ops"][0] == ["fused_deliver_pallas.1", pytest.approx(0.06)]
    idle = dict(bd["idle_gaps"])
    assert idle["gather_inputs"] == pytest.approx(0.02)
    assert idle["finish_step"] == pytest.approx(0.01)
    assert sum(idle.values()) == pytest.approx(0.03)
    assert _trace.op_name('%fused_deliver_pallas.1 = f32[2]{0} custom-call(...)') == \
        "fused_deliver_pallas.1"
    nested = _trace.top_level([("while.4", 0, 10), ("fusion.2", 2, 3), ("copy.1", 10, 2)])
    assert nested == [("while.4", 0, 10), ("copy.1", 10, 2)]


# events as a v5e's trace gives them (``XLA Ops`` line, ns), from a traced
# step of tablev-2res-fused.backlog: the step's queue compaction loop with
# an op nested in it, then the fused kernel
CHIP_OPS = [
    ("%convert_reduce_fusion = (s32[128]{0:T(128)}, pred[128,3072]{1,0:T(8,128)(4,1)S(1)}) "
     "fusion(f32[128,3072]{1,0:T(8,128)} %carry_1_.1), kind=kLoop", 72590274, 3828),
    ("%while.4 = (s32[]{:T(128)}, s32[128,3072]{1,0:T(8,128)S(1)}) while(%tuple.31), "
     "condition=%wide.region_2.7.clone, body=%wide.region_1.6.clone.sunk", 72600000, 48000000),
    ("%fusion.42 = s32[393216]{0:T(1024)S(1)} fusion(%get-tuple-element.105, %bitcast.64), "
     "kind=kCustom", 72700000, 47000000),
    ("%fused_deliver_pallas.1 = f32[128,12,256,4]{3,2,1,0:T(8,128)} custom-call(%custom-call.16, "
     "%copy.34, %copy.35, %custom-call.21, %custom-call.22), custom_call_target=\"tpu_custom_call\"",
     120700000, 142600000),
]


def test_reduction_of_ops_in_the_chips_format():
    ops = _trace.device_ops(CHIP_OPS)
    assert [o[0] for o in ops] == ["convert_reduce_fusion", "while.4", "fused_deliver_pallas.1"]
    lo, hi = 72590274 - 1_000_000, 72590274 + 219_000_000
    tr = _trace.Trace((lo, hi), {0: ops}, [("bench.finish_step", lo, hi - lo)])
    run = _run([], window_s=tr.window_s, steps=1, trace=tr,
               device={"kind": "TPU v5 lite", "count": 1})
    busy = 3828 + 48_000_000 + 142_600_000
    assert spec.reader("device_step_ms").read(run) == pytest.approx(busy * 1e-6)
    assert spec.reader("device_idle").read(run) == pytest.approx(100 * (1 - busy / (hi - lo)))
    need = _work.bound_s(_work.deliver(run.cell.cfg), spec.peaks("TPU v5 lite"))
    share = spec.reader("fused_deliver_roofline").read(run)
    assert share == pytest.approx(100 * need / 0.1426)
    assert 0 < share < 100
    assert spec.reader("fabric_deliver_roofline").read(run) is None
    assert 0 < spec.reader("step_mfu").read(run) < 100
    assert dict(_trace.breakdown(tr)["idle_gaps"]) == {"finish_step": pytest.approx((hi - lo - busy) * 1e-9)}

