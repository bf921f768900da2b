"""The benchmark's plain reference describes the network the program serves.

The reference (bench/configs/tablev_cnn.py) builds the Table-V CNN from its
published description and imports nothing of the program; here, and only
here, the two are held side by side: the same dense connectivity, the same
readout selection, the same neuron and serving parameters, and the same
mesh delays.
"""

import dataclasses
import json

import numpy as np
import pytest

from _bench_path import cell as make_cell

CONFIGS = ["tablev-2res-fused", "tablev-3x3-fabric", "tablev-2res-fused-fleet4"]


def _cell(config):
    return make_cell(config, "backlog")


@pytest.fixture(scope="module")
def program_models():
    from repro.serve.aer import table_v_models

    seeds = {_cell(c).cfg["network_seed"] for c in CONFIGS}
    assert len(seeds) == 1
    return table_v_models(np.random.default_rng(seeds.pop()))


@pytest.fixture(scope="module")
def reference():
    cell = _cell("tablev-2res-fused")
    return cell.reference().build(cell.cfg)


def test_reference_network_is_the_served_network(program_models, reference):
    from repro.core.event_engine import dense_weights_from_tables

    n = reference.lay.n
    want = reference.w_by_delay[0].reshape(2 * n, 2 * n, 4)[:n, :n]  # [src, dst, syn]
    for name in ("tableV-3x3", "tableV-2x2"):
        got = dense_weights_from_tables(program_models[name].tables)  # [dst, src, syn]
        np.testing.assert_array_equal(got.transpose(1, 0, 2), want, err_msg=name)


def test_reference_input_taps_are_the_served_taps(program_models, reference):
    cc = program_models["tableV-3x3"]
    rng = np.random.default_rng(0)
    ev = rng.integers(0, 32, (16, 2))
    act = cc.input_activity(ev)  # [nc, K]: per-cluster pixel counts
    from repro.core.two_stage import stage2_cam_match

    tables = cc.tables
    drive = np.asarray(stage2_cam_match(act, tables.cam_tag, tables.cam_syn,
                                        tables.cluster_size))
    counts = np.bincount(ev[:, 0] * 32 + ev[:, 1], minlength=1024).astype(np.float32)
    want = (counts @ reference.w_in).reshape(-1, 4)
    np.testing.assert_array_equal(drive, want)


def test_configurations_state_the_program_parameters():
    from repro.core.cnn import CnnConfig, poker_neuron_params
    from repro.serve.aer import AerServeConfig

    p = dataclasses.asdict(poker_neuron_params())
    s = AerServeConfig()
    c = CnnConfig()
    for name in CONFIGS:
        cfg = _cell(name).cfg
        assert cfg["neuron"] == {k: list(v) if isinstance(v, tuple) else v for k, v in p.items()}
        assert cfg["serve"] == {"drive": s.drive, "threshold": s.decision_threshold,
                                "min_steps": s.min_steps, "max_steps": s.max_steps}
        net = cfg["network"]
        assert (net["input_hw"], net["n_kernels"], net["kernel"], net["stride"],
                net["conv_hw"], net["pool"], net["n_classes"], net["pop_per_class"]) == (
            c.input_hw, c.n_kernels, c.kernel, c.stride, c.conv_hw, c.pool,
            c.n_classes, c.pop_per_class)
        assert (cfg["cluster_size"], cfg["k_tags"], cfg["cam_words"], cfg["sram_entries"]) == (
            c.cluster_size, c.k_tags, c.max_cam_words, c.max_sram_entries)


def test_mesh_delays_are_the_fabric_delays(program_models):
    from repro.serve.aer import build_poker_engine

    cell = _cell("tablev-3x3-fabric")
    mesh = cell.cfg["mesh"]
    eng = build_poker_engine(program_models["tableV-3x3"].tables, "fabric")
    model = eng.fabric_model
    assert list(model.tile_of_cluster) == mesh["tile_of_cluster"]
    fab = eng.fabric_backend.fabric
    assert (fab.grid_x, fab.grid_y, fab.cores_per_tile) == (
        mesh["grid_x"], mesh["grid_y"], mesh["cores_per_tile"])
    assert fab.constants.latency_across_chip_s == mesh["hop_latency_s"]
    ref = cell.reference()
    lay = ref.layout_of(cell.cfg["network"])
    masks = ref._delay_mask(cell.cfg, lay)
    cl = np.arange(lay.n) // cell.cfg["cluster_size"]
    want = model.delay_steps[cl[:, None], cl[None, :]]
    for d, m in masks.items():
        assert np.all(want[m] == d)


def test_reference_files_import_nothing_of_the_program():
    from bench.spec import BENCH

    for path in (BENCH / "configs").glob("*.py"):
        text = path.read_text()
        assert "repro" not in text, path
    for path in (BENCH / "configs").glob("*.json"):
        json.loads(path.read_text())
