"""The work a pool step needs, from the configuration's shapes alone.

Counts are the algorithm's, whatever implements it, so a roofline built on
them stays fixed across implementations:

* stage 1: one add per (slot, source neuron, SRAM entry);
* stage 2: one lookup-and-add per (slot, neuron, CAM word);
* the neuron update: ``NEURON_OPS`` operations per (slot, neuron).

Bytes: the routing tables read once per step (int32), and each slot's
external activity, spikes and synaptic drive (float32); a mesh deployment's
delay ring is read and written once. The neuron update reads and writes the
state (membrane, adaptation, refractory time, four synapse filters) and
reads the drive.
"""

from __future__ import annotations

import math

NEURON_OPS = 30  # 4 filter decays and injections, the AdExp update, the spike test
F32 = 4


def dims(cfg: dict) -> dict:
    net = cfg["network"]
    conv = net["n_kernels"] * net["conv_hw"] ** 2
    pool = net["n_kernels"] * (net["conv_hw"] // net["pool"]) ** 2
    n = (conv + pool + net["n_classes"] * net["pop_per_class"]) * len(cfg["models"])
    mesh = cfg.get("mesh")
    return {
        "slots": cfg["pool_size"],
        "neurons": n,
        "clusters": n // cfg["cluster_size"],
        "k": cfg["k_tags"],
        "cam": cfg["cam_words"],
        "sram": cfg["sram_entries"],
        "ring": 0 if mesh is None else 1 + _max_delay(cfg),
    }


def _max_delay(cfg: dict) -> int:
    mesh = cfg["mesh"]
    tiles = mesh["tile_of_cluster"]
    gx = mesh["grid_x"]
    hops = max(abs(a % gx - b % gx) + abs(a // gx - b // gx) for a in tiles for b in tiles)
    return max(0, math.ceil(hops * mesh["hop_latency_s"] / cfg["neuron"]["dt"] - 1e-9))


def deliver(cfg: dict) -> tuple[float, float]:
    """(operations, bytes) of one delivery over the whole pool."""
    d = dims(cfg)
    p, n = d["slots"], d["neurons"]
    ops = p * n * d["sram"] + p * n * d["cam"]
    tables = n * (d["sram"] + d["cam"]) * 2 * F32
    activity = p * d["clusters"] * d["k"] * F32
    ring = 2 * p * d["ring"] * d["clusters"] * d["k"] * F32
    spikes = p * n * F32
    drive = p * n * 4 * F32
    return float(ops), float(tables + activity + ring + spikes + drive)


def neuron(cfg: dict) -> tuple[float, float]:
    d = dims(cfg)
    cells = d["slots"] * d["neurons"]
    return float(cells * NEURON_OPS), float(cells * (7 + 7 + 4 + 1) * F32)


def bound_s(work: tuple[float, float], peak: dict) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    ops, nbytes = work
    return max(ops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def step(cfg: dict) -> tuple[float, float]:
    a, b = deliver(cfg), neuron(cfg)
    return a[0] + b[0], a[1] + b[1]
