"""Quickstart: build a two-stage tag-routed network and run it.

Demonstrates the paper's §II claim end-to-end:
  1. describe clustered connectivity,
  2. compile to distributed SRAM/CAM routing tables,
  3. run the event engine and verify against dense connectivity,
  4. compare memory against conventional (flat-address) routing,
  5. serve a batch of independent event streams in one dispatch
     (the batched, backend-pluggable delivery path).

Run: PYTHONPATH=src python examples/quickstart.py
"""

import jax.numpy as jnp
import numpy as np

from repro.core import memory_model as mm
from repro.core.event_engine import EventEngine, dense_weights_from_tables
from repro.core.tags import NetworkSpec, SynapseType, compile_network


def main():
    rng = np.random.default_rng(0)
    # 256 neurons in 4 clusters ("cores") of 64; K = 64 tags per core.
    spec = NetworkSpec(n_neurons=256, cluster_size=64, k_tags=64,
                       max_cam_words=32, max_sram_entries=8)

    # clustered connectivity: populations project within/between clusters
    for src_cluster in range(4):
        srcs = list(range(src_cluster * 64, src_cluster * 64 + 16))
        dst_cluster = (src_cluster + 1) % 4
        tgts = [(dst_cluster * 64 + i, SynapseType.FAST_EXC) for i in range(24)]
        spec.connect_group(srcs, tgts, shared_tag=True)  # 1 tag per cluster!
    # plus some specific point-to-point connections
    for _ in range(60):
        spec.connect(int(rng.integers(256)), int(rng.integers(256)),
                     int(rng.integers(4)))

    tables = compile_network(spec)
    print(f"compiled: {tables.n_neurons} neurons, {tables.n_clusters} cores")
    print(f"  source (SRAM) bits used: {tables.sram_bits()}")
    print(f"  target (CAM)  bits used: {tables.cam_bits()}")
    n_conn = len(tables.dense_equivalent())
    conv_bits = n_conn * np.log2(256)  # flat addressing needs log2(N)/connection
    print(f"  connections realized: {n_conn}; flat routing would need "
          f"{conv_bits:.0f} bits ({conv_bits / (tables.sram_bits() + tables.cam_bits()):.1f}x)")

    # theory: the same network at brain scale
    print("\npaper §II at scale (N=2^20, F=2^13, C=256):")
    print(f"  conventional: {mm.conventional_bits(2**20, 2**13):.0f} bits/neuron")
    print(f"  two-stage optimum: {mm.mem_at_optimal_m(2**20, 2**13, 256):.0f} bits/neuron "
          f"(M* = {mm.optimal_m(2**20, 2**13, 256):.0f})")

    # run the engine: stimulate cluster 0's shared tag, watch activity propagate
    eng = EventEngine(tables)
    carry = eng.init_state()
    inp = jnp.zeros((80, tables.n_clusters, tables.k_tags)).at[:, 0, :6].set(6.0)
    carry, spikes = eng.run(carry, inp)
    per_cluster = np.asarray(spikes).sum(0).reshape(4, 64).sum(1)
    print(f"\nspikes per core over 80 steps: {per_cluster} (stimulus -> core0 -> core1 ...)")

    # verify two-stage delivery == dense connectivity on a random state
    dense = dense_weights_from_tables(tables)
    s = (rng.random(256) < 0.2).astype(np.float32)
    from repro.core.dispatch import two_stage_deliver

    drive = two_stage_deliver(
        jnp.asarray(s), jnp.asarray(tables.src_tag), jnp.asarray(tables.src_dest),
        jnp.asarray(tables.cam_tag), jnp.asarray(tables.cam_syn), 64, 64,
    )
    ref = np.einsum("dst,s->dt", dense, s)
    print(f"two-stage == dense connectivity: max err = {np.abs(np.asarray(drive) - ref).max():.2e}")

    # batched serving: B independent event streams through ONE dispatch.
    # Each stream stimulates a different core; spikes stay per-stream.
    b = 4
    inp_b = jnp.zeros((80, b, tables.n_clusters, tables.k_tags))
    for stream in range(b):
        inp_b = inp_b.at[:, stream, stream % 4, :6].set(6.0)
    carry_b, spikes_b = eng.run(eng.init_state(batch=b), inp_b)
    per_stream = np.asarray(spikes_b).sum(axis=(0, 2)).astype(int)
    print(f"\nbatched run (B={b}, one stimulus core per stream): "
          f"spikes per stream = {per_stream}")
    # stream 0 stimulates core 0 exactly like the single run above
    assert np.allclose(np.asarray(spikes_b)[:, 0], np.asarray(spikes)), "stream 0 != single run"
    print("stream 0 of the batch reproduces the single-stream run exactly")

    from repro.core.dispatch import available_backends

    print(f"dispatch backends available: {', '.join(available_backends())} "
          "(EventEngine(tables, backend=...))")


if __name__ == "__main__":
    main()
