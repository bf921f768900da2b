"""Two-stage dispatch == dense connectivity (the paper's core claim)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st  # degrades to skip without hypothesis

from repro.core.event_engine import (
    EventEngine,
    dense_reference_step,
    dense_weights_from_tables,
)
from repro.core.neuron import NeuronParams, init_state
from repro.core.tags import NetworkSpec, compile_network
from repro.core.dispatch import two_stage_deliver
from repro.core.two_stage import stage1_route, stage2_cam_match


def _tables(seed, n=48, cluster=16, k=48, edges=60):
    rng = np.random.default_rng(seed)
    spec = NetworkSpec(n_neurons=n, cluster_size=cluster, k_tags=k,
                       max_cam_words=24, max_sram_entries=16)
    seen = set()
    for _ in range(edges):
        s, d = int(rng.integers(n)), int(rng.integers(n))
        if (s, d) in seen:
            continue
        seen.add((s, d))
        spec.connect(s, d, int(rng.integers(4)))
    return compile_network(spec)


@given(seed=st.integers(0, 500), spike_p=st.floats(0.0, 1.0))
@settings(max_examples=25, deadline=None)
def test_two_stage_equals_dense(seed, spike_p):
    tables = _tables(seed)
    rng = np.random.default_rng(seed + 7)
    spikes = (rng.random(tables.n_neurons) < spike_p).astype(np.float32)
    drive = two_stage_deliver(
        jnp.asarray(spikes),
        jnp.asarray(tables.src_tag),
        jnp.asarray(tables.src_dest),
        jnp.asarray(tables.cam_tag),
        jnp.asarray(tables.cam_syn),
        tables.cluster_size,
        tables.k_tags,
    )
    dense = dense_weights_from_tables(tables)
    ref = jnp.einsum("dst,s->dt", jnp.asarray(dense), jnp.asarray(spikes))
    np.testing.assert_allclose(np.asarray(drive), np.asarray(ref), rtol=1e-6)


def test_stage1_drops_invalid_entries():
    src_tag = jnp.asarray([[0, -1], [1, 2]], jnp.int32)
    src_dest = jnp.asarray([[1, -1], [0, 1]], jnp.int32)
    a = stage1_route(jnp.asarray([1.0, 2.0]), src_tag, src_dest, n_clusters=2, k_tags=4)
    expect = np.zeros((2, 4), np.float32)
    expect[1, 0] = 1.0  # neuron 0, entry 0
    expect[0, 1] = 2.0  # neuron 1, entry 0
    expect[1, 2] = 2.0  # neuron 1, entry 1
    np.testing.assert_allclose(np.asarray(a), expect)


def test_engine_dynamics_match_dense_reference():
    """Full engine step == dense-delivery reference step over several steps."""
    tables = _tables(3)
    dense = jnp.asarray(dense_weights_from_tables(tables))
    params = NeuronParams()
    eng = EventEngine(tables, params)
    carry = eng.init_state()
    state_ref = init_state(tables.n_neurons, params)
    spikes_ref = jnp.zeros((tables.n_neurons,))
    ext = jnp.zeros((tables.n_clusters, tables.k_tags)).at[:, 0].set(4.0)
    ext_drive = stage2_cam_match(
        ext, jnp.asarray(tables.cam_tag), jnp.asarray(tables.cam_syn), tables.cluster_size
    )
    for _ in range(30):
        carry, spikes = eng.step(carry, ext)
        state_ref, spikes_ref = dense_reference_step(
            dense, spikes_ref, state_ref, params, external_drive=ext_drive
        )
        np.testing.assert_allclose(np.asarray(spikes), np.asarray(spikes_ref), atol=1e-6)
    assert not bool(jnp.isnan(carry[0].v).any())


def test_engine_run_scan_no_nan():
    tables = _tables(11)
    eng = EventEngine(tables)
    carry = eng.init_state()
    inp = jnp.zeros((50, tables.n_clusters, tables.k_tags)).at[:, :, :4].set(2.0)
    carry, out = eng.run(carry, inp)
    assert out.shape == (50, tables.n_neurons)
    assert not bool(jnp.isnan(out).any())


def test_engine_run_time_varying_i_ext_matches_per_step():
    """Regression: a [T, ...] external current must be scanned per step, not
    broadcast whole every step (which silently mis-applied all T currents at
    once)."""
    tables = _tables(13)
    eng = EventEngine(tables)
    t = 20
    rng = np.random.default_rng(0)
    i_ext = jnp.asarray(
        rng.uniform(0, 3e3, size=(t, tables.n_neurons)), jnp.float32
    )
    inp = jnp.zeros((t, tables.n_clusters, tables.k_tags)).at[:, :, :4].set(2.0)
    _, out_run = eng.run(eng.init_state(), inp, i_ext)
    carry = eng.init_state()
    per_step = []
    for step in range(t):
        carry, spikes = eng.step(carry, inp[step], i_ext[step])
        per_step.append(np.asarray(spikes))
    np.testing.assert_array_equal(np.asarray(out_run), np.stack(per_step))
    assert np.asarray(out_run).sum() > 0  # the current did drive spikes
    # constant (non-time-varying) i_ext still broadcasts as before
    _, out_const = eng.run(eng.init_state(), inp, i_ext[0])
    carry = eng.init_state()
    for step in range(t):
        carry, spikes = eng.step(carry, inp[step], i_ext[0])
    np.testing.assert_array_equal(np.asarray(out_const[-1]), np.asarray(spikes))
    # a batched per-stream constant [B, N] with B == T must NOT be misread
    # as a time series (it has the spike state's rank, not rank + 1)
    b = t
    i_const = jnp.asarray(
        rng.uniform(0, 3e3, size=(b, tables.n_neurons)), jnp.float32
    )
    inp_b = jnp.broadcast_to(
        inp[:, None], (t, b, tables.n_clusters, tables.k_tags)
    )
    _, out_b = eng.run(eng.init_state(batch=b), inp_b, i_const)
    carry = eng.init_state(batch=b)
    for step in range(t):
        carry, spikes_b = eng.step(carry, inp_b[step], i_const)
    np.testing.assert_array_equal(np.asarray(out_b[-1]), np.asarray(spikes_b))


def test_inhibition_reduces_firing():
    """Subtractive-inhibition events must not increase firing (paper §IV-A)."""
    spec = NetworkSpec(n_neurons=16, cluster_size=16, k_tags=16, max_cam_words=8)
    tables = compile_network(spec)
    # neuron 0: excitatory input tag 0; neuron 1: same + inhibitory tag 1
    cam_tag = tables.cam_tag.copy()
    cam_syn = tables.cam_syn.copy()
    cam_tag[0, 0], cam_syn[0, 0] = 0, 0
    cam_tag[1, 0], cam_syn[1, 0] = 0, 0
    cam_tag[1, 1], cam_syn[1, 1] = 1, 2  # subtractive inh
    import dataclasses

    tables = dataclasses.replace(tables, cam_tag=cam_tag, cam_syn=cam_syn)
    eng = EventEngine(tables)
    carry = eng.init_state()
    inp = jnp.zeros((400, 1, 16)).at[:, :, 0].set(3.0).at[:, :, 1].set(3.0)
    _, out = eng.run(carry, inp)
    assert float(out[:, 1].sum()) <= float(out[:, 0].sum())
    assert float(out[:, 0].sum()) > 0  # excitation drives spiking
