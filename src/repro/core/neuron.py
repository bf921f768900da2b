"""AdExp-I&F neuron + 4-type DPI synapse dynamics (paper §IV, refs [2,17,29]).

The chip implements, per computing node: four DPI log-domain filters (one per
synapse type: fast-exc, slow-exc, subtractive-inh, shunting-inh) feeding one
Adaptive-Exponential Integrate & Fire neuron. We simulate the same structure
with exponential-Euler updates inside ``jax.lax.scan``.

Units are SI-ish but arbitrary-scaled (subthreshold analog circuits are tuned
by bias currents, not physical constants); defaults give biologically
plausible dynamics (tau_m ~ 20 ms, synaptic taus from 5 ms to 100 ms, matching
the paper's "fractions of us to hundreds of ms" range).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.two_stage import N_SYN_TYPES

__all__ = ["NeuronParams", "NeuronState", "init_state", "neuron_step"]


@dataclasses.dataclass(frozen=True)
class NeuronParams:
    dt: float = 1e-3  # simulation step [s]
    # AdExp membrane
    tau_m: float = 20e-3
    v_rest: float = -70e-3
    v_thresh: float = -50e-3  # exponential take-off V_T
    delta_t: float = 2e-3  # sharpness
    v_peak: float = 0.0  # spike detection
    v_reset: float = -65e-3
    refrac: float = 2e-3  # refractory period [s]
    # adaptation (negative-feedback block)
    tau_w: float = 100e-3
    a_adapt: float = 2.0  # subthreshold coupling [1/s scale]
    b_adapt: float = 8e-3  # spike-triggered increment [V equivalent]
    # DPI synapses: time constants + weights per type
    tau_syn: tuple[float, float, float, float] = (5e-3, 100e-3, 10e-3, 20e-3)
    w_syn: tuple[float, float, float, float] = (1.0, 0.3, 1.0, 1.0)
    shunt_gain: float = 5.0  # shunting inhibition multiplies leak conductance
    input_gain: float = 0.12  # synaptic current -> membrane drive [V/s per unit]


@dataclasses.dataclass
class NeuronState:
    v: jax.Array  # [..., N] membrane potential
    w: jax.Array  # [..., N] adaptation variable
    refrac: jax.Array  # [..., N] remaining refractory time
    i_syn: jax.Array  # [..., N, 4] DPI filter states


jax.tree_util.register_dataclass(
    NeuronState, data_fields=["v", "w", "refrac", "i_syn"], meta_fields=[]
)


def init_state(
    n: int,
    params: NeuronParams,
    dtype=jnp.float32,
    batch: int | tuple[int, ...] | None = None,
) -> NeuronState:
    """Fresh state for ``n`` neurons; ``batch`` prepends leading batch dims
    (B independent network instances sharing one set of routing tables)."""
    lead = () if batch is None else (batch,) if isinstance(batch, int) else tuple(batch)
    return NeuronState(
        v=jnp.full((*lead, n), params.v_rest, dtype=dtype),
        w=jnp.zeros((*lead, n), dtype=dtype),
        refrac=jnp.zeros((*lead, n), dtype=dtype),
        i_syn=jnp.zeros((*lead, n, N_SYN_TYPES), dtype=dtype),
    )


@jax.named_scope("neuron_update")
def neuron_step(
    state: NeuronState,
    drive: jax.Array,  # [..., N, 4] matched-event weight per synapse type (stage-2 output)
    params: NeuronParams,
    i_ext: jax.Array | None = None,  # [..., N] external (DC) input current
) -> tuple[NeuronState, jax.Array]:
    """One exponential-Euler step; returns (new_state, spikes[..., N] float32).

    Purely elementwise over the leading dims, so a batched state steps all
    instances at once with no outer vmap.
    """
    p = params
    dt = p.dt
    taus = jnp.asarray(p.tau_syn, dtype=state.i_syn.dtype)
    ws = jnp.asarray(p.w_syn, dtype=state.i_syn.dtype)

    # DPI filters: exponential decay + weighted pulse injection (PE -> DPI).
    decay = jnp.exp(-dt / taus)
    i_syn = state.i_syn * decay + drive * ws

    i_fast, i_slow, i_sub, i_shunt = (i_syn[..., k] for k in range(N_SYN_TYPES))
    exc = i_fast + i_slow
    leak_gain = 1.0 + p.shunt_gain * i_shunt  # shunting = divisive inhibition
    i_in = p.input_gain * (exc - i_sub)
    if i_ext is not None:
        i_in = i_in + i_ext

    # AdExp membrane (clip the exponential for numerical safety).
    v = state.v
    exp_term = p.delta_t * jnp.exp(jnp.clip((v - p.v_thresh) / p.delta_t, -20.0, 20.0))
    dv = (-(v - p.v_rest) * leak_gain + exp_term - state.w) / p.tau_m + i_in
    v_new = v + dt * dv
    # adaptation
    dw = (p.a_adapt * (v - p.v_rest) - state.w) / p.tau_w
    w_new = state.w + dt * dw

    in_refrac = state.refrac > 0.0
    v_new = jnp.where(in_refrac, p.v_reset, v_new)
    spikes = (v_new >= p.v_peak) & ~in_refrac
    spikes_f = spikes.astype(v_new.dtype)

    v_out = jnp.where(spikes, p.v_reset, v_new)
    w_out = jnp.where(spikes, w_new + p.b_adapt, w_new)
    refrac_out = jnp.where(spikes, p.refrac, jnp.maximum(state.refrac - dt, 0.0))

    return NeuronState(v=v_out, w=w_out, refrac=refrac_out, i_syn=i_syn), spikes_f
