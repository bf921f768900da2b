"""Plain reference of the paper's Table-V spiking CNN, served as DVS sessions.

It imports nothing of the program under test and takes nothing it made: the
network is built here from its published description (paper §V, Table V),
as one dense ``[N, N, 4]`` fan-in tensor per resident model, and stepped
with straightforward ``jax.numpy`` (float32 at HIGHEST, or a lower
precision for the control):

    input 32x32 DVS events
     -> conv: 4 ternary 8x8 edge kernels, stride 2, padding 5 -> 4 x 16 x 16
     -> 2x2 pooling, weight 8 per conv neuron                  -> 4 x 8 x 8
     -> fully connected: 64 output neurons per class, each fed
        by the 64 pooling neurons most selective for the class

The readout selection is the offline-Hebbian calibration of §V, recomputed
here from the configuration's network seed. The neuron is the AdExp
integrate-and-fire unit with four DPI synapse filters (paper §IV), stepped
by exponential Euler at dt = 1 ms. Cross-tile events of a mesh deployment
arrive ``delay`` steps late, from the placement the configuration states.
The readout is the majority rule: per-class cumulative output spikes, a
decision once the leading class reaches the threshold, forced at the step cap.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

N_SYN = 4
FAST_EXC, SLOW_EXC, SUB_INH = 0, 1, 2
HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# DVS stimuli: a suit flash as an event cloud on the sensor (paper §V)
# ---------------------------------------------------------------------------
def suit_events(symbol: int, n_events: int, rng: np.random.Generator,
                input_hw: int = 32, jitter: float = 1.0) -> np.ndarray:
    """``[n_events, 2]`` (y, x) events of one suit: 0 vertical bar, 1
    horizontal bar, 2 upward vertex, 3 downward vertex."""
    s = input_hw / 32.0
    if symbol == 0:
        ys = rng.integers(int(6 * s), int(26 * s), n_events)
        xs = 15 * s + rng.normal(0, jitter, n_events)
    elif symbol == 1:
        xs = rng.integers(int(6 * s), int(26 * s), n_events)
        ys = 15 * s + rng.normal(0, jitter, n_events)
    elif symbol == 2:
        t = rng.uniform(-1, 1, n_events)
        xs = 16 * s + t * 10 * s + rng.normal(0, jitter, n_events)
        ys = 8 * s + np.abs(t) * 14 * s
    else:
        t = rng.uniform(-1, 1, n_events)
        xs = 16 * s + t * 10 * s + rng.normal(0, jitter, n_events)
        ys = 24 * s - np.abs(t) * 14 * s
    hi = input_hw - 1
    return np.stack(
        [np.clip(ys, 0, hi).astype(np.int64), np.clip(xs, 0, hi).astype(np.int64)], 1
    )


def session_pixel_counts(stream: dict, session_id: int, step: int) -> np.ndarray:
    """Per-pixel event counts ``[hw * hw]`` of one session's step: the
    stream is a pure function of (stream seed, session id, step)."""
    rng = np.random.default_rng([stream["seed"], session_id, step])
    hw = stream["input_hw"]
    ev = suit_events(stream["symbol"], stream["events_per_step"], rng, hw,
                     stream["jitter"])
    return np.bincount(ev[:, 0] * hw + ev[:, 1], minlength=hw * hw).astype(np.float32)


# ---------------------------------------------------------------------------
# The network as dense fan-in
# ---------------------------------------------------------------------------
def edge_kernels(k: int) -> np.ndarray:
    """Four ternary oriented detectors ``[4, k, k]``: vertical edge,
    horizontal edge, upward vertex, downward vertex."""
    ks = np.zeros((4, k, k), np.float32)
    half = k // 2
    ks[0, :, half - 1: half + 1] = 1.0
    ks[0, :, : half - 2] = -1.0
    ks[0, :, half + 2:] = -1.0
    ks[1] = ks[0].T
    for y in range(k):
        for x in range(k):
            d = y - abs(x - half)
            ks[2, y, x] = 1.0 if 0 <= d <= 1 else (-1.0 if d > 2 else 0.0)
    ks[3] = ks[2, ::-1, :]
    return ks


@dataclasses.dataclass(frozen=True)
class Layout:
    """Neuron index ranges of one model: conv, pool, out."""

    hw: int
    conv_hw: int
    pool_hw: int
    n_kernels: int
    n_classes: int
    pop: int

    @property
    def n_conv(self):
        return self.n_kernels * self.conv_hw ** 2

    @property
    def n_pool(self):
        return self.n_kernels * self.pool_hw ** 2

    @property
    def n(self):
        return self.n_conv + self.n_pool + self.n_classes * self.pop

    @property
    def out0(self):
        return self.n_conv + self.n_pool


def layout_of(net: dict) -> Layout:
    conv_hw = net["conv_hw"]
    return Layout(net["input_hw"], conv_hw, conv_hw // net["pool"],
                  net["n_kernels"], net["n_classes"], net["pop_per_class"])


def input_fanin(net: dict) -> np.ndarray:
    """``[hw*hw, N, 4]``: drive per event on each pixel, per target and
    synapse type (positive taps fast excitatory, negative subtractive)."""
    lay = layout_of(net)
    kern = edge_kernels(net["kernel"])
    stride, k, hw = net["stride"], net["kernel"], lay.hw
    pad = (lay.conv_hw * stride + k - stride - hw) // 2
    w = np.zeros((hw * hw, lay.n, N_SYN), np.float32)
    for f in range(lay.n_kernels):
        for y in range(lay.conv_hw):
            for x in range(lay.conv_hw):
                n = (f * lay.conv_hw + y) * lay.conv_hw + x
                for ky in range(k):
                    iy = y * stride - pad + ky
                    if not 0 <= iy < hw:
                        continue
                    for kx in range(k):
                        ix = x * stride - pad + kx
                        if 0 <= ix < hw and kern[f, ky, kx] != 0:
                            syn = FAST_EXC if kern[f, ky, kx] > 0 else SUB_INH
                            w[iy * hw + ix, n, syn] += 1.0
    return w


def recurrent_fanin(net: dict, fc_select: np.ndarray | None) -> np.ndarray:
    """``[N_src, N_dst, 4]`` fan-in counts between the model's neurons.

    ``fc_select[c]`` lists the pooling neurons that feed class ``c``'s
    population; ``None`` leaves the pool -> out layer out (calibration).
    """
    lay = layout_of(net)
    w = np.zeros((lay.n, lay.n, N_SYN), np.float32)
    p = net["pool"]
    for f in range(lay.n_kernels):
        for py in range(lay.pool_hw):
            for px in range(lay.pool_hw):
                dst = lay.n_conv + (f * lay.pool_hw + py) * lay.pool_hw + px
                for dy in range(p):
                    for dx in range(p):
                        src = (f * lay.conv_hw + py * p + dy) * lay.conv_hw + px * p + dx
                        w[src, dst, FAST_EXC] += net["pool_weight"]
    if fc_select is not None:
        for c in range(lay.n_classes):
            dsts = lay.out0 + c * lay.pop + np.arange(lay.pop)
            for src in np.asarray(fc_select[c]):
                w[lay.n_conv + int(src), dsts, SLOW_EXC] += 1.0
    return w


# ---------------------------------------------------------------------------
# Neuron: AdExp I&F with four DPI synapse filters, exponential Euler
# ---------------------------------------------------------------------------
def neuron_update(state, drive, p, dtype):
    """One step of every neuron; ``state`` = (v, w, refrac, i_syn)."""
    v, w, refrac, i_syn = state
    dt = p["dt"]
    taus = jnp.asarray(p["tau_syn"], dtype)
    ws = jnp.asarray(p["w_syn"], dtype)
    i_syn = i_syn * jnp.exp(-dt / taus) + drive * ws
    exc = i_syn[..., 0] + i_syn[..., 1]
    leak_gain = 1.0 + p["shunt_gain"] * i_syn[..., 3]
    i_in = p["input_gain"] * (exc - i_syn[..., 2])
    exp_term = p["delta_t"] * jnp.exp(
        jnp.clip((v - p["v_thresh"]) / p["delta_t"], -20.0, 20.0))
    dv = (-(v - p["v_rest"]) * leak_gain + exp_term - w) / p["tau_m"] + i_in
    v_new = v + dt * dv
    w_new = w + dt * ((p["a_adapt"] * (v - p["v_rest"]) - w) / p["tau_w"])
    in_refrac = refrac > 0.0
    v_new = jnp.where(in_refrac, p["v_reset"], v_new)
    spikes = (v_new >= p["v_peak"]) & ~in_refrac
    state = (
        jnp.where(spikes, p["v_reset"], v_new).astype(dtype),
        jnp.where(spikes, w_new + p["b_adapt"], w_new).astype(dtype),
        jnp.where(spikes, p["refrac"], jnp.maximum(refrac - dt, 0.0)).astype(dtype),
        i_syn.astype(dtype),
    )
    return state, spikes.astype(dtype)


def rest_state(batch: int, n: int, p, dtype):
    return (
        jnp.full((batch, n), p["v_rest"], dtype),
        jnp.zeros((batch, n), dtype),
        jnp.zeros((batch, n), dtype),
        jnp.zeros((batch, n, N_SYN), dtype),
    )


# ---------------------------------------------------------------------------
# The served deployment: resident models side by side, one slab each
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Reference:
    """Dense fan-in of the resident models, by arrival delay in steps."""

    cfg: dict
    lay: Layout
    n_models: int
    w_by_delay: dict  # delay -> [N_total, N_total * 4] float32 (numpy)
    w_in: np.ndarray  # [hw*hw, N_model * 4] float32
    fc_select: np.ndarray

    @property
    def n_total(self):
        return self.lay.n * self.n_models


def _delay_mask(cfg: dict, lay: Layout) -> dict:
    """delay -> [N, N] bool mask of (src, dst) pairs arriving that late.

    Without a mesh every event arrives the next step (delay 0); on a mesh
    an event crossing tiles arrives ``ceil(hops * hop_latency / dt)`` steps
    later, the cluster of neuron ``i`` being ``i // cluster_size``.
    """
    n = lay.n
    mesh = cfg.get("mesh")
    if mesh is None:
        return {0: np.ones((n, n), bool)}
    tile = np.asarray(mesh["tile_of_cluster"])[np.arange(n) // cfg["cluster_size"]]
    gx = mesh["grid_x"]
    tx, ty = tile % gx, tile // gx
    hops = np.abs(tx[:, None] - tx[None, :]) + np.abs(ty[:, None] - ty[None, :])
    delay = np.ceil(hops * mesh["hop_latency_s"] / cfg["neuron"]["dt"] - 1e-9)
    delay = np.maximum(delay, 0).astype(int)
    return {int(d): delay == d for d in np.unique(delay)}


def build(cfg: dict) -> Reference:
    """The deployment's reference network from its configuration."""
    net = cfg["network"]
    lay = layout_of(net)
    p = cfg["neuron"]
    w_in = input_fanin(net)
    # offline-Hebbian readout calibration (paper §V): present each suit,
    # sum the pooling layer's spikes, keep the most selective neurons
    rng = np.random.default_rng(cfg["network_seed"])
    cal = net["calibration"]
    reps, t_steps = cal["reps"], cal["steps"]
    streams = [suit_events(sym, cal["events"], rng, lay.hw)
               for sym in range(lay.n_classes) for _ in range(reps)]
    counts = np.stack([np.bincount(e[:, 0] * lay.hw + e[:, 1], minlength=lay.hw ** 2)
                       for e in streams]).astype(np.float32)
    ext = counts / t_steps * cal["gain"]
    w_cal = recurrent_fanin(net, None).reshape(lay.n, -1)
    spikes = _run_fixed(jnp.asarray(w_cal), jnp.asarray(w_in.reshape(lay.hw ** 2, -1)),
                        jnp.asarray(ext), t_steps, p)
    pool = np.asarray(spikes)[:, :, lay.n_conv: lay.n_conv + lay.n_pool]
    rates = pool.sum(0).reshape(lay.n_classes, reps, -1).sum(1).astype(np.float64)
    sel = rates - rates.mean(0, keepdims=True)
    fc_select = np.stack([np.argsort(-sel[c])[: lay.pop] for c in range(lay.n_classes)])

    w_rec = recurrent_fanin(net, fc_select)
    masks = _delay_mask(cfg, lay)
    n_models = len(cfg["models"])
    nt = lay.n * n_models
    w_by_delay = {}
    for d, m in masks.items():
        blk = w_rec * m[:, :, None]
        full = np.zeros((nt, nt, N_SYN), np.float32)
        for j in range(n_models):
            s = slice(j * lay.n, (j + 1) * lay.n)
            full[s, s] = blk
        w_by_delay[d] = full.reshape(nt, nt * N_SYN)
    return Reference(cfg, lay, n_models, w_by_delay,
                     w_in.reshape(lay.hw ** 2, -1), fc_select)


def _run_fixed(w, w_in, ext, t_steps, p):
    """Calibration run: a constant input for ``t_steps``; spikes ``[T, B, N]``."""
    n = w.shape[0]
    drive_ext = jnp.matmul(ext, w_in, precision=HIGHEST).reshape(ext.shape[0], n, N_SYN)

    def body(carry, _):
        state, prev = carry
        drive = jnp.matmul(prev, w, precision=HIGHEST).reshape(prev.shape[0], n, N_SYN)
        state, spikes = neuron_update(state, drive + drive_ext, p, jnp.float32)
        return (state, spikes), spikes

    init = (rest_state(ext.shape[0], n, p, jnp.float32),
            jnp.zeros((ext.shape[0], n), jnp.float32))
    return jax.jit(lambda c: jax.lax.scan(body, c, None, length=t_steps)[1])(init)


@dataclasses.dataclass
class Replay:
    """What the reference says of each session."""

    decisions: dict  # session_id -> (prediction, steps, decided)
    states: dict  # session_id -> (v, w, refrac, i_syn) numpy after its steps
    counts: dict  # session_id -> per-class cumulative spikes after its steps


def replay(ref: Reference, sessions: list[dict], dtype=jnp.float32,
           block: int = 512) -> Replay:
    """Serve ``sessions`` on the reference, in blocks of sessions.

    Each session is ``{"key", "id", "model", "stream", "steps"}``: the key
    its readings are filed under, its id and stream, the slab of its model,
    and the steps to run it for. The reference records its decision at the
    first step it decides (or ``(argmax, steps, False)`` when it has not
    decided by then: a forced decision at the step cap), and its class
    counts, and with ``want_state`` its state, after ``steps``.
    """
    out = Replay({}, {}, {})
    order = sorted(sessions, key=lambda s: s["steps"])
    step_fn = _step_fn(ref, dtype)
    ws = {d: jnp.asarray(w, dtype) for d, w in ref.w_by_delay.items()}
    w_in = jnp.asarray(ref.w_in, dtype)
    for i in range(0, len(order), block):
        _replay_block(ref, order[i: i + block], dtype, step_fn, ws, w_in, out)
    return out


def _replay_block(ref: Reference, sessions, dtype, step_fn, ws, w_in, out) -> None:
    cfg, lay = ref.cfg, ref.lay
    p, serve = cfg["neuron"], cfg["serve"]
    b, n, nt = len(sessions), lay.n, ref.n_total
    slab = np.array([s["model"] for s in sessions])
    steps = np.array([s["steps"] for s in sessions])
    max_d = max(ref.w_by_delay)
    state = rest_state(b, nt, p, dtype)
    hist = jnp.zeros((max_d + 1, b, nt), dtype)  # hist[d]: spikes d + 1 steps ago
    place = np.zeros((b, ref.n_models), np.float32)
    place[np.arange(b), slab] = 1.0
    place = jnp.asarray(place, dtype)
    counts = np.zeros((b, lay.n_classes))
    decided = np.zeros(b, bool)
    cols = (lay.out0 + slab * n)[:, None] + np.arange(lay.n_classes * lay.pop)[None, :]
    hw2 = lay.hw ** 2
    for t in range(1, int(steps.max()) + 1):
        pix = np.zeros((b, hw2), np.float32)
        for j, s in enumerate(sessions):
            if t <= s["steps"]:
                pix[j] = session_pixel_counts(s["stream"], s["id"], t - 1)
        pix *= serve["drive"]
        state, hist, spikes = step_fn(ws, w_in, state, hist, jnp.asarray(pix, dtype), place)
        sp = np.asarray(jnp.take_along_axis(spikes, jnp.asarray(cols), 1), np.float64)
        counts += sp.reshape(b, lay.n_classes, lay.pop).sum(-1)
        now = (steps == t) | (~decided & (t >= serve["min_steps"])
                              & (counts.max(-1) >= serve["threshold"]) & (t <= steps))
        for j in np.flatnonzero(now):
            sid = sessions[j]["key"]
            if not decided[j]:
                hit = t >= serve["min_steps"] and counts[j].max() >= serve["threshold"]
                out.decisions[sid] = (int(np.argmax(counts[j])), t, bool(hit))
                decided[j] = True
            if t == steps[j]:
                out.counts[sid] = counts[j].copy()
                if sessions[j].get("want_state"):
                    out.states[sid] = tuple(np.asarray(x[j]) for x in state)


def _step_fn(ref: Reference, dtype):
    lay, p = ref.lay, ref.cfg["neuron"]
    n, nt = lay.n, ref.n_total

    @jax.jit
    def step(ws, w_in, state, hist, pix, place):
        b = pix.shape[0]
        drive = sum(jnp.matmul(hist[d], w, precision=HIGHEST) for d, w in ws.items())
        ext = jnp.matmul(pix, w_in, precision=HIGHEST).reshape(b, 1, n, N_SYN)
        ext = (place[:, :, None, None] * ext).reshape(b, nt, N_SYN)
        state, spikes = neuron_update(state, drive.reshape(b, nt, N_SYN) + ext, p, dtype)
        hist = jnp.concatenate([spikes[None], hist[:-1]], 0)
        return state, hist, spikes

    return step
