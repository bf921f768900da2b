"""Compile rehearsal for a TPU v5e chip, with no chip attached.

The three delivery kernels and the jitted serving-pool step are compiled by
the TPU compiler for a described v5e device at the served Table-V shapes
(pool of 32 slots; 6 clusters for one resident model, 12 for two; 256
neurons per cluster, K = 1024 tags, 64 CAM words), the fabric kernel
and pool step at the benchmark's fabric cell (128 slots, one resident),
and the fused pool step at the fused cell (128 slots, two residents).
Interpret mode accepts block layouts and VMEM footprints the chip refuses;
these compiles do not.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, so a description made while pytest
collects would break the other workers. The tests skip where no v5e
topology can be described.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cam_match.cam_match import cam_match_pallas
from repro.kernels.fabric_deliver.fabric_deliver import fabric_deliver_ring_pallas
from repro.kernels.fused_deliver.fused_deliver import fused_deliver_pallas

POOL, C, K, S, E = 32, 256, 1024, 64, 16
MAX_DELAY = 1  # the served fabric's delay horizon
CELL_POOL = 128  # slots of both benchmark cells
CELL_ENTRIES = 1280  # occupied SRAM entries of one Table-V CNN
STEP_SCOPES = {"compact", "link_arbitration", "deliver", "neuron_update", "reset_slots"}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # programs compiled for a described chip cannot be read back from
        # the persistent cache without the chip: keep them out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    lowered = jax.jit(fn).lower(*shapes)
    text = lowered.as_text()
    compiled = lowered.compile()
    return text, compiled


def _kernel_call(kernel: str, nc: int, sds, pool: int = POOL, m: int | None = None):
    n = nc * C
    tags = sds((n, S), jnp.int32)
    if kernel == "cam_match":
        return (
            lambda a, t, s: cam_match_pallas(a, t, s, C, interpret=False),
            sds((POOL, nc, K)), tags, tags,
        )
    if kernel == "fused_deliver":
        qe = n * E  # lossless AER queue: every neuron, every SRAM entry
        # n_entries is a runtime operand: stage 1's trip count is dynamic
        return (
            lambda f, w, t, s, x, ne: fused_deliver_pallas(
                f, w, t, s, x, C, K, n_entries=ne, interpret=False
            ),
            sds((POOL, qe), jnp.int32), sds((POOL, qe)), tags, tags,
            sds((POOL, nc, K)), sds((POOL,), jnp.int32),
        )
    m = n if m is None else m  # occupied SRAM entries: about one per neuron
    return (
        lambda f, w, r, c, x, t, s: fabric_deliver_ring_pallas(
            f, w, r, c, x, t, s, C, K, MAX_DELAY, interpret=False
        ),
        sds((m,), jnp.int32), sds((pool, m)),
        sds((pool, MAX_DELAY + 1, nc, K)), sds((), jnp.int32),
        sds((pool, nc, K)), tags, tags,
    )


@pytest.mark.parametrize("nc", [6, 12])
@pytest.mark.parametrize("kernel", ["cam_match", "fused_deliver", "fabric_deliver"])
def test_kernel_compiles_for_v5e(one_chip, kernel, nc):
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, *shapes = _kernel_call(kernel, nc, sds)
    text, compiled = _compile(fn, *shapes)
    assert "tpu_custom_call" in text
    assert compiled.memory_analysis() is not None


def test_fabric_kernel_compiles_for_v5e_at_the_cell_shapes(one_chip):
    """``fabric_deliver`` as the fabric cell runs it: 128 slots, one resident
    (nc 6), a ring of D + 1 = 2 slots and the network's 1,280 occupied SRAM
    entries, which the kernel pads to whole event chunks."""
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, *shapes = _kernel_call("fabric_deliver", 6, sds, pool=CELL_POOL, m=CELL_ENTRIES)
    text, compiled = _compile(fn, *shapes)
    assert "tpu_custom_call" in text
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("kernel", ["cam_match", "fused_deliver", "fabric_deliver"])
def test_compiled_kernel_is_named_for_the_kernel(one_chip, kernel):
    """The kernel's instruction in the compiled program, which a profiler
    trace names its op by, carries the ``pallas_call``'s own name: a change
    to the wrapper around it leaves the name the trace readers match."""
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, *shapes = _kernel_call(kernel, 6, sds)
    _, compiled = _compile(fn, *shapes)
    assert re.search(rf"%{kernel}(\.\d+)? = [^\n]*custom-call\(", compiled.as_text())


@pytest.mark.parametrize("kernel", ["cam_match", "fused_deliver", "fabric_deliver"])
def test_compiled_kernel_refuses_unaligned_k(kernel):
    """The compiled path tiles K as one lane block; K % 128 != 0 is refused
    before lowering, on any platform (interpret mode keeps any K)."""
    nc, k = 2, 24
    n = nc * C
    tags = jnp.zeros((n, S), jnp.int32)
    act = jnp.zeros((1, nc, k))
    with pytest.raises(ValueError, match="K % 128"):
        if kernel == "cam_match":
            cam_match_pallas(act, tags, tags, C, interpret=False)
        elif kernel == "fused_deliver":
            ev = jnp.zeros((1, 8), jnp.int32)
            fused_deliver_pallas(ev, ev.astype(jnp.float32), tags, tags, act,
                                 C, k, interpret=False)
        else:
            ev = jnp.zeros((8,), jnp.int32)
            ring = jnp.zeros((1, MAX_DELAY + 1, nc, k))
            fabric_deliver_ring_pallas(
                ev, jnp.zeros((1, 8)), ring, jnp.int32(0), act, tags, tags,
                C, k, MAX_DELAY, interpret=False,
            )


@pytest.mark.parametrize("backend", ["pallas", "fused", "fabric"])
def test_two_model_pool_step_compiles_for_v5e(one_chip, backend):
    """The jitted step of the served 2-model pool (nc = 12), with the
    backend's kernel forced to its compiled form: the platform policy would
    pick the jnp reference on this CPU-only host."""
    from repro.core.cnn import compile_poker_cnn
    from repro.core.dispatch import FusedBackend, PallasBackend
    from repro.serve.aer import AerServeConfig, AerSessionPool

    cc = compile_poker_cnn()
    kw = {
        "pallas": {"backend": PallasBackend(interpret=False)},
        "fused": {"backend": FusedBackend(interpret=False)},
        "fabric": {"backend": "fabric", "fabric_options": {"interpret": False}},
    }[backend]
    pool = AerSessionPool.from_models(
        {"a": cc, "b": cc}, AerServeConfig(pool_size=POOL),
        **kw,
    )
    eng = pool.engine
    assert eng.n_clusters == 12 and eng.k_tags == K

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    carry = jax.tree.map(sds, pool.carry)
    inp = jax.ShapeDtypeStruct((POOL, eng.n_clusters, K), jnp.float32,
                               sharding=one_chip)
    text, compiled = _compile(eng.step, carry, inp)
    assert "tpu_custom_call" in text
    assert compiled.memory_analysis() is not None


def test_one_model_fabric_pool_step_compiles_for_v5e_at_128_slots(one_chip):
    """The fabric cell's pool step (one resident, 128 slots) compiles with
    the kernel in it, and every instruction of the compiled step that the
    program named lies under one of the step's scopes, so a trace reduction
    can put each op in a stage. The compiler's own copies and layout ops
    name nothing, or the parameter they copy; constants run nothing."""
    from repro.core.cnn import compile_poker_cnn
    from repro.serve.aer import AerServeConfig, AerSessionPool

    pool = AerSessionPool.from_models(
        {"tableV-3x3": compile_poker_cnn()}, AerServeConfig(pool_size=CELL_POOL),
        backend="fabric", fabric_options={"interpret": False},
    )
    eng = pool.engine
    assert eng.n_clusters == 6 and eng._fabric_entries.src.shape == (CELL_ENTRIES,)

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    carry = jax.tree.map(sds, pool.carry)
    inp = jax.ShapeDtypeStruct((CELL_POOL, eng.n_clusters, K), jnp.float32,
                               sharding=one_chip)
    text, compiled = _compile(eng.step, carry, inp)
    assert "tpu_custom_call" in text
    hlo = compiled.as_text()
    assert re.search(r"%fabric_deliver(\.\d+)? = [^\n]*custom-call\(", hlo)
    entry = re.search(r"\nENTRY [^\n]*\{\n(.*?)\n\}", hlo, re.S).group(1)
    named = re.findall(r"%([\w.\-]+) = [^\n]*? (\w[\w\-]*)\([^\n]*op_name=\"([^\"]*)\"", entry)
    params = {path for _, op, path in named if op == "parameter"}
    assert params, "the step's parameters carry their names"
    unscoped = [(name, path) for name, op, path in named
                if op != "constant" and path not in params
                and not STEP_SCOPES & set(re.split(r"[/;]", path))]
    assert not unscoped


def test_two_model_fused_pool_step_at_128_slots_has_no_compaction_loop(one_chip):
    """The fused cell's pool step (two residents, nc 12, 128 slots) compiles
    with the kernel in it, and the AER queue's compaction under scope
    ``compact`` lowers to no loop (a ``while`` there would run a trip per
    step of a search over every slot's queue) and keeps that scope on its
    scatter."""
    from repro.core.cnn import compile_poker_cnn
    from repro.core.dispatch import FusedBackend
    from repro.serve.aer import AerServeConfig, AerSessionPool

    cc = compile_poker_cnn()
    pool = AerSessionPool.from_models(
        {"a": cc, "b": cc}, AerServeConfig(pool_size=CELL_POOL),
        backend=FusedBackend(interpret=False),
    )
    eng = pool.engine
    assert eng.n_clusters == 12 and eng.k_tags == K

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    carry = jax.tree.map(sds, pool.carry)
    inp = jax.ShapeDtypeStruct((CELL_POOL, eng.n_clusters, K), jnp.float32,
                               sharding=one_chip)
    text, compiled = _compile(eng.step, carry, inp)
    assert "tpu_custom_call" in text
    hlo = compiled.as_text()
    assert re.search(r"%fused_deliver(\.\d+)? = [^\n]*custom-call\(", hlo)
    scoped = re.findall(r"%[\w.\-]+ = [^\n]*? (\w[\w\-]*)\([^\n]*op_name=\"([^\"]*)\"", hlo)
    # the scatter keeps its scope, so a trace reduction counts it as compaction
    assert any(op == "fusion" and path.endswith("/compact/scatter") for op, path in scoped)
    loops = [path for op, path in scoped
             if op == "while" and "compact" in re.split(r"[/;]", path)]
    assert not loops
