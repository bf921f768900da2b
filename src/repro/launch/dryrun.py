"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver:
  1. builds the FULL architecture config and the production mesh,
  2. resolves parameter/optimizer/cache/input shardings (logical axes ->
     PartitionSpec via distributed/sharding.py),
  3. ``jax.jit(step).lower(**ShapeDtypeStructs).compile()`` — no allocation,
  4. records memory_analysis(), cost_analysis(), and per-device collective
     bytes parsed from the compiled HLO,
into ``experiments/dryrun/<arch>__<shape>__<mesh>.json`` — the §Roofline
inputs.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-1b --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import dataclasses
import json
import os
import re
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import LONG_OK, SHAPES, Shape, get_config
from repro.launch.costs import hlo_collective_bytes, jaxpr_cost
from repro.distributed import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.models import layers as L
from repro.models.model import build_model
from repro.train.loop import make_train_step
from repro.train.optimizer import OptConfig, init_opt_state

ART_DIR = os.environ.get(
    "REPRO_DRYRUN_DIR",
    os.path.join(os.path.dirname(__file__), "..", "..", "..", "experiments", "dryrun"),
)

import contextlib


@contextlib.contextmanager
def _null_ctx():
    yield


def pad_heads(cfg, multiple: int):
    """Pad attention q-heads up to a multiple of the TP degree (zero-weight
    heads — exact numerics, vLLM-style). Enables clean head sharding for
    head counts like yi-34b's 56 on a 16-way axis (§Perf iteration E)."""
    import math as _math

    h = _math.ceil(cfg.n_heads / multiple) * multiple
    if h == cfg.n_heads or cfg.n_heads < multiple:
        return cfg
    if cfg.n_kv_heads and h % cfg.n_kv_heads != 0:
        return cfg  # would break GQA grouping
    return dataclasses.replace(cfg, n_heads=h)


# v5e hardware constants (per chip)
PEAK_FLOPS = 197e12  # bf16
HBM_BW = 819e9
LINK_BW = 50e9


# ---------------------------------------------------------------------------
# sharding resolution for the full state
# ---------------------------------------------------------------------------
def model_param_pspecs(model, params_shapes, mesh):
    spec_tree = model.param_specs()
    out = {}
    for k, sub in spec_tree.items():
        if isinstance(sub, dict) and "periods" in sub:  # stack-like (decoder/encoder)
            sub_out = {}
            for name, blk in sub.items():
                pn = 1 if name == "periods" else 0
                sub_out[name] = shd.tree_pspecs(blk, params_shapes[k][name], mesh, prefix_none=pn)
            out[k] = sub_out
        else:
            out[k] = shd.tree_pspecs(sub, params_shapes[k], mesh)
    return out


def opt_pspecs(param_pspec_tree, params_shapes, mesh, opt_cfg: OptConfig, zero1: bool = True):
    """Moments follow params; ZeRO-1 adds spare axes on the first divisible
    unsharded dim. q8 moments shard the block dim."""
    spare = [a for a in ("pod",) if a in mesh.shape]

    def moment_spec(pspec, shape):
        if opt_cfg.state_dtype == "q8":
            # q/scale add trailing (blocks, block) dims; leading dims (and
            # their shardings) match the parameter exactly
            lead = list(pspec)[: max(0, len(shape) - 1)]
            lead += [None] * (max(0, len(shape) - 1) - len(lead))
            return {"q": P(*lead, None, None), "scale": P(*lead, None, None)}
        if not zero1 or not spare:
            return pspec
        used = set()
        for e in pspec:
            if e is None:
                continue
            used.update(e if isinstance(e, tuple) else (e,))
        size = int(np.prod([mesh.shape[a] for a in spare]))
        new = list(pspec) + [None] * (len(shape) - len(pspec))
        for i, d in enumerate(shape):
            if new[i] is None and d % size == 0:
                new[i] = tuple(spare) if len(spare) > 1 else spare[0]
                break
        return P(*new)

    def walk(pspec_node, shape_node):
        return jax.tree.map(
            lambda ps, sh: moment_spec(ps, sh.shape),
            pspec_node,
            shape_node,
            is_leaf=lambda x: isinstance(x, P),
        )

    return walk(param_pspec_tree, params_shapes)


def cache_pspecs(cache_shapes, mesh, batch: int):
    """Resolve cache tree shardings by leaf name + shape."""

    def resolve(path, leaf):
        name = str(path[-1].key) if hasattr(path[-1], "key") else str(path[-1])
        shape = leaf.shape
        in_periods = any(getattr(p, "key", None) == "periods" for p in path)
        off = 1 if in_periods else 0  # leading stacked-period dim
        spec = [None] * len(shape)
        used: set[str] = set()

        def assign(i, axes_pref):
            for axes in axes_pref:
                axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
                if not all(a in mesh.shape for a in axes_t) or (set(axes_t) & used):
                    continue
                size = int(np.prod([mesh.shape[a] for a in axes_t]))
                if size > 1 and shape[i] % size == 0:
                    spec[i] = axes_t if len(axes_t) > 1 else axes_t[0]
                    used.update(axes_t)
                    return

        if name in ("k", "v"):  # [.., B, L, KV, HD]
            assign(off + 2, ["model"])
            assign(off + 0, [("pod", "data"), "data", "pod"])
            assign(off + 1, ["data"])
        elif name in ("c_kv", "k_rope"):  # [.., B, L, R]
            assign(off + 0, [("pod", "data"), "data", "pod"])
            assign(off + 1, ["data"])
        elif name == "pos":  # [.., B, L]
            assign(off + 0, [("pod", "data"), "data", "pod"])
            assign(off + 1, ["data"])
        elif name == "conv":  # [.., B, K-1, C]
            assign(off + 2, ["model"])
            assign(off + 0, [("pod", "data"), "data", "pod"])
        elif name == "ssm":  # [.., B, H, P, N]
            assign(off + 1, ["model"])
            assign(off + 0, [("pod", "data"), "data", "pod"])
        elif name == "wkv":  # [.., B, H, P, P]
            assign(off + 1, ["model"])
            assign(off + 0, [("pod", "data"), "data", "pod"])
        elif name == "x_prev":  # [.., B, D]
            assign(off + 0, [("pod", "data"), "data", "pod"])
        elif name == "enc_out":  # [B, S, D]
            assign(0, [("pod", "data"), "data", "pod"])
        return P(*spec)

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shapes)
    specs = [resolve(path, leaf) for path, leaf in flat]
    return jax.tree.unflatten(treedef, specs)


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins; no allocation)
# ---------------------------------------------------------------------------
def input_specs(cfg, shape: Shape, mesh):
    """Training/prefill/decode inputs for one cell."""
    b, s = shape.global_batch, shape.seq_len
    tok_spec = shd.token_pspec(b, s, mesh)
    batch_axes = tok_spec[0]
    out = {}
    if shape.kind == "train":
        out["tokens"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
        out["labels"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
    elif shape.kind == "prefill":
        out["tokens"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
    else:  # decode
        out["tokens"] = jax.ShapeDtypeStruct((b, 1), jnp.int32)
        out["pos"] = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    if cfg.frontend == "vision_stub" and shape.kind in ("train", "prefill"):
        out["prefix_embeddings"] = jax.ShapeDtypeStruct(
            (b, cfg.n_prefix_embeddings, cfg.d_model), jnp.bfloat16
        )
    if cfg.frontend == "audio_stub" and shape.kind in ("train", "prefill"):
        out["frames"] = jax.ShapeDtypeStruct((b, cfg.enc_seq, cfg.d_model), jnp.bfloat16)
    shardings = {}
    for k, v in out.items():
        if k in ("tokens", "labels"):
            shardings[k] = NamedSharding(mesh, tok_spec if shape.kind == "train" else P(batch_axes, None))
        elif k == "pos":
            shardings[k] = NamedSharding(mesh, P(batch_axes, None))
        else:
            shardings[k] = NamedSharding(mesh, P(batch_axes, None, None))
    return out, shardings


# ---------------------------------------------------------------------------
# cell runner
# ---------------------------------------------------------------------------
def run_cell(arch: str, shape: Shape, multi_pod: bool, opt_cfg: OptConfig | None = None,
             save: bool = True, mesh=None, cfg=None) -> dict:
    t0 = time.time()
    cfg = cfg if cfg is not None else get_config(arch)
    mesh = mesh if mesh is not None else make_production_mesh(multi_pod=multi_pod)
    mesh_name = "multi" if multi_pod else "single"
    moe_impl = "sharded" if cfg.n_experts else "local"
    loss_chunk = int(os.environ.get("REPRO_LOSS_CHUNK", "0"))
    if int(os.environ.get("REPRO_PAD_HEADS", "0")):
        cfg = pad_heads(cfg, int(os.environ["REPRO_PAD_HEADS"]))
    model = build_model(cfg, moe_impl=moe_impl, mesh=mesh, loss_chunk=loss_chunk)
    opt_cfg = opt_cfg or OptConfig(state_dtype="q8" if cfg.param_count()[0] > 1e11 else "float32")

    opt_level = int(os.environ.get("REPRO_OPT_LEVEL", "1"))  # 0 = baseline
    act_ctx = shd.activation_mesh(mesh) if opt_level >= 1 else _null_ctx()
    params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_pspecs = model_param_pspecs(model, params_shapes, mesh)
    p_shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), p_pspecs,
                               is_leaf=lambda x: isinstance(x, P))

    inputs, in_shardings = input_specs(cfg, shape, mesh)

    if shape.kind == "train":
        o_shapes = jax.eval_shape(lambda p: init_opt_state(p, opt_cfg), params_shapes)
        o_pspecs = opt_pspecs(p_pspecs, params_shapes, mesh, opt_cfg)
        o_shardings = {
            "m": jax.tree.map(lambda s: NamedSharding(mesh, s), o_pspecs, is_leaf=lambda x: isinstance(x, P)),
            "v": jax.tree.map(lambda s: NamedSharding(mesh, s), o_pspecs, is_leaf=lambda x: isinstance(x, P)),
            "step": NamedSharding(mesh, P()),
        }
        state_shapes = {"params": params_shapes, "opt": o_shapes}
        state_shardings = {"params": p_shardings, "opt": o_shardings}
        step_fn = make_train_step(model, opt_cfg)
        jitted = jax.jit(
            step_fn,
            in_shardings=(state_shardings, in_shardings),
            donate_argnums=0,
        )
        with mesh, act_ctx:
            lowered = jitted.lower(state_shapes, {k: v for k, v in inputs.items()})
            traced_jaxpr = jax.make_jaxpr(step_fn)(state_shapes, inputs)
    else:
        cache_shapes = jax.eval_shape(
            lambda: model.init_caches(shape.global_batch, shape.seq_len + 8)
        )
        c_pspecs = cache_pspecs(cache_shapes, mesh, shape.global_batch)
        c_shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), c_pspecs,
                                   is_leaf=lambda x: isinstance(x, P))
        if shape.kind == "prefill":
            extras = {k: v for k, v in inputs.items() if k not in ("tokens",)}
            extras_sh = {k: in_shardings[k] for k in extras} or None

            def prefill_fn(params, tokens, caches, batch):
                return model.prefill(params, tokens, caches, batch)

            jitted = jax.jit(
                prefill_fn,
                in_shardings=(p_shardings, in_shardings["tokens"], c_shardings, extras_sh),
                donate_argnums=2,
            )
            with mesh, act_ctx:
                lowered = jitted.lower(
                    params_shapes, inputs["tokens"], cache_shapes,
                    {k: extras[k] for k in extras} if extras else None,
                )
                traced_jaxpr = jax.make_jaxpr(prefill_fn)(
                    params_shapes, inputs["tokens"], cache_shapes,
                    {k: extras[k] for k in extras} if extras else None,
                )
        else:  # decode
            def decode_fn(params, tokens, pos, caches):
                return model.decode_step(params, tokens, pos, caches)

            jitted = jax.jit(
                decode_fn,
                in_shardings=(p_shardings, in_shardings["tokens"], in_shardings["pos"], c_shardings),
                donate_argnums=3,
            )
            with mesh, act_ctx:
                lowered = jitted.lower(params_shapes, inputs["tokens"], inputs["pos"], cache_shapes)
                traced_jaxpr = jax.make_jaxpr(decode_fn)(
                    params_shapes, inputs["tokens"], inputs["pos"], cache_shapes
                )

    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll_hlo = hlo_collective_bytes(hlo)
    # analytic (scan-aware) cost from the traced jaxpr
    analytic = jaxpr_cost(traced_jaxpr)
    coll = dict(coll_hlo)
    coll["analytic_total"] = analytic["collective"]["total"]
    coll["total"] = max(coll_hlo.get("total", 0.0), analytic["collective"]["total"])

    n_chips = mesh.devices.size
    flops = analytic["flops"] / n_chips  # global -> per-chip
    bytes_acc = analytic["bytes"] / n_chips
    hlo_flops_raw = float(cost.get("flops", 0.0)) if cost else 0.0
    total_p, active_p = cfg.param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    if shape.kind == "train":
        model_flops = 6 * active_p * tokens
    elif shape.kind == "prefill":
        model_flops = 2 * active_p * tokens
    else:
        model_flops = 2 * active_p * tokens

    result = {
        "arch": arch,
        "shape": shape.name,
        "kind": shape.kind,
        "mesh": mesh_name,
        "n_chips": int(n_chips),
        "seconds_to_compile": round(time.time() - t0, 1),
        "memory": {
            k: int(getattr(mem, k))
            for k in (
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "temp_size_in_bytes",
                "generated_code_size_in_bytes",
            )
            if hasattr(mem, k)
        },
        "cost": {
            "flops_per_device": flops,
            "bytes_per_device": bytes_acc,
            "xla_cost_analysis_flops_raw": hlo_flops_raw,  # body-once; see costs.py
        },
        "collective_bytes_per_device": coll,
        "params": {"total": total_p, "active": active_p},
        "model_flops_global": model_flops,
        "roofline": {},
    }
    # roofline terms (seconds), per §Roofline
    comp_t = flops / PEAK_FLOPS
    mem_t = bytes_acc / HBM_BW
    coll_t = coll.get("total", 0) / LINK_BW
    dom = max(("compute", comp_t), ("memory", mem_t), ("collective", coll_t), key=lambda kv: kv[1])
    result["roofline"] = {
        "compute_s": comp_t,
        "memory_s": mem_t,
        "collective_s": coll_t,
        "dominant": dom[0],
        "model_flops_ratio": (model_flops / (flops * n_chips)) if flops else None,
        "mfu_upper_bound": (model_flops / (PEAK_FLOPS * n_chips)) / max(comp_t, mem_t, coll_t)
        if max(comp_t, mem_t, coll_t) > 0
        else None,
    }
    if save:
        os.makedirs(ART_DIR, exist_ok=True)
        fn = os.path.join(ART_DIR, f"{arch}__{shape.name}__{mesh_name}.json")
        with open(fn, "w") as f:
            json.dump(result, f, indent=1)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None)
    ap.add_argument("--mesh", type=str, default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args()

    from repro.configs import ARCHS, cells

    todo = []
    for arch, shape, runnable, skip in cells():
        if not args.all:
            if args.arch and arch != args.arch:
                continue
            if args.shape and shape.name != args.shape:
                continue
        if not runnable:
            print(f"SKIP {arch} x {shape.name}: {skip}")
            continue
        for mp in ([False, True] if args.mesh == "both" else [args.mesh == "multi"]):
            todo.append((arch, shape, mp))

    failures = 0
    for arch, shape, mp in todo:
        tag = f"{arch} x {shape.name} x {'multi' if mp else 'single'}"
        try:
            r = run_cell(arch, shape, mp)
            rf = r["roofline"]
            print(
                f"OK   {tag}: compile={r['seconds_to_compile']}s "
                f"compute={rf['compute_s']:.3e}s memory={rf['memory_s']:.3e}s "
                f"collective={rf['collective_s']:.3e}s dominant={rf['dominant']}"
            )
        except Exception as e:  # noqa: BLE001 — report and continue the sweep
            failures += 1
            print(f"FAIL {tag}: {e}")
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    # the production meshes span 512 fake host devices; XLA reads the flag
    # when the backend first starts, which no import above does
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 "
        + os.environ.get("XLA_FLAGS", "")
    )
    main()
