import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input shape x mesh) combination, build the step
function with explicit in_shardings, ``.lower().compile()`` it against
ShapeDtypeStruct inputs (no allocation), and extract memory / cost /
collective analyses for the roofline table.

NOTE: the XLA_FLAGS line above MUST execute before any jax import -- jax
locks the device count on first init.  Do not set this flag globally.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-2b \
      --shape train_4k [--multi-pod] [--mode fed|standard] [--out out.json]
  PYTHONPATH=src python -m repro.launch.dryrun --all --out results.json
"""

import argparse          # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
from functools import partial  # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCH_IDS, SHAPES, get_config  # noqa: E402
from repro.fed import runtime, sharding  # noqa: E402
from repro.fed.api import FedSpec, PrivacySpec  # noqa: E402
from repro.launch import roofline  # noqa: E402
from repro.launch.mesh import make_production_mesh, mesh_axis_sizes  # noqa: E402
from repro.models import model as model_lib  # noqa: E402
from repro.models.model import build_model  # noqa: E402
from repro.optim import sgd, apply_updates  # noqa: E402


# ---------------------------------------------------------------------------
# Case construction
# ---------------------------------------------------------------------------

def _ns(mesh, tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))


def build_case(arch: str, shape_name: str, mesh, mode: str = "fed",
               n_epochs: int = 4):
    """Returns (fn, arg_specs tuple, in_shardings tuple, meta dict)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = model_lib.shape_supported(cfg, shape)
    if not ok:
        return None, None, None, {"skipped": reason}
    model = build_model(cfg)
    axes = mesh_axis_sizes(mesh)
    multi_pod = "pod" in axes
    batch_axes = ("pod", "data") if multi_pod else ("data",)
    meta = {"arch": arch, "shape": shape_name, "mode": mode,
            "mesh": dict(axes), "params": None}

    key_spec = jax.ShapeDtypeStruct((2,), jnp.uint32)
    import math
    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    meta["params"] = sum(math.prod(l.shape)
                         for l in jax.tree_util.tree_leaves(params_shape))

    if shape.kind == "train" and mode == "fed":
        # one placement source: sharding.fed_axes / fed_state_specs /
        # fed_batch_specs (shared with build_trainer)
        agent_axis, fsdp_axis = sharding.fed_axes(axes)
        n_agents = axes[agent_axis]
        spec = FedSpec(n_agents=n_agents, n_epochs=n_epochs, gamma=0.05,
                       participation=0.8, privacy=PrivacySpec(tau=1e-3))
        step = runtime.make_train_step(model, spec)
        state_shape = jax.eval_shape(
            partial(runtime.init_state, model, spec=spec),
            jax.random.PRNGKey(0))
        state_spec = sharding.fed_state_specs(
            state_shape.x, fsdp_axis=fsdp_axis, agent_axis=agent_axis,
            axis_sizes=axes, compressed=spec.compression.name != "none")
        # batch: (A, B/A, S): per-agent batch shards over 'data' when the
        # agent axis is dedicated ('agent'/'pod'), else unsharded
        inner_axis = "data" if agent_axis != "data" else None
        batch_shape = jax.eval_shape(
            lambda: _fed_batch_specs(cfg, shape, n_agents))
        bspec = sharding.fed_batch_specs(batch_shape, agent_axis,
                                         inner_axis)
        fn = lambda state, batch, key: step(state, batch, key)
        args = (state_shape, batch_shape, key_spec)
        shardings_in = (_ns(mesh, state_spec), _ns(mesh, bspec),
                        NamedSharding(mesh, P()))
        meta["model_flops"] = roofline.model_flops(cfg, shape, "train") \
            * n_epochs
        return fn, args, shardings_in, meta

    if shape.kind == "train" and mode == "standard":
        opt = sgd(1e-2)
        pspec = sharding.param_specs(params_shape, fsdp_axis="data",
                                 axis_sizes=axes)

        def fn(params, batch, key):
            del key
            loss, grads = jax.value_and_grad(
                lambda p: model.loss_fn(p, batch=batch, remat=True))(params)
            upd, _ = opt.update(grads, (), params)
            return apply_updates(params, upd), loss

        batch_shape = model_lib.batch_specs(cfg, shape, with_labels=True)
        bspec = jax.tree_util.tree_map(
            lambda l: P(batch_axes, *([None] * (l.ndim - 1))), batch_shape)
        args = (params_shape, batch_shape, key_spec)
        shardings_in = (_ns(mesh, pspec), _ns(mesh, bspec),
                        NamedSharding(mesh, P()))
        meta["model_flops"] = roofline.model_flops(cfg, shape, "train")
        return fn, args, shardings_in, meta

    pspec = sharding.param_specs(params_shape, fsdp_axis="data",
                                 axis_sizes=axes)

    if shape.kind == "prefill":
        def fn(params, batch):
            return model.forward(params, batch=batch)[0]

        batch_shape = model_lib.batch_specs(cfg, shape, with_labels=False)
        bspec = jax.tree_util.tree_map(
            lambda l: P(batch_axes, *([None] * (l.ndim - 1))), batch_shape)
        args = (params_shape, batch_shape)
        shardings_in = (_ns(mesh, pspec), _ns(mesh, bspec))
        meta["model_flops"] = roofline.model_flops(cfg, shape, "prefill")
        return fn, args, shardings_in, meta

    # decode
    long_ctx = shape.name == "long_500k"
    cache_shape = model_lib.cache_specs(cfg, shape)
    cspec = sharding.cache_spec_tree(cache_shape, axes,
                                     data_axes=batch_axes)
    tok_shape = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)
    data_size = 1
    for a in batch_axes:
        data_size *= axes.get(a, 1)
    tok_spec = P(batch_axes) if shape.global_batch % data_size == 0 \
        and data_size > 1 else P()

    def fn(params, cache, tokens):
        return model.decode_step(params, cache=cache, tokens=tokens,
                                 long_ctx=long_ctx)

    args = (params_shape, cache_shape, tok_shape)
    shardings_in = (_ns(mesh, pspec), _ns(mesh, cspec),
                    NamedSharding(mesh, tok_spec))
    meta["model_flops"] = roofline.model_flops(cfg, shape, "decode")
    return fn, args, shardings_in, meta


def _fed_batch_specs(cfg, shape, n_agents):
    base = model_lib.batch_specs(cfg, shape, with_labels=True)
    out = {}
    for k, v in base.items():
        out[k] = jax.ShapeDtypeStruct(
            (n_agents, v.shape[0] // n_agents) + v.shape[1:], v.dtype)
    return out


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------

def run_case(arch: str, shape_name: str, multi_pod: bool = False,
             mode: str = "fed", verbose: bool = True,
             mesh=None) -> dict:
    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    fn, args, shardings_in, meta = build_case(arch, shape_name, mesh,
                                              mode=mode)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    result = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
              "mode": mode}
    if fn is None:
        result["status"] = "skipped"
        result["reason"] = meta["skipped"]
        if verbose:
            print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: "
                  f"SKIPPED ({meta['skipped']})")
        return result

    t0 = time.time()
    try:
        with mesh:
            jitted = jax.jit(fn, in_shardings=shardings_in)
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            rl = roofline.analyze(compiled, compiled.as_text(),
                                  meta["model_flops"], n_dev)
    except Exception as e:  # noqa: BLE001 -- dry-run failures are bugs
        result["status"] = "FAILED"
        result["error"] = f"{type(e).__name__}: {e}"
        if verbose:
            print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: FAILED "
                  f"{result['error']}")
        return result

    result.update({
        "status": "ok",
        "params": meta["params"],
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "roofline": rl.as_dict(),
    })
    for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes", "generated_code_size_in_bytes"):
        if mem is not None and hasattr(mem, attr):
            result[f"mem_{attr}"] = int(getattr(mem, attr))
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} x {mesh_name} [{mode}]: OK "
              f"compile={t_compile:.0f}s "
              f"compute={rl.compute_s:.3e}s memory={rl.memory_s:.3e}s "
              f"collective={rl.collective_s:.3e}s -> {rl.bottleneck}; "
              f"args/dev={result.get('mem_argument_size_in_bytes', 0)/1e9:.2f}GB "
              f"temp/dev={result.get('mem_temp_size_in_bytes', 0)/1e9:.2f}GB")
        print(f"          memory_analysis: {mem}")
        print(f"          cost_analysis: flops/dev={rl.flops:.3e} "
              f"bytes/dev={rl.hbm_bytes:.3e} "
              f"coll_bytes/dev={rl.coll_bytes:.3e} "
              f"counts={rl.coll_detail['counts']}")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--mode", default="fed", choices=["fed", "standard"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    archs = ARCH_IDS if args.all or args.arch is None else [args.arch]
    shapes = list(SHAPES) if args.all or args.shape is None \
        else [args.shape]
    meshes = [False, True] if (args.both_meshes or args.all) \
        else [args.multi_pod]

    results = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                results.append(run_case(arch, shape, multi_pod=mp,
                                        mode=args.mode))
                if args.out:
                    with open(args.out, "w") as f:
                        json.dump(results, f, indent=1)
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_fail = sum(r["status"] == "FAILED" for r in results)
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skipped, {n_fail} failed")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
