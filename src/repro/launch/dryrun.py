"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k --mesh pod1
    PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun.jsonl
    PYTHONPATH=src python -m repro.launch.dryrun --arch whisper-tiny \
        --shape train_4k --mesh pod1 --map     # + SharedMap placement loop

Writes one JSON line per cell (incremental — crashes/restarts resume by
skipping completed cells). The roofline report reads this file.
"""
# The VERY FIRST lines, before ANY other import (jax locks the device count
# on first init):
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs.registry import ARCHS, SHAPES, cell_applicable, get_config
from repro.launch import shardings as SH
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.models.sharding import ShardCtx
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import init_train_state, make_train_step

# TPU v5e hardware model (per chip)
PEAK_FLOPS = 197e12       # bf16
HBM_BW = 819e9            # bytes/s
ICI_BW = 50e9             # bytes/s/link


def make_ctx(mesh, multi_pod: bool, global_batch: int | None = None, **knobs) -> ShardCtx:
    axes = ("pod", "data") if multi_pod else ("data",)
    if global_batch is not None:
        # tiny batches (long_500k has B=1) cannot shard over the batch axes;
        # drop axes until the product divides the batch.
        while axes:
            prod = 1
            for a in axes:
                prod *= mesh.shape[a]
            if global_batch % prod == 0:
                break
            axes = axes[1:]
    return ShardCtx(
        mesh=mesh,
        batch_axes=axes,
        model_axis="model",
        **knobs,
    )


def lower_cell(cfg, cell, mesh, ctx, serve_bf16: bool = False):
    """Returns (lowered, trip_hints, extra_info)."""
    V = ctx.model_size
    specs = M.input_specs(cfg, cell.seq_len, cell.global_batch, cell.mode)
    batch_sds = SH.to_sds(specs, SH.batch_specs(cfg, specs, ctx), mesh)
    wmode = ctx.weight_mode

    if cell.mode == "train":
        state_abs = jax.eval_shape(
            lambda: init_train_state(cfg, jax.random.PRNGKey(0), V=V))
        pspec = SH.param_specs(state_abs.params, wmode)
        from repro.train.train_step import TrainState
        from repro.train.optimizer import OptState
        state_spec = TrainState(
            params=pspec,
            opt=OptState(step=jax.sharding.PartitionSpec(), mu=pspec, nu=pspec))
        state_sds = SH.to_sds(state_abs, state_spec, mesh)
        step = make_train_step(cfg, AdamWConfig(), ctx)
        with mesh:
            lowered = jax.jit(step, donate_argnums=(0,)).lower(state_sds, batch_sds)
        hints = M.scan_trip_hints(cfg, cell.seq_len, cell.mode,
                                  slstm_chunk=ctx.slstm_chunk)
        return lowered, hints, {}

    params_abs = jax.eval_shape(lambda: M.init_fn(cfg, jax.random.PRNGKey(0), V=V))
    if serve_bf16:  # serving checkpoints are bf16 (H3)
        params_abs = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(
                l.shape, jnp.bfloat16 if l.dtype == jnp.float32 else l.dtype),
            params_abs)
    params_sds = SH.to_sds(params_abs, SH.param_specs(params_abs, wmode), mesh)

    if cell.mode == "prefill":
        def prefill(params, batch):
            return M.prefill_fn(cfg, params, batch, ctx)
        with mesh:
            lowered = jax.jit(prefill).lower(params_sds, batch_sds)
        return lowered, M.scan_trip_hints(cfg, cell.seq_len, cell.mode,
                                          slstm_chunk=ctx.slstm_chunk), {}

    # decode: one token against a KV cache of cell.seq_len
    cache_abs = jax.eval_shape(
        lambda: M.init_cache(cfg, cell.global_batch, cell.seq_len, V=V))
    cache_sds = SH.to_sds(cache_abs, SH.cache_specs(cfg, cache_abs, ctx), mesh)
    pos_sds = jax.ShapeDtypeStruct((), jnp.int32)

    def decode(params, tokens, cache, pos):
        return M.decode_fn(cfg, params, tokens, cache, pos, ctx)

    with mesh:
        lowered = jax.jit(decode, donate_argnums=(2,)).lower(
            params_sds, batch_sds["tokens"], cache_sds, pos_sds)
    return lowered, M.scan_trip_hints(cfg, cell.seq_len, cell.mode,
                                      slstm_chunk=ctx.slstm_chunk), {}


def run_cell(arch: str, cell, multi_pod: bool, knobs: dict | None = None,
             map_placement: bool = False) -> dict:
    cfg = get_config(arch)
    chips = 512 if multi_pod else 256
    mesh = make_production_mesh(multi_pod=multi_pod)
    knobs = dict(knobs or {})
    serve_bf16 = knobs.pop("serve_bf16", False)
    ctx = make_ctx(mesh, multi_pod, global_batch=cell.global_batch, **knobs)
    rec = {
        "arch": arch, "shape": cell.name, "mesh": "pod2" if multi_pod else "pod1",
        "chips": chips, "mode": cell.mode,
        "knobs": {**knobs, **({"serve_bf16": True} if serve_bf16 else {})},
    }
    t0 = time.time()
    lowered, hints, _ = lower_cell(cfg, cell, mesh, ctx, serve_bf16=serve_bf16)
    rec["lower_s"] = round(time.time() - t0, 1)
    t0 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 1)

    ma = compiled.memory_analysis()
    rec["memory"] = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "per_device_total": int(ma.argument_size_in_bytes + ma.temp_size_in_bytes
                                + ma.output_size_in_bytes - ma.alias_size_in_bytes),
    }
    ca = compiled.cost_analysis() or {}
    rec["cost_analysis"] = {"flops": float(ca.get("flops", -1)),
                            "bytes_accessed": float(ca.get("bytes accessed", -1))}

    hlo = compiled.as_text()
    an = analyze_hlo(hlo, trip_hints=hints)
    rec["hlo"] = {
        "flops_per_device": an.flops,
        "collective_bytes": an.collective_bytes,
        "collective_total": an.total_collective_bytes,
        "num_collectives": an.num_collectives,
        "hbm_bytes": an.hbm_bytes,
        "while_trips": an.while_trips,
        "trip_hints": hints,
    }
    # roofline terms (seconds, per device == per step global / chips)
    rec["roofline"] = {
        "compute_s": an.flops / PEAK_FLOPS,
        "memory_s": an.hbm_bytes / HBM_BW,
        "collective_s": an.total_collective_bytes / ICI_BW,
    }
    dom = max(rec["roofline"], key=rec["roofline"].get)
    rec["roofline"]["dominant"] = dom
    # model flops (global) for the usefulness ratio
    tokens = cell.global_batch * (cell.seq_len if cell.mode != "decode" else 1)
    n_active = cfg.active_param_count()
    mf = 6 * n_active * tokens if cell.mode == "train" else 2 * n_active * tokens
    rec["model_flops_global"] = float(mf)
    rec["useful_ratio"] = float(mf / max(an.flops * chips, 1.0))

    if map_placement:
        # PR 10 closed loop: the compiled HLO's per-op communication graph,
        # mapped onto the physical chip hierarchy by SharedMap, scored
        # against the default (program-order) placement — next to the
        # roofline collective term it would discount.
        from repro.core.api import SharedMapConfig, shared_map
        from repro.core.mapping import evaluate_J
        from repro.launch.comm_graph import default_placement, extract_comm_graph
        from repro.launch.mesh import physical_hierarchy

        h = physical_hierarchy(multi_pod)
        t0 = time.time()
        tg = extract_comm_graph(hlo, trip_hints=hints, min_tasks=2 * h.k)
        extract_s = time.time() - t0
        if tg.n < h.k:
            rec["map"] = {"skipped": f"graph has {tg.n} tasks < k={h.k}"}
        else:
            g = tg.to_graph()
            t0 = time.time()
            res = shared_map(g, h, SharedMapConfig(preset="fast"))
            map_s = time.time() - t0
            j_def = evaluate_J(g, h, default_placement(tg.n, h.k))
            rec["map"] = {
                "tasks": tg.n, "task_edges": tg.m,
                "granularity": tg.meta["granularity"],
                "extract_s": round(extract_s, 2),
                "map_s": round(map_s, 2),
                "J_sharedmap": res.J, "J_default": j_def,
                "improvement": j_def / max(res.J, 1e-12),
                "roofline_collective_s": rec["roofline"]["collective_s"],
            }
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS)
    ap.add_argument("--shape", choices=[s.name for s in SHAPES])
    ap.add_argument("--mesh", choices=["pod1", "pod2", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun.jsonl")
    ap.add_argument("--map", action="store_true", dest="map_placement",
                    help="extract the HLO communication graph and SharedMap "
                         "it onto the physical hierarchy (closed loop); adds "
                         "a 'map' record with J vs the default placement")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args()

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    done = set()
    if os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    if "error" not in r:
                        done.add((r["arch"], r["shape"], r["mesh"]))
                except json.JSONDecodeError:
                    pass

    cells = []
    meshes = ["pod1", "pod2"] if args.mesh == "both" else [args.mesh]
    for arch in ([args.arch] if args.arch else ARCHS):
        cfg = get_config(arch)
        for cell in SHAPES:
            if args.shape and cell.name != args.shape:
                continue
            ok, why = cell_applicable(cfg, cell)
            for mname in meshes:
                if (arch, cell.name, mname) in done:
                    continue
                cells.append((arch, cell, mname, ok, why))

    with open(args.out, "a") as f:
        for arch, cell, mname, ok, why in cells:
            tag = f"{arch} x {cell.name} x {mname}"
            if not ok:
                rec = {"arch": arch, "shape": cell.name, "mesh": mname,
                       "skipped": why}
                f.write(json.dumps(rec) + "\n")
                f.flush()
                print(f"[skip] {tag}: {why}", flush=True)
                continue
            print(f"[run ] {tag} ...", flush=True)
            try:
                rec = run_cell(arch, cell, multi_pod=(mname == "pod2"),
                               map_placement=args.map_placement)
                rl = rec["roofline"]
                print(f"[ ok ] {tag}: compute={rl['compute_s']:.3f}s "
                      f"mem={rl['memory_s']:.3f}s coll={rl['collective_s']:.3f}s "
                      f"dom={rl['dominant']} compile={rec['compile_s']}s",
                      flush=True)
                mp = rec.get("map")
                if mp and "skipped" not in mp:
                    print(f"[ map] {tag}: tasks={mp['tasks']} "
                          f"J={mp['J_sharedmap']:.3g} vs default "
                          f"{mp['J_default']:.3g} "
                          f"({mp['improvement']:.2f}x better)", flush=True)
            except Exception as e:  # record failures; the sweep continues
                rec = {"arch": arch, "shape": cell.name, "mesh": mname,
                       "error": f"{type(e).__name__}: {e}",
                       "trace": traceback.format_exc()[-2000:]}
                print(f"[FAIL] {tag}: {type(e).__name__}: {str(e)[:200]}", flush=True)
            f.write(json.dumps(rec) + "\n")
            f.flush()


if __name__ == "__main__":
    main()
