import os
os.environ["XLA_FLAGS"] = " ".join(filter(None, [
    os.environ.get("XLA_FLAGS", ""),
    "--xla_force_host_platform_device_count=512"]))
# ^^ MUST precede every other import: jax pins the device count at first
# init.  Only the dry-run gets 512 placeholder devices; tests/benches see 1.

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this produces, without allocating a single model byte:
  * proof the sharding config is coherent (compile succeeds, no sharding
    mismatch / unsupported collective),
  * ``memory_analysis``  — per-device bytes (does it fit HBM?),
  * ``cost_analysis``    — HLO FLOPs / bytes for §Roofline,
  * parsed collective bytes (repro.launch.hlo_analysis) for the third
    roofline term,
and appends a JSON record under benchmarks/results/.

Usage:
  python -m repro.launch.dryrun --arch llama3.2-3b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--bpt]
"""
import argparse
import dataclasses
import functools
import json
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.distributed import sharding_rules as rules
from repro.launch import hlo_analysis, specs
from repro.launch.mesh import make_production_mesh
from repro.models import decode as dec
from repro.models import model
from repro.models.config import LONG_CONTEXT_FAMILIES, SHAPES
from repro.train.step import make_train_step

RESULTS_DIR = pathlib.Path(__file__).resolve().parents[3] / "benchmarks" / \
    "results"

# v5e roofline constants (per assignment)
PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9

TRAIN_MICROBATCHES = {"train_4k": 8}


def _cell_skip_reason(cfg, shape_name: str):
    if shape_name == "long_500k" and cfg.family not in LONG_CONTEXT_FAMILIES:
        return ("full-attention arch: 512K decode requires sub-quadratic "
                "sequence mixing")
    return None


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               cfg=None, mesh=None, shape=None) -> dict:
    """Lower + compile one cell.  ``cfg``/``mesh``/``shape`` overrides let
    tests exercise the identical code path at reduced scale."""
    cfg = cfg or registry.get(arch)
    shape = shape or SHAPES[shape_name]
    mesh = mesh or make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    record = {"arch": arch, "shape": shape_name,
              "mesh": "x".join(str(mesh.shape[a]) for a in mesh.axis_names),
              "axes": list(mesh.axis_names), "chips": chips,
              "kind": shape.kind}
    skip = _cell_skip_reason(cfg, shape_name)
    if skip:
        record.update(status="skipped", reason=skip)
        return record

    rules.set_mesh(mesh)
    try:
        p_shapes = specs.param_specs(cfg)
        p_sh = rules.param_shardings(mesh, p_shapes)
        t0 = time.time()
        if shape.kind == "train":
            o_shapes = specs.opt_specs(cfg, p_shapes)
            o_sh = specs.opt_shardings(mesh, o_shapes, p_sh)
            b_shapes = specs.batch_specs(cfg, shape)
            b_sh = specs.batch_shardings(mesh, b_shapes)
            M = TRAIN_MICROBATCHES.get(shape_name, 1)
            step = make_train_step(cfg, lambda s: 3e-4, num_microbatches=M)
            fn = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                         donate_argnums=(0, 1))
            lowered = fn.lower(p_shapes, o_shapes, b_shapes)
        elif shape.kind == "prefill":
            b_shapes = specs.batch_specs(cfg, shape, with_labels=False)
            b_sh = specs.batch_shardings(mesh, b_shapes)

            def prefill_fn(params, batch):
                logits, _, caches = model.forward(params, cfg, batch,
                                                  collect_cache=True)
                return logits[:, -1:], caches

            fn = jax.jit(prefill_fn, in_shardings=(p_sh, b_sh))
            lowered = fn.lower(p_shapes, b_shapes)
        else:                                        # decode
            c_shapes, tok, cur = specs.decode_specs(cfg, shape)
            c_sh = specs.cache_shardings(mesh, c_shapes)
            b_sh = specs.batch_shardings(mesh, {"tokens": tok})["tokens"]

            def serve_step(params, caches, token, cur_len):
                return dec.decode_step(params, cfg, caches, token, cur_len)

            from jax.sharding import NamedSharding, PartitionSpec as P
            fn = jax.jit(serve_step,
                         in_shardings=(p_sh, c_sh, b_sh,
                                       NamedSharding(mesh, P())),
                         donate_argnums=(1,))
            lowered = fn.lower(p_shapes, c_shapes, tok, cur)
        t_lower = time.time() - t0

        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

        mem = compiled.memory_analysis()
        xla_cost = compiled.cost_analysis() or {}
        text = compiled.as_text()
        cost = hlo_analysis.full_cost(text)      # loop-weighted (exact for
        # scans; XLA's cost_analysis counts while bodies once — see module)
        flops_per_device = cost["flops"]
        bytes_per_device = cost["bytes"]
        record.update(
            status="ok",
            lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
            flops_per_device=flops_per_device,
            bytes_per_device=bytes_per_device,
            xla_flops_body_once=float(xla_cost.get("flops", 0.0)),
            collective=cost["collective"],
            memory={
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "generated_code_bytes": getattr(
                    mem, "generated_code_size_in_bytes", None),
            },
            roofline=roofline_terms(cfg, shape, flops_per_device,
                                    bytes_per_device,
                                    cost["collective"]["per_device_bytes"],
                                    chips),
        )
    except Exception as e:                           # record the failure
        record.update(status="error", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-2000:])
    finally:
        rules.set_mesh(None)
    return record


def roofline_terms(cfg, shape, flops_dev, bytes_dev, coll_dev, chips):
    t_compute = flops_dev / PEAK_FLOPS
    t_memory = bytes_dev / HBM_BW
    t_collective = coll_dev / ICI_BW
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_collective}
    dominant = max(terms, key=terms.get)
    tokens = shape.global_batch * (1 if shape.kind == "decode"
                                   else shape.seq_len)
    n_active = cfg.active_param_count()
    model_flops = (6 if shape.kind == "train" else 2) * n_active * tokens
    hlo_total = flops_dev * chips
    terms.update(
        dominant=dominant.replace("_s", ""),
        model_flops=model_flops,
        hlo_flops_total=hlo_total,
        useful_fraction=(model_flops / hlo_total) if hlo_total else None,
        bound_step_time_s=max(terms["compute_s"], terms["memory_s"],
                              terms["collective_s"]),
    )
    return terms


# ------------------------------------------------------------- BPT workloads
def lower_bpt_cell(which: str, *, multi_pod: bool) -> dict:
    """The paper's own workload on the production mesh."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    record = {"arch": f"fused-bpt-{which}", "shape": which,
              "mesh": "x".join(str(mesh.shape[a]) for a in mesh.axis_names),
              "axes": list(mesh.axis_names), "chips": chips, "kind": "bpt"}
    try:
        from repro.graph import csr, partition as part_lib

        if which == "sample":
            # soc-LiveJournal1 scale, graph replicated (paper's strategy):
            # the data_parallel backend's shard_map of the dense block
            # program, one batch per chip, batches over every mesh axis.
            from repro.core import traversal
            from repro.sampling import SamplerSpec
            from repro.sampling.sampler import _data_parallel_block_fn

            V, E, C = 4_847_571, 68_993_773, 512
            g = csr.Graph(
                indptr=jax.ShapeDtypeStruct((V + 1,), jnp.int32),
                src=jax.ShapeDtypeStruct((E,), jnp.int32),
                dst=jax.ShapeDtypeStruct((E,), jnp.int32),
                prob=jax.ShapeDtypeStruct((E,), jnp.float32),
                num_vertices=V, num_edges=E)
            segments = jax.eval_shape(
                functools.partial(traversal.dst_segments, num_rows=V), g.dst)
            spec = SamplerSpec(backend="data_parallel", num_colors=C,
                               max_iters=64)
            fn = _data_parallel_block_fn(mesh, tuple(mesh.axis_names), spec,
                                         None)
            lowered = fn.lower((g, segments),
                               jax.ShapeDtypeStruct((chips, C), jnp.int32),
                               jax.ShapeDtypeStruct((chips,), jnp.uint32))
        elif which in ("graph", "graph_q"):          # graph parallel
            # web-BerkStan scale tiled graph, 1-D partition over "model".
            # tiles_per_shard from the measured cluster-reordered density
            # (benchmarks/bench_reorder.py → ~32 edges/tile): E/32/S ≈ 1900.
            V, E, C, T = 685_230, 7_600_595, 64, 128
            S = mesh.shape["model"]
            nb = -(-(-(-V // T)) // S) * S           # blocks, shard-divisible
            nb_loc = nb // S
            tiles_per_shard = 1900
            if which == "graph":
                # The graph_parallel backend's block program: one batch
                # per data shard, rows over "model".
                from repro.distributed.traversal import graph_parallel_block

                ptg = part_lib.PartitionedTiledGraph(
                    prob=jax.ShapeDtypeStruct((S, tiles_per_shard, T, T),
                                              jnp.float32),
                    edge_id=jax.ShapeDtypeStruct((S, tiles_per_shard, T, T),
                                                 jnp.uint32),
                    tile_src=jax.ShapeDtypeStruct((S, tiles_per_shard),
                                                  jnp.int32),
                    tile_dst=jax.ShapeDtypeStruct((S, tiles_per_shard),
                                                  jnp.int32),
                    first_of_dst=jax.ShapeDtypeStruct((S, tiles_per_shard),
                                                      jnp.int32),
                    num_vertices=V, num_edges=E, tile_size=T, num_shards=S,
                    blocks_per_shard=nb_loc)
                B = mesh.shape["data"]
                fn = graph_parallel_block(
                    ptg, mesh, num_colors=C, max_levels=64,
                    use_kernel=False, interpret=False)
                lowered = fn.lower(ptg,
                                   jax.ShapeDtypeStruct((B, C), jnp.int32),
                                   jax.ShapeDtypeStruct((B,), jnp.uint32))
            else:
                # §Perf B1: quantized tiles — u8 threshold, no edge-id
                # plane (8× tile bytes), 8 hashes/word instead of 32.
                from jax.sharding import PartitionSpec as P

                from repro.core import bitmask, tiles as tiles_lib
                from repro.core.traversal import init_frontier
                from repro.kernels import fused_expand_q as feq

                starts = jax.ShapeDtypeStruct((C,), jnp.int32)
                q8 = jax.ShapeDtypeStruct((S, tiles_per_shard, T, T),
                                          jnp.uint8)
                ts = jax.ShapeDtypeStruct((S, tiles_per_shard), jnp.int32)
                td = jax.ShapeDtypeStruct((S, tiles_per_shard), jnp.int32)
                vp = S * nb_loc * T

                def body(q8, ts, td, fr_local):
                    seed = jnp.uint32(7)

                    def cond(c):
                        fr, _, lvl = c
                        anyb = jax.lax.psum(
                            bitmask.any_set(fr).astype(jnp.int32), "model")
                        return jnp.logical_and(anyb > 0, lvl < 64)

                    def step(c):
                        fr, vis, lvl = c
                        vis = vis | fr
                        fr_g = jax.lax.all_gather(fr, "model", tiled=True)
                        nf = feq.fused_expand_q_ref(
                            q8[0], ts[0], td[0], fr_g, vis, seed,
                            lvl.astype(jnp.uint32))
                        return nf, vis, lvl + 1

                    fr, vis, lvl = jax.lax.while_loop(
                        cond, step,
                        (fr_local, jnp.zeros_like(fr_local), jnp.int32(0)))
                    return vis | fr, lvl

                fn = jax.shard_map(
                    body, mesh=mesh,
                    in_specs=(P("model"), P("model"), P("model"),
                              P("model")),
                    out_specs=(P("model"), P()), check_vma=False)

                def run(q8, ts, td, starts):
                    fr = tiles_lib.pad_mask_rows(
                        init_frontier(V, C, starts), vp)
                    return fn(q8, ts, td, fr)

                lowered = jax.jit(run).lower(q8, ts, td, starts)

        t0 = time.time()
        compiled = lowered.compile()
        cost = hlo_analysis.full_cost(compiled.as_text())
        mem = compiled.memory_analysis()
        flops_dev = cost["flops"]
        bytes_dev = cost["bytes"]
        coll = cost["collective"]
        record.update(
            status="ok", compile_s=round(time.time() - t0, 1),
            flops_per_device=flops_dev, bytes_per_device=bytes_dev,
            collective=coll,
            memory={"argument_bytes": getattr(mem, "argument_size_in_bytes",
                                              None),
                    "temp_bytes": getattr(mem, "temp_size_in_bytes", None)},
            roofline={
                "compute_s": flops_dev / PEAK_FLOPS,
                "memory_s": bytes_dev / HBM_BW,
                "collective_s": coll["per_device_bytes"] / ICI_BW,
            })
        r = record["roofline"]
        r["dominant"] = max(("compute_s", "memory_s", "collective_s"),
                            key=lambda k: r[k]).replace("_s", "")
    except Exception as e:
        record.update(status="error", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-2000:])
    return record


def save_record(record: dict, tag: str = ""):
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    name = (f"dryrun_{record['arch']}_{record['shape']}_"
            f"{record['mesh']}{tag}.json")
    with open(RESULTS_DIR / name, "w") as f:
        json.dump(record, f, indent=1)
    return name


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=registry.ARCHS + ["all"])
    ap.add_argument("--shape", choices=list(SHAPES) + ["all"],
                    default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--bpt", action="store_true",
                    help="lower the paper's fused-BPT workloads")
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args()

    cells = []
    if args.bpt:
        for which in ("sample", "graph", "graph_q"):
            rec = lower_bpt_cell(which, multi_pod=args.multi_pod)
            print(json.dumps({k: rec.get(k) for k in
                              ("arch", "mesh", "status", "roofline",
                               "error")}, indent=1))
            save_record(rec)
        if not (args.all or args.arch):
            return

    archs = registry.ARCHS if (args.all or args.arch in (None, "all")) \
        else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    for arch in archs:
        for shape in shapes:
            rec = lower_cell(arch, shape, multi_pod=args.multi_pod)
            status = rec["status"]
            extra = (rec["roofline"]["dominant"] if status == "ok"
                     else rec.get("reason", rec.get("error", "")))
            print(f"[dryrun] {arch:28s} {shape:12s} {rec['mesh']:9s} "
                  f"{status:8s} {extra}")
            save_record(rec)
            cells.append(rec)
    ok = sum(1 for c in cells if c["status"] == "ok")
    sk = sum(1 for c in cells if c["status"] == "skipped")
    print(f"[dryrun] {ok} ok / {sk} skipped / "
          f"{len(cells) - ok - sk} failed of {len(cells)}")


if __name__ == "__main__":
    main()
