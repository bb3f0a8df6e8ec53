import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without hardware: every cell
must partition over the production meshes — 16×16 (data, model) single pod
and 2×16×16 (pod, data, model) multi-pod — and fit per-device memory.
Emits the roofline terms per cell for EXPERIMENTS.md.

``--queries`` is the SQL analog: compile every TPC-H query to its
physical pipeline plan through the ``repro.api`` session (planning only —
zero workers invoked), proving planner coherence across scale factors.

Usage:
  python -m repro.launch.dryrun --arch granite-3-2b --shape train_4k
  python -m repro.launch.dryrun --all [--mesh single|multi|both]
  python -m repro.launch.dryrun --all --out bench/dryrun.jsonl
  python -m repro.launch.dryrun --queries [--sf 0.01]
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import numpy as np

from repro.analysis import memtraffic
from repro.analysis import roofline as roofline_lib
from repro.configs import ARCHS, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import build_cell
from repro.models.config import SHAPES
from repro.parallel import sharding as sh


def _compile_one(cfg, arch, shape_name, mesh, plan, *, remat, donate,
                 scan_layers, optimizer=None, accum_steps=1):
    cell = build_cell(cfg, arch, shape_name, mesh=mesh, remat=remat,
                      scan_layers=scan_layers, optimizer=optimizer,
                      accum_steps=accum_steps)
    p_specs = cell.arg_specs[0]
    in_shardings: list = [sh.param_shardings(plan, p_specs)]
    if cell.kind == "train":
        in_shardings.append(
            sh.opt_state_shardings(plan, p_specs, cell.arg_specs[1]))
        in_shardings.append(sh.batch_shardings(plan, cell.arg_specs[2]))
        out_shardings = (in_shardings[0], in_shardings[1], None)
    elif cell.kind == "prefill":
        in_shardings.append(sh.batch_shardings(plan, cell.arg_specs[1]))
        out_shardings = (None, sh.cache_shardings(
            plan, jax.eval_shape(cell.step_fn, *cell.arg_specs)[1]))
    else:  # decode
        cache_sh = sh.cache_shardings(plan, cell.arg_specs[1])
        in_shardings.append(cache_sh)
        in_shardings.append(sh.batch_shardings(plan, cell.arg_specs[2]))
        out_shardings = (None, None, cache_sh)
    with mesh:
        jitted = jax.jit(
            cell.step_fn,
            in_shardings=tuple(in_shardings),
            out_shardings=out_shardings,
            donate_argnums=cell.donate_argnums if donate else ())
        lowered = jitted.lower(*cell.arg_specs)
        compiled = lowered.compile()
    return compiled


def _with_layers(cfg, n: int):
    return dataclasses.replace(
        cfg, n_layers=n, enc_layers=(n if cfg.enc_dec else cfg.enc_layers))


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               remat: bool = True, donate: bool = True,
               extra_config: dict | None = None,
               extrapolate: bool = True, optimizer=None,
               accum_steps: int = 1):
    """Compile the full scanned-layers artifact (the deliverable: proves
    sharding coherence + memory fit), then — because XLA cost analysis
    counts loop bodies once — compile unrolled L=2 / L=4 variants and fit
    cost(L) = a + b·L to report true full-depth roofline terms.

    Returns (record dict, compiled or None)."""
    cfg = get_config(arch)
    if extra_config:
        cfg = dataclasses.replace(cfg, **extra_config)
    mesh = make_production_mesh(multi_pod=multi_pod)
    shape = SHAPES[shape_name]
    plan = sh.make_plan(
        mesh, shard_sequence=(shape_name == "long_500k"
                              and cfg.family in ("ssm", "hybrid")))
    cell = build_cell(cfg, arch, shape_name, mesh=mesh, remat=remat)
    record = {
        "arch": arch, "shape": shape_name, "kind": cell.kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": int(np.prod(list(mesh.shape.values()))),
    }
    if cell.skip_reason:
        record["status"] = "skip"
        record["reason"] = cell.skip_reason
        return record, None

    # 1. Full-depth scanned artifact: compile proof + memory analysis +
    #    collective schedule.
    t0 = time.time()
    compiled = _compile_one(cfg, arch, shape_name, mesh, plan,
                            remat=remat, donate=donate, scan_layers=True,
                            optimizer=optimizer, accum_steps=accum_steps)
    record["compile_s"] = round(time.time() - t0, 1)
    ma = compiled.memory_analysis()
    record["memory_analysis"] = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
    }
    sched = roofline_lib.parse_collectives(compiled.as_text())
    record["collective_schedule"] = sched.count_by_kind

    # Analytic HBM model (CPU-backend scheduling is not TPU-representative
    # — see analysis/memtraffic.py).
    opt_bytes = 12.0
    if optimizer is not None and getattr(optimizer, "state_dtype", None) \
            is not None:
        import jax.numpy as _jnp
        if optimizer.state_dtype == _jnp.bfloat16:
            opt_bytes = 8.0          # f32 params + bf16 m/v
    mem = memtraffic.analyze_memory(
        cfg, shape, n_devices=record["n_devices"], dp=plan.dp_size,
        tp=mesh.shape["model"], kind=cell.kind,
        accum_steps=accum_steps, opt_bytes_per_param=opt_bytes)
    record["fits_hbm"] = mem.fits_hbm
    record["hbm_residency_bytes"] = mem.residency_bytes
    record["memory_detail"] = mem.detail

    # 2. Roofline terms via depth extrapolation (unrolled L=2, L=4).
    if extrapolate:
        costs = {}
        for lvar in (2, 4):
            cvar = _compile_one(_with_layers(cfg, lvar), arch, shape_name,
                                mesh, plan, remat=remat, donate=False,
                                scan_layers=False, optimizer=optimizer,
                                accum_steps=accum_steps)
            roof = roofline_lib.analyze(cvar)
            costs[lvar] = roof
        L = cfg.n_layers

        def fit(f2, f4):
            slope = (f4 - f2) / 2.0
            return max(f2 + slope * (L - 2), 0.0)

        flops = fit(costs[2].flops_per_device, costs[4].flops_per_device)
        nbytes = fit(costs[2].bytes_per_device, costs[4].bytes_per_device)
        cbytes = fit(costs[2].collective_bytes_per_device,
                     costs[4].collective_bytes_per_device)
        if accum_steps > 1:
            # the microbatch lax.scan body is counted once by XLA cost
            # analysis (same loop-body issue as layers): scale by the
            # accumulation factor (optimizer-update costs outside the
            # scan are <1% of a step for these models)
            flops *= accum_steps
            cbytes *= accum_steps
    else:
        roof = roofline_lib.analyze(compiled)
        flops, nbytes, cbytes = (roof.flops_per_device,
                                 roof.bytes_per_device,
                                 roof.collective_bytes_per_device)

    compute_s = flops / 197e12
    memory_s = mem.traffic_bytes / 819e9
    collective_s = cbytes / 50e9
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    record["roofline"] = {
        "flops_per_device": flops,
        "bytes_per_device": mem.traffic_bytes,
        "bytes_hlo_unfused": nbytes,
        "collective_bytes_per_device": cbytes,
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": collective_s, "dominant": dominant,
    }
    mf = roofline_lib.model_flops(get_config(arch), SHAPES[shape_name],
                                  train=(cell.kind == "train"))
    record["model_flops_global"] = mf
    hlo_global = flops * record["n_devices"]
    record["useful_flops_ratio"] = mf / hlo_global if hlo_global else 0.0
    bound = max(compute_s, memory_s, collective_s)
    record["roofline_fraction"] = (
        (mf / record["n_devices"] / 197e12) / bound if bound else 0.0)
    record["status"] = "ok"
    return record, compiled


def dryrun_queries(sf: float = 0.01, out: str | None = None) -> int:
    """Plan (never execute) all TPC-H queries; returns failure count."""
    from repro.api import connect
    from repro.sql.queries import QUERIES

    session = connect(tier="local")
    session.ensure_tpch(sf=sf, n_parts=4)
    out_f = open(out, "a") if out else None
    failures = 0
    for qname, sql in QUERIES.items():
        try:
            text = session.explain(sql)
            n_pipes = text.splitlines()[0]
            print(f"[ok]   {qname}: {n_pipes}")
            rec = {"query": qname, "sf": sf, "status": "ok",
                   "plan": text}
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"[FAIL] {qname}: {e!r}")
            rec = {"query": qname, "sf": sf, "status": "error",
                   "error": repr(e)}
        if out_f:
            out_f.write(json.dumps(rec) + "\n")
    if out_f:
        out_f.close()
    assert session.platform.invocations == 0, "dry-run invoked workers"
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--out", default=None)
    # §Perf optimization knobs (EXPERIMENTS.md) — off = paper-faithful
    ap.add_argument("--seq-parallel", action="store_true",
                    help="Megatron sequence parallelism")
    ap.add_argument("--accum", type=int, default=1,
                    help="gradient accumulation steps")
    ap.add_argument("--bf16-adam", action="store_true",
                    help="bf16 optimizer moments")
    ap.add_argument("--queries", action="store_true",
                    help="SQL mode: compile all TPC-H plans (no "
                         "execution) through the repro.api session")
    ap.add_argument("--sf", type=float, default=0.01,
                    help="TPC-H scale factor for --queries")
    args = ap.parse_args()

    if args.queries:
        failures = dryrun_queries(sf=args.sf, out=args.out)
        if failures:
            raise SystemExit(f"{failures} query plans failed")
        return

    extra = {}
    if args.seq_parallel:
        extra["seq_parallel"] = True
    optimizer = None
    if args.bf16_adam:
        import jax.numpy as jnp
        from repro.optim import AdamW
        optimizer = AdamW(state_dtype=jnp.bfloat16)

    archs = ARCHS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) \
        else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    out_f = open(args.out, "a") if args.out else None
    failures = 0
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                tag = f"{arch} × {shape} × " \
                      f"{'2x16x16' if multi else '16x16'}"
                try:
                    rec, _ = lower_cell(arch, shape, multi_pod=multi,
                                        remat=not args.no_remat,
                                        extra_config=extra or None,
                                        optimizer=optimizer,
                                        accum_steps=args.accum)
                except Exception as e:  # noqa: BLE001
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if multi else "16x16",
                           "status": "error", "error": repr(e)}
                    failures += 1
                    traceback.print_exc()
                if rec["status"] == "ok":
                    r = rec["roofline"]
                    print(f"[ok]   {tag}: compile={rec['compile_s']}s "
                          f"dominant={r['dominant']} "
                          f"compute={r['compute_s']:.4f}s "
                          f"memory={r['memory_s']:.4f}s "
                          f"collective={r['collective_s']:.4f}s "
                          f"roofline_frac={rec['roofline_fraction']:.3f}")
                elif rec["status"] == "skip":
                    print(f"[skip] {tag}: {rec['reason']}")
                else:
                    print(f"[FAIL] {tag}: {rec['error']}")
                if out_f:
                    out_f.write(json.dumps(rec) + "\n")
                    out_f.flush()
    if out_f:
        out_f.close()
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
