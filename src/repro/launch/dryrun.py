import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces (into benchmarks/results/dryrun/*.json):

  * the **full compile** (scan-over-layers, remat, real layer count) on the
    16x16 single-pod mesh AND the 2x16x16 multi-pod mesh — proving the
    sharding config is coherent (memory_analysis = fits; collective ops
    resolve);
  * **costing lowers**: the same program with every scan unrolled at
    n_layers = period and 2*period (period = attn_every for hybrids, else 1),
    because XLA's cost analysis counts a while body once; per-layer slopes
    b = (c2-c1)/period and intercept a = c1 - period*b extrapolate exact
    FLOPs / bytes / collective-bytes to the real depth:  total = a + L*b.
  * collective bytes parsed from post-SPMD ``compiled.as_text()``
    (all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute operand shapes, summed per op kind).

Usage:
  python -m repro.launch.dryrun [--arch yi-9b] [--shape train_4k]
      [--mesh single|multi|both] [--out DIR] [--skip-costing]
"""
import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op, per kind."""
    out: dict[str, float] = {}
    for m in _COLL_RE.finditer(hlo_text):
        shapes, kind = m.group(1), m.group(2)
        total = 0
        for sm in _SHAPE_RE.finditer(shapes):
            dt, dims = sm.group(1), sm.group(2)
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * _DTYPE_BYTES[dt]
        out[kind] = out.get(kind, 0) + total
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


# --------------------------------------------------------------------------
def auto_microbatches(cfg, global_batch: int, dp_size: int) -> int:
    """Baseline microbatch policy: local microbatch ~2 sequences for wide
    models (d_model >= 4096), ~8 otherwise — fits 16 GiB/chip at 4k train.
    (The §Perf hillclimb tunes this per cell.)"""
    b_local = max(global_batch // dp_size, 1)
    target = 4 if cfg.d_model < 4096 else 2
    if cfg.d_model >= 5120 or (cfg.is_ssm and cfg.d_model >= 4096):
        target = 1   # widest models / mamba chunk states; EXPERIMENTS.md §Dry-run
    mb = max(b_local // target, 1)
    while b_local % mb:
        mb -= 1
    return max(mb, 1)


def build_cell(arch: str, shape: str, *, n_layers_override=None,
               unroll=False, remat=None, dp_size: int = 16,
               microbatches: int | None = None):
    """Returns (step_fn, arg_shapes, in_specs_fn) for one cell."""
    from repro.configs import get_arch, get_shape, input_specs
    from repro.models import decode_step, init_cache, init_params, prefill
    from repro.train import AdamWConfig, adamw_init, make_train_step

    cfg = get_arch(arch)
    if n_layers_override:
        cfg = dataclasses.replace(cfg, n_layers=n_layers_override)
    if remat is not None:
        cfg = dataclasses.replace(cfg, remat=remat)
    if unroll:
        # fewer, larger chunk bodies for the unrolled costing lowers (the
        # chunked recurrences are exact for any chunk size; memory analysis
        # comes from the real compile, not these)
        cfg = dataclasses.replace(cfg, ssm_chunk=1024)
    sh = get_shape(shape)
    batch_shapes = input_specs(cfg, shape)
    params_shapes = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0)))

    if sh["kind"] == "train":
        ocfg = AdamWConfig()
        opt_shapes = jax.eval_shape(adamw_init, params_shapes)
        mb = microbatches or auto_microbatches(cfg, sh["global_batch"], dp_size)
        step = make_train_step(cfg, ocfg, unroll=unroll, num_microbatches=mb)
        args = (params_shapes, opt_shapes, batch_shapes)
        kind = "train"
    elif sh["kind"] == "prefill":
        def step(params, batch):
            return prefill(cfg, params, batch, unroll=unroll)
        args = (params_shapes, batch_shapes)
        kind = "prefill"
    else:
        cache_shapes = jax.eval_shape(
            lambda: init_cache(cfg, sh["global_batch"], sh["seq_len"]))

        def step(params, cache, batch):
            return decode_step(cfg, params, cache, batch, unroll=unroll)
        args = (params_shapes, cache_shapes, batch_shapes)
        kind = "decode"
    return cfg, step, args, kind


def shardings_for(mesh, args, kind, expert_2d=False, layout="tp"):
    from repro.sharding import (batch_specs, cache_specs, named, opt_specs,
                                param_specs)
    if kind == "train":
        params_s, opt_s, batch_s = args
        return (named(mesh, param_specs(params_s, mesh, expert_2d=expert_2d,
                                        layout=layout)),
                named(mesh, opt_specs(params_s, mesh, expert_2d=expert_2d,
                                      layout=layout)),
                named(mesh, batch_specs(batch_s, mesh,
                                        include_model=(layout == "dp"))))
    if kind == "prefill":
        params_s, batch_s = args
        return (named(mesh, param_specs(params_s, mesh, serve=True)),
                named(mesh, batch_specs(batch_s, mesh)))
    params_s, cache_s, batch_s = args
    return (named(mesh, param_specs(params_s, mesh, serve=True)),
            named(mesh, cache_specs(cache_s, mesh)),
            named(mesh, batch_specs(batch_s, mesh)))


def lower_cell(mesh, arch, shape, *, n_layers_override=None, unroll=False,
               remat=None, microbatches=None, expert_2d=False, layout="tp"):
    dp = int(np.prod([s for s, a in zip(mesh.devices.shape, mesh.axis_names)
                      if a in ("pod", "data")]))
    if layout == "dp":
        dp *= int(np.prod([s for s, a in zip(mesh.devices.shape, mesh.axis_names)
                           if a == "model"]))
    # costing lowers use a single microbatch (identical per-token math; the
    # scan-counting problem would otherwise hide mb-1 of the accumulation)
    if unroll and microbatches is None:
        microbatches = 1
    cfg, step, args, kind = build_cell(arch, shape,
                                       n_layers_override=n_layers_override,
                                       unroll=unroll, remat=remat,
                                       dp_size=dp, microbatches=microbatches)
    in_sh = shardings_for(mesh, args, kind, expert_2d=expert_2d, layout=layout)
    # production aliasing: train updates (params, opt) in place; decode
    # updates the cache in place
    donate = {"train": (0, 1), "decode": (1,), "prefill": ()}[kind]
    with jax.set_mesh(mesh):
        jitted = jax.jit(step, in_shardings=in_sh, donate_argnums=donate)
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    return cfg, compiled


def analyze(compiled) -> dict:
    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    txt = compiled.as_text()
    coll = collective_bytes(txt)
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "collectives": coll,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_hbm_estimate": (mem.argument_size_in_bytes
                                  + mem.output_size_in_bytes
                                  + mem.temp_size_in_bytes
                                  - mem.alias_size_in_bytes),
        },
    }


def run_cell(arch: str, shape: str, mesh_kind: str, out_dir: pathlib.Path,
             costing: bool = True, variant: str | None = None,
             **lower_kw) -> dict:
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rec: dict = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                 "mesh_shape": list(mesh.devices.shape),
                 "variant": variant or "baseline", "overrides": repr(lower_kw)}
    t0 = time.time()

    # 1) the real compile (scan, remat, full depth): memory + schedule proof
    cfg, compiled = lower_cell(mesh, arch, shape, **lower_kw)
    full = analyze(compiled)
    rec["full"] = full
    rec["compile_seconds"] = time.time() - t0

    # 2) costing lowers (single-pod only: per-chip roofline; the multi-pod
    #    pass proves the pod axis shards)
    if costing:
        period = cfg.attn_every or 1
        t1 = time.time()
        _, c1 = lower_cell(mesh, arch, shape, n_layers_override=period,
                           unroll=True, **lower_kw)
        a1 = analyze(c1)
        _, c2 = lower_cell(mesh, arch, shape, n_layers_override=2 * period,
                           unroll=True, **lower_kw)
        a2 = analyze(c2)
        L = cfg.n_layers

        def extrapolate(v1, v2):
            b = (v2 - v1) / period
            a = v1 - period * b
            return a + L * b

        rec["costing"] = {
            "flops": extrapolate(a1["flops"], a2["flops"]),
            "bytes": extrapolate(a1["bytes"], a2["bytes"]),
            "collective_bytes": extrapolate(a1["collectives"]["total"],
                                            a2["collectives"]["total"]),
            "collectives_by_kind": {
                k: extrapolate(a1["collectives"].get(k, 0.0),
                               a2["collectives"].get(k, 0.0))
                for k in set(a1["collectives"]) | set(a2["collectives"])
                if k != "total"},
            "period": period,
            "costing_seconds": time.time() - t1,
        }

    out_dir.mkdir(parents=True, exist_ok=True)
    tag = f"__{variant}" if variant else ""
    path = out_dir / f"{arch}__{shape}__{mesh_kind}{tag}.json"
    path.write_text(json.dumps(rec, indent=1))
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="benchmarks/results/dryrun")
    ap.add_argument("--skip-costing", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--variant", default=None,
                    help="tag for §Perf experiments (suffixes the JSON name)")
    ap.add_argument("--layout", default="tp", choices=["tp", "dp", "fsdp"])
    ap.add_argument("--expert-2d", action="store_true")
    ap.add_argument("--microbatches", type=int, default=None)
    args = ap.parse_args()

    from repro.configs import runnable_cells
    out_dir = pathlib.Path(args.out)
    meshes = {"single": ["single"], "multi": ["multi"],
              "both": ["single", "multi"]}[args.mesh]

    cells = [(a, s) for a, s, ok in runnable_cells() if ok
             and (args.arch is None or a == args.arch)
             and (args.shape is None or s == args.shape)]
    skipped = [(a, s) for a, s, ok in runnable_cells() if not ok
               and (args.arch is None or a == args.arch)
               and (args.shape is None or s == args.shape)]
    for a, s in skipped:
        print(f"SKIP {a} x {s} (full attention at 500k — see DESIGN.md §5)")

    failures = []
    for a, s in cells:
        for mk in meshes:
            tag = f"{a} x {s} x {mk}"
            if args.skip_existing and (out_dir / f"{a}__{s}__{mk}.json").exists():
                print(f"HAVE {tag}")
                continue
            try:
                t0 = time.time()
                # costing only needed once (per-chip terms identical across pods)
                rec = run_cell(a, s, mk, out_dir,
                               costing=(not args.skip_costing and mk == "single"),
                               variant=args.variant, layout=args.layout,
                               expert_2d=args.expert_2d,
                               microbatches=args.microbatches)
                mem = rec["full"]["memory"]["peak_hbm_estimate"] / 2**30
                print(f"OK   {tag}: peak/dev ~{mem:.2f} GiB, "
                      f"colls {rec['full']['collectives']['total']/2**20:.1f} MiB, "
                      f"{time.time()-t0:.0f}s")
            except Exception as e:  # noqa: BLE001 — report and continue
                failures.append((tag, repr(e)))
                print(f"FAIL {tag}: {e!r}")
                traceback.print_exc()
    print(f"\n{len(cells)*len(meshes)-len(failures)} ok, {len(failures)} failed,"
          f" {len(skipped)} skipped")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
