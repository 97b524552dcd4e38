import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes, with zero device allocation (ShapeDtypeStruct inputs).

Per cell this emits a JSON artifact with:
  * memory_analysis (per-device bytes: args / outputs / temps)
  * cost_analysis   (HLO FLOPs / bytes accessed)
  * collective bytes parsed from the optimized HLO text, by collective type
  * compile wall time

For roofline cost extraction (scan bodies are counted ONCE by XLA's cost
analysis — measured, see DESIGN.md §6) it can additionally compile unrolled
1-block and 2-block variants (--roofline) whose difference isolates the exact
per-block cost.

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
  python -m repro.launch.dryrun --all --multi-pod both --out results/dryrun
"""
import argparse
import dataclasses
import json
import pathlib
import re
import time
from collections import defaultdict

import jax
import numpy as np

from repro.configs import (SHAPES, TrainConfig, all_cells, cell_skip_reason,
                           get_config, get_shape)
from repro.launch.input_specs import input_specs
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import make_step
from repro.models import MeshInfo
from repro.models.params import abstract

# HLO collective result parsing: "bf16[128,4096]{...} all-reduce(..." etc.
_COLL_RE = re.compile(
    r"=\s+(\w+)\[([\d,]*)\][^\s]*\s+(all-reduce|all-gather|reduce-scatter|"
    r"all-to-all|collective-permute)\(")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0, "tuple": 0,
}


def parse_collectives(hlo_text: str):
    """Sum result bytes per collective type (per-device program => per-chip)."""
    out = defaultdict(lambda: {"count": 0, "bytes": 0})
    for m in _COLL_RE.finditer(hlo_text):
        dt, dims, kind = m.groups()
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out[kind]["count"] += 1
        out[kind]["bytes"] += n * _DTYPE_BYTES[dt]
    return dict(out)


def collective_link_bytes(colls) -> int:
    """Roofline bytes-per-chip-on-link: all-reduce counts 2x (ring)."""
    total = 0
    for kind, v in colls.items():
        factor = 2 if kind == "all-reduce" else 1
        total += factor * v["bytes"]
    return total


def compile_cell(cfg, shape, mesh, tc=None, donate_cache=True):
    """Lower + compile one cell; returns (compiled, artifact_dict)."""
    mi = MeshInfo(mesh)
    step, state_specs = make_step(cfg, shape, mi, tc)
    state_abs = {k: abstract(v, mesh) for k, v in state_specs.items()}
    ins = input_specs(cfg, shape, mesh)

    t0 = time.time()
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            jitted = jax.jit(step, donate_argnums=(0, 1))
            lowered = jitted.lower(state_abs["params"], state_abs["opt_state"],
                                   ins["batch"])
        elif shape.kind == "prefill":
            jitted = jax.jit(step, donate_argnums=(2,) if donate_cache else ())
            lowered = jitted.lower(state_abs["params"], ins["batch"],
                                   ins["cache"])
        else:
            jitted = jax.jit(step, donate_argnums=(3,) if donate_cache else ())
            lowered = jitted.lower(state_abs["params"], ins["token"],
                                   ins["pos"], ins["cache"])
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    colls = parse_collectives(hlo)
    art = {
        "arch": cfg.name,
        "shape": dataclasses.asdict(shape),
        "mesh": {"shape": tuple(int(s) for s in np.shape(mesh.devices)),
                 "axes": mesh.axis_names},
        "lower_s": round(t1 - t0, 3),
        "compile_s": round(t2 - t1, 3),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_bytes_per_device": mem.argument_size_in_bytes
                + mem.temp_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes,
        },
        "cost": {"flops": cost.get("flops", 0.0),
                 "bytes_accessed": cost.get("bytes accessed", 0.0)},
        "collectives": colls,
        "collective_link_bytes": collective_link_bytes(colls),
    }
    return compiled, art


def reduce_to_blocks(cfg, n: int):
    """Unrolled n-block variant of cfg (for per-block cost differencing)."""
    kw = dict(
        n_layers=cfg.first_k_dense + n * len(cfg.block_pattern),
        scan_blocks=False, unroll_scans=True,
        # single flash block: identical FLOPs, no 1000-step unrolled compile
        flash_q_chunk=1 << 30, flash_kv_chunk=1 << 30,
    )
    if cfg.is_encdec:
        kw["n_enc_layers"] = n * len(cfg.enc_block_pattern)
    return dataclasses.replace(cfg, **kw)


def run_cell(arch: str, shape_name: str, multi_pod: bool, roofline: bool,
             out_dir, tc=None, page_size=None):
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    skip = cell_skip_reason(cfg, shape)
    tag = f"{arch}__{shape_name}__{'pod2' if multi_pod else 'pod1'}"
    if skip:
        art = {"arch": arch, "shape": shape_name, "skipped": skip}
        _write(out_dir, tag, art)
        print(f"SKIP {tag}: {skip}")
        return art
    mesh = make_production_mesh(multi_pod=multi_pod)
    _, art = compile_cell(cfg, shape, mesh, tc)
    if roofline:
        for n in (1, 2):
            sub = reduce_to_blocks(cfg, n)
            _, sub_art = compile_cell(sub, shape, mesh, tc)
            art[f"unrolled_{n}block"] = {
                "cost": sub_art["cost"],
                "collectives": sub_art["collectives"],
                "collective_link_bytes": sub_art["collective_link_bytes"],
                "compile_s": sub_art["compile_s"],
            }
        art["n_blocks"] = cfg.n_blocks
    _write(out_dir, tag, art)
    mem_gb = art["memory"]["peak_bytes_per_device"] / 2**30
    print(f"OK   {tag}: compile={art['compile_s']:.1f}s "
          f"peak={mem_gb:.2f}GiB/dev flops={art['cost']['flops']:.3e} "
          f"coll={art['collective_link_bytes']:.3e}B", flush=True)
    return art


def _write(out_dir, tag, art):
    if out_dir:
        p = pathlib.Path(out_dir)
        p.mkdir(parents=True, exist_ok=True)
        (p / f"{tag}.json").write_text(json.dumps(art, indent=1, default=str))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--roofline", action="store_true",
                    help="also compile unrolled 1/2-block cost variants")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    pods = {"single": [False], "multi": [True], "both": [False, True]}[args.multi_pod]
    if args.all:
        cells = [(a, s) for a, s, _ in all_cells()]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        cells = [(args.arch, args.shape)]

    t0 = time.time()
    n_ok = n_skip = n_fail = 0
    for arch, shape_name in cells:
        for mp in pods:
            tag = f"{arch}__{shape_name}__{'pod2' if mp else 'pod1'}"
            if args.skip_existing and (pathlib.Path(args.out) / f"{tag}.json").exists():
                existing = json.loads((pathlib.Path(args.out) / f"{tag}.json").read_text())
                if not existing.get("error"):
                    n_ok += 0 if existing.get("skipped") else 1
                    n_skip += 1 if existing.get("skipped") else 0
                    continue
            try:
                art = run_cell(arch, shape_name, mp, args.roofline, args.out)
                if art.get("skipped"):
                    n_skip += 1
                else:
                    n_ok += 1
            except Exception as e:  # noqa: BLE001 — record and continue
                n_fail += 1
                tag = f"{arch}__{shape_name}__{'pod2' if mp else 'pod1'}"
                _write(args.out, tag, {"arch": arch, "shape": shape_name,
                                       "error": repr(e)})
                print(f"FAIL {tag}: {e!r}", flush=True)
    print(f"\ndone in {time.time()-t0:.0f}s: ok={n_ok} skip={n_skip} "
          f"fail={n_fail}")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
