import os
# a CPU tool with described devices: pin the platform so neither this
# process nor its --fork children reach for an accelerator
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: for each cell
the step function (train / prefill / decode) is jit'd with explicit
shardings, ``.lower(...)``'d on ShapeDtypeStruct inputs, ``.compile()``'d,
and its ``memory_analysis()`` / ``cost_analysis()`` / collective schedule
recorded to ``artifacts/dryrun/<arch>__<shape>__<mesh>.json``.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --all            # every cell
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b \
      --shape train_4k --mesh multi                             # one cell

Cells are resumable: existing artifacts are skipped unless --force.
The per-cell compile runs in a fresh subprocess by default (--fork) so a
pathological cell cannot take down the sweep.
"""
import argparse
import json
import pathlib
import re
import subprocess
import sys
import time
import traceback

ARTIFACTS = pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def _mesh(kind: str):
    from .mesh import make_production_mesh
    return make_production_mesh(multi_pod=(kind == "multi"))


def _accum_for(arch_cfg) -> int:
    # keep microbatch activations ~2k tokens per data-shard row
    return 8 if arch_cfg.d_model >= 4096 else 2


def _train_dtypes(arch_cfg):
    """Param/moment dtypes: bf16 state for the near-trillion class."""
    import jax.numpy as jnp
    big = arch_cfg.d_model >= 6144 or (arch_cfg.moe is not None
                                       and arch_cfg.moe.n_experts >= 64)
    return (jnp.bfloat16, jnp.bfloat16) if big else (jnp.float32,
                                                     jnp.float32)


def lower_cell(arch: str, shape_name: str, mesh_kind: str):
    """Build the jitted step for one cell and lower it (no compile)."""
    import jax
    import jax.numpy as jnp

    from ..configs import get_config
    from ..configs.base import SHAPES
    from ..distributed import sharding as shd
    from ..distributed.act_sharding import activation_policy
    from ..models.model_zoo import Model
    from ..serve.engine import ServeConfig, jit_decode_step
    from ..train import optimizer as opt
    from ..train.train_loop import (TrainConfig, batch_shardings,
                                    jit_train_step, split_microbatches)

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    model = Model(cfg)
    mesh = _mesh(mesh_kind)
    specs = model.input_specs(shape)

    if shape.kind == "train":
        pdt, mdt = _train_dtypes(cfg)
        tcfg = TrainConfig(opt=opt.OptConfig(moment_dtype=mdt),
                           accum=_accum_for(cfg), remat=True,
                           param_dtype=pdt)
        batch = split_microbatches(specs["batch"], tcfg.accum)
        params = model.abstract_params(dtype=pdt)
        state = {"params": params,
                 "opt": {"mu": jax.tree_util.tree_map(
                     lambda s: jax.ShapeDtypeStruct(s.shape, mdt), params),
                     "nu": jax.tree_util.tree_map(
                     lambda s: jax.ShapeDtypeStruct(s.shape, mdt), params),
                     "step": jax.ShapeDtypeStruct((), jnp.int32)},
                 "ef": None}
        step = jit_train_step(model, tcfg, mesh, specs["batch"])
        with activation_policy(mesh):
            return step.lower(state, batch), mesh

    if shape.kind == "prefill":
        scfg = ServeConfig(max_len=shape.seq_len, batch=shape.global_batch)
        params = model.abstract_params(dtype=jnp.bfloat16)
        pshard = shd.param_shardings(model.abstract_ptree(), mesh)
        bshard = shd.data_shardings(specs["batch"], mesh)

        def prefill_step(p, b):
            return model.prefill(p, b, scfg.max_len, dtype=jnp.bfloat16)

        step = jax.jit(prefill_step, in_shardings=(pshard, bshard))
        with activation_policy(mesh):
            return step.lower(params, specs["batch"]), mesh

    # decode
    scfg = ServeConfig(max_len=shape.seq_len, batch=shape.global_batch)
    params = model.abstract_params(dtype=jnp.bfloat16)
    step = jit_decode_step(model, scfg, mesh, specs)
    with activation_policy(mesh):
        return step.lower(params, specs["tokens"], specs["caches"],
                          specs["cache_len"], specs["extra"]), mesh


COLLECTIVE_RE = re.compile(
    r"(\w[\w.\-]*)\s*=\s*((?:[a-z0-9]+\[[^\]]*\])(?:, [a-z0-9]+\[[^\]]*\])*|\([^)]*\))\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)\(",
)
SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s32": 4, "u32": 4,
               "s64": 8, "u64": 8, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
               "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16}


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Sum result-shape bytes of every collective op in optimized HLO."""
    out: dict[str, float] = {}
    for m in COLLECTIVE_RE.finditer(hlo_text):
        shapes_str, kind = m.group(2), m.group(3)
        total = 0.0
        for sm in SHAPE_RE.finditer(shapes_str):
            dt, dims = sm.group(1), sm.group(2)
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * DTYPE_BYTES.get(dt, 4)
        out[kind] = out.get(kind, 0.0) + total
    return out


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             out_path: pathlib.Path) -> dict:
    t0 = time.time()
    lowered, mesh = lower_cell(arch, shape_name, mesh_kind)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    try:
        mem = compiled.memory_analysis()
        mem_info = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes":
                getattr(mem, "generated_code_size_in_bytes", None),
        }
    except Exception as exc:
        mem_info = {"error": str(exc)}
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        cost = {k: float(v) for k, v in cost.items()
                if isinstance(v, (int, float))}
    except Exception as exc:
        cost = {"error": str(exc)}

    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    try:
        from ..roofline.hlo_analyzer import analyze_hlo
        hlo_stats = analyze_hlo(hlo).as_dict()
    except Exception as exc:
        hlo_stats = {"error": str(exc)}
    n_dev = mesh.devices.size
    record = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "n_devices": int(n_dev),
        "mesh_shape": {k: int(v) for k, v in
                       zip(mesh.axis_names, mesh.devices.shape)},
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": mem_info,
        "flops": cost.get("flops"),
        "bytes_accessed": cost.get("bytes accessed"),
        "cost_raw": {k: v for k, v in cost.items()
                     if k in ("flops", "bytes accessed", "transcendentals")},
        "collective_bytes": coll,
        "hlo_stats": hlo_stats,
        "hlo_bytes": len(hlo),
    }
    out_path.parent.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(record, indent=1))
    return record


def all_cells():
    from ..configs import ALL_ARCHS, get_config, shapes_for
    for arch in ALL_ARCHS:
        for shape in shapes_for(get_config(arch)):
            for mesh_kind in ("single", "multi"):
                yield arch, shape.name, mesh_kind


def cell_path(arch: str, shape: str, mesh_kind: str) -> pathlib.Path:
    return ARTIFACTS / f"{arch}__{shape}__{mesh_kind}.json"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=("single", "multi"))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--fork", action="store_true",
                    help="run each cell in a fresh subprocess")
    args = ap.parse_args()

    if args.all:
        cells = list(all_cells())
    else:
        if not (args.arch and args.shape and args.mesh):
            ap.error("--all or all of --arch/--shape/--mesh")
        cells = [(args.arch, args.shape, args.mesh)]

    failures = []
    for arch, shape, mesh_kind in cells:
        path = cell_path(arch, shape, mesh_kind)
        tag = f"{arch} x {shape} x {mesh_kind}"
        if path.exists() and not args.force:
            print(f"[skip] {tag}", flush=True)
            continue
        if args.fork and len(cells) > 1:
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--mesh", mesh_kind]
            if args.force:
                cmd.append("--force")
            r = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=7200)
            ok = r.returncode == 0 and path.exists()
            print(f"[{'ok' if ok else 'FAIL'}] {tag}", flush=True)
            if not ok:
                failures.append(tag)
                err = (r.stderr or "")[-2000:]
                (path.parent / f"FAIL_{path.stem}.log").parent.mkdir(
                    parents=True, exist_ok=True)
                (path.parent / f"FAIL_{path.stem}.log").write_text(err)
            continue
        try:
            rec = run_cell(arch, shape, mesh_kind, path)
            print(f"[ok] {tag}: compile {rec['compile_s']}s "
                  f"flops={rec.get('flops')} "
                  f"coll={ {k: f'{v/1e9:.2f}GB' for k, v in rec['collective_bytes'].items()} }",
                  flush=True)
            # headline evidence for EXPERIMENTS.md §Dry-run
            print(f"     memory: {rec['memory']}", flush=True)
        except Exception:
            failures.append(tag)
            print(f"[FAIL] {tag}\n{traceback.format_exc()}", flush=True)
    if failures:
        print(f"FAILED cells: {failures}", flush=True)
        return 1
    print("all cells ok", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
