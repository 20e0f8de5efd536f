import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# (the two lines above MUST run before any jax-importing module)
"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture x input shape x mesh) cell and record memory/cost/collective
artifacts for the roofline analysis.

  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multipod/--singlepod]
  PYTHONPATH=src python -m repro.launch.dryrun --pipeline   # PP compile check

Artifacts: .cache/dryrun/<arch>__<shape>__<mesh>.json
"""

import argparse
import functools
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, get_config
from repro.distributed.sharding import (
    batch_spec, param_sharding, sharding_rules)
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (
    decode_input_specs, prefill_input_specs, train_input_specs)
from repro.models import abstract_model, model_specs, shapes_for
from repro.models.config import ShapeConfig
from repro.models.lm import decode_step, prefill
from repro.training.optimizer import AdamWConfig, adamw_init, opt_state_specs
from repro.training.train_loop import TrainConfig, build_train_step

OUT_DIR = os.path.join(os.environ.get("REPRO_CACHE", ".cache"), "dryrun")

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "pred": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def collective_bytes(hlo_text: str) -> dict:
    """Sum result bytes of every collective op in optimized HLO."""
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    shape_re = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
    op_re = re.compile(
        r"=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[^\]]*\](?:\{[^}]*\})?))\s+"
        r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
        r"collective-permute)(?:-start)?\(")
    for m in op_re.finditer(hlo_text):
        type_str, op = m.group(1), m.group(2)
        nbytes = 0
        for dt, dims in shape_re.findall(type_str):
            if dt not in _DTYPE_BYTES:
                continue
            numel = 1
            for d in dims.split(","):
                if d:
                    numel *= int(d)
            nbytes += numel * _DTYPE_BYTES[dt]
        out[op] += nbytes
        counts[op] += 1
    return {"bytes": out, "counts": counts,
            "total_bytes": int(sum(out.values()))}


def _accum_for(cfg) -> int:
    if cfg.d_model >= 7000 or cfg.n_layers >= 90:
        return 8
    if cfg.d_model >= 2560:
        return 4
    return 1


def _memory_analysis(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    fields = ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes")
    out = {}
    for f in fields:
        try:
            out[f] = int(getattr(ma, f))
        except Exception:
            pass
    return out


def _cost_analysis(compiled) -> dict:
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return {k: float(v) for k, v in dict(ca).items()
            if isinstance(v, (int, float))}


def run_cell(arch: str, shape: ShapeConfig, *, multi_pod: bool,
             save_hlo: bool = False) -> dict:
    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    # decode serves read-only weights: replicate over dp instead of ZeRO-3
    # (kills per-token weight all-gathers — §Perf iteration 4). Archs whose
    # replicated params would blow the 16 GiB budget (llama-90B dense) keep
    # FSDP and pay the gathers — the policy is capacity-aware.
    mode = "train"
    if shape.kind == "decode":
        from repro.models.accounting import local_param_bytes
        from repro.distributed.sharding import mesh_axis_sizes

        serve_bytes = local_param_bytes(
            cfg, mesh_axis_sizes(mesh), mode="serve")
        mode = "serve" if serve_bytes < 9 * 2**30 else "train"
    rules = sharding_rules(mesh, mode=mode)
    record_mode = mode
    pspecs = model_specs(cfg, rules)
    psh = param_sharding(pspecs, mesh)
    params_abs = abstract_model(cfg, jnp.bfloat16)
    record = {
        "arch": arch, "shape": shape.name, "kind": shape.kind,
        "param_mode": record_mode if shape.kind == "decode" else "train",
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "n_devices": mesh.devices.size,
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
    }
    t0 = time.time()

    if shape.kind == "train":
        tc = TrainConfig(accum_steps=_accum_for(cfg),
                         accum_dtype="bfloat16",
                         opt=AdamWConfig(quantize_moments=True))
        record["accum_steps"] = tc.accum_steps
        opt_abs = jax.eval_shape(
            functools.partial(adamw_init, cfg=tc.opt), params_abs)
        ospecs = opt_state_specs(pspecs, tc.opt, params_abs)
        osh = param_sharding(ospecs, mesh)
        state_abs = {"params": params_abs, "opt": opt_abs}
        state_sh = {"params": psh, "opt": osh}
        batch_abs, batch_sh = train_input_specs(cfg, shape, mesh)
        step = build_train_step(cfg, tc)
        jitted = jax.jit(
            step,
            in_shardings=(state_sh, batch_sh, None),
            out_shardings=(state_sh, None),
            donate_argnums=(0,))
        with mesh:
            lowered = jitted.lower(
                state_abs, batch_abs, jax.ShapeDtypeStruct((), jnp.int32))
            compiled = lowered.compile()
    elif shape.kind == "decode":
        (token, cache, cur_len), (tok_sh, cache_sh, len_sh) = \
            decode_input_specs(cfg, shape, mesh)
        fn = functools.partial(decode_step, cfg=None)  # placeholder

        def serve_step(params, tok, cch, cl):
            return decode_step(params, cfg, tok, cch, cl)

        jitted = jax.jit(
            serve_step,
            in_shardings=(psh, tok_sh, cache_sh, len_sh),
            out_shardings=(None, cache_sh),
            donate_argnums=(2,))
        with mesh:
            lowered = jitted.lower(params_abs, token, cache, cur_len)
            compiled = lowered.compile()
    elif shape.kind == "prefill":
        batch_abs, batch_sh = prefill_input_specs(cfg, shape, mesh)
        out_spec = NamedSharding(
            mesh, P(batch_spec(mesh, shape.global_batch, 0)[0], None,
                    "model" if cfg.d_model % 16 == 0 else None))

        def prefill_step(params, batch):
            return prefill(params, cfg, batch["tokens"], batch.get("aux"))

        jitted = jax.jit(prefill_step, in_shardings=(psh, batch_sh),
                         out_shardings=out_spec)
        with mesh:
            lowered = jitted.lower(params_abs, batch_abs)
            compiled = lowered.compile()
    else:
        raise ValueError(shape.kind)

    record["compile_seconds"] = round(time.time() - t0, 1)
    record["memory"] = _memory_analysis(compiled)
    record["cost"] = _cost_analysis(compiled)
    hlo = compiled.as_text()
    record["collectives"] = collective_bytes(hlo)
    record["hlo_bytes"] = len(hlo)
    # always keep the optimized HLO: the roofline analyzer re-walks it with
    # while-loop trip counts (XLA cost analysis counts loop bodies once)
    import gzip

    hdir = os.path.join(OUT_DIR, "hlo")
    os.makedirs(hdir, exist_ok=True)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    with gzip.open(os.path.join(
            hdir, f"{arch}__{shape.name}__{mesh_name}.txt.gz"), "wt") as f:
        f.write(hlo)
    print(f"[dryrun] {arch} {shape.name} mesh={record['mesh']} "
          f"compile={record['compile_seconds']}s "
          f"flops={record['cost'].get('flops', float('nan')):.3g} "
          f"coll={record['collectives']['total_bytes']:.3g}B")
    mem = record["memory"]
    if mem:
        print(f"  memory: args={mem.get('argument_size_in_bytes', 0)/2**30:.2f}GiB "
              f"temp={mem.get('temp_size_in_bytes', 0)/2**30:.2f}GiB "
              f"out={mem.get('output_size_in_bytes', 0)/2**30:.2f}GiB")
    return record


def run_pipeline_check(multi_pod: bool = True) -> dict:
    """PP-over-pod compile check on qwen2-0.5b (DESIGN.md §5)."""
    from repro.distributed.pipeline import pipeline_forward
    from repro.models.blocks import stage_forward, superblock_table

    cfg = get_config("qwen2-0.5b")
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_stages = 2
    table, kinds, n_rep, _ = superblock_table(cfg)
    params_abs = abstract_model(cfg, jnp.bfloat16)
    blocks = params_abs["blocks"]
    staged = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(
            (n_stages, l.shape[0] // n_stages) + l.shape[1:], l.dtype),
        blocks)

    def stage_fn(p_stage, x):
        h, _ = stage_forward(p_stage, None, cfg, kinds, x)
        return h

    n_micro, bm, s = 4, 8, 4096
    x_micro = jax.ShapeDtypeStruct((n_micro, bm, s, cfg.d_model),
                                   jnp.bfloat16)
    run = pipeline_forward(stage_fn, n_stages, axis="pod")
    spec_p = jax.tree_util.tree_map(lambda _: P("pod"), staged)
    fn = jax.shard_map(run, mesh=mesh, in_specs=(spec_p, P()),
                       out_specs=P(), check_vma=False)
    t0 = time.time()
    with mesh:
        lowered = jax.jit(fn).lower(staged, x_micro)
        compiled = lowered.compile()
    rec = {"arch": "qwen2-0.5b", "shape": "pipeline_pp2", "kind": "pipeline",
           "mesh": "2x16x16", "compile_seconds": round(time.time() - t0, 1),
           "memory": _memory_analysis(compiled),
           "cost": _cost_analysis(compiled),
           "collectives": collective_bytes(compiled.as_text())}
    print(f"[dryrun] pipeline pp2 compile={rec['compile_seconds']}s "
          f"coll={rec['collectives']['total_bytes']:.3g}B")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default="")
    ap.add_argument("--shape", type=str, default="")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--singlepod", action="store_true")
    ap.add_argument("--pipeline", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    os.makedirs(OUT_DIR, exist_ok=True)

    meshes = []
    if args.singlepod or not args.multipod:
        meshes.append(False)
    if args.multipod or not args.singlepod:
        meshes.append(True)

    if args.pipeline:
        rec = run_pipeline_check()
        with open(os.path.join(OUT_DIR, "pipeline_pp2.json"), "w") as f:
            json.dump(rec, f, indent=1)
        return

    cells = []
    archs = [args.arch] if args.arch else sorted(ARCHS)
    for arch in archs:
        cfg = get_config(arch)
        for shape in shapes_for(cfg):
            if args.shape and shape.name != args.shape:
                continue
            for mp in meshes:
                cells.append((arch, shape, mp))

    failures = []
    for arch, shape, mp in cells:
        mesh_name = "2x16x16" if mp else "16x16"
        path = os.path.join(
            OUT_DIR, f"{arch}__{shape.name}__{mesh_name}.json")
        if os.path.exists(path) and not args.force:
            print(f"[skip] {path}")
            continue
        try:
            rec = run_cell(arch, shape, multi_pod=mp)
            with open(path + ".tmp", "w") as f:
                json.dump(rec, f, indent=1)
            os.replace(path + ".tmp", path)
        except Exception as e:
            failures.append((arch, shape.name, mesh_name, repr(e)))
            traceback.print_exc()
        jax.clear_caches()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nall requested cells compiled")


if __name__ == "__main__":
    main()
