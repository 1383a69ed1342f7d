import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × input-shape) combination
on the production meshes, WITHOUT allocating any real arrays.

Per combination this prints/records:
  * compile success,
  * memory analysis (bytes per device: arguments, temps, outputs),
  * cost analysis (HLO flops/bytes — per-scan-iteration, see roofline.py for
    the trip-count-corrected numbers),
  * the collective-op inventory parsed from the compiled HLO.

Usage:
  python -m repro.launch.dryrun --arch phi4-mini-3.8b --shape train_4k
  python -m repro.launch.dryrun --all --mesh pod1 --out results/dryrun.json
"""
import argparse
import json
import re
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_NAMES, get_config
from repro.launch.mesh import make_production_mesh
from repro.models.config import INPUT_SHAPES, ArchConfig, InputShape
from repro.training import dist_steps as ds


# ---------------------------------------------------------------------------
# long_500k policy (DESIGN.md §6): native for state-bounded archs, sliding-
# window serving variant for full-attention archs, skip whisper.
# ---------------------------------------------------------------------------

LONG_NATIVE = {"xlstm-125m", "jamba-v0.1-52b", "gemma2-9b"}
LONG_SWA = {"phi4-mini-3.8b", "qwen2.5-3b", "llama3-405b",
            "qwen3-moe-235b-a22b", "kimi-k2-1t-a32b", "internvl2-2b"}
LONG_SKIP = {"whisper-tiny": "enc-dec audio: 500k-token decode is "
                             "semantically void for 30s audio"}
SWA_WINDOW = 32768

DTYPE_OVERRIDES = dict(param_dtype="bfloat16", compute_dtype="bfloat16")

COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?(?!-done)\b")
SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def collective_bytes_of_line(line: str) -> int:
    """Sum result-shape bytes of a collective HLO line (output ≈ traffic
    proxy; all-reduce moves ~2× in a ring — accounted in roofline.py)."""
    head = line.split("=", 1)
    if len(head) < 2:
        return 0
    # result shapes appear between '=' and the op name
    m = COLLECTIVE_RE.search(line)
    if not m:
        return 0
    result_part = line[len(head[0]) + 1: m.start()]
    total = 0
    for dt, dims in SHAPE_RE.findall(result_part):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Inventory: op kind -> (count, bytes). Only top-level + loop bodies
    counted ONCE (per-iteration); roofline.py handles trip counts."""
    out: dict[str, list] = {}
    for line in hlo_text.splitlines():
        m = COLLECTIVE_RE.search(line)
        if not m or "=" not in line:
            continue
        kind = m.group(1)
        b = collective_bytes_of_line(line)
        if kind not in out:
            out[kind] = [0, 0]
        out[kind][0] += 1
        out[kind][1] += b
    return {k: {"count": v[0], "bytes": v[1]} for k, v in out.items()}


def prepare_cfg(arch: str, shape: InputShape, mesh, *,
                for_cost: bool = False, variant: str = "base") -> ArchConfig:
    import math
    opts = set(variant.split("+"))
    cfg = get_config(arch).replace(**DTYPE_OVERRIDES)
    dp = math.prod(mesh.shape[a] for a in mesh.axis_names if a != "model")
    cfg = cfg.replace(moe_shards=dp)   # shard-local MoE dispatch
    batch_axes = ("pod", "data") if "pod" in mesh.axis_names else "data"
    if shape.global_batch % dp != 0:   # long_500k: batch 1 — replicate
        batch_axes = None
        cfg = cfg.replace(moe_shards=1)
    cfg = cfg.replace(act_spec=(batch_axes, None, "model"))
    if "gqarep" in opts:
        cfg = cfg.replace(attn_gqa_repeat=True)
    if "seqact" in opts:
        # §Perf: Megatron-SP-style activation sharding — shard the SEQUENCE
        # dim over the model axis between blocks instead of d_model. The
        # baseline (d→model) forces every weight-grad dot to all-gather its
        # activation over the model axis (the dW contraction needs full d);
        # sequence sharding keeps d intact so dW = xᵀdy reduces over the
        # data axis only (reduce-scatter), no giant gathers.
        cfg = cfg.replace(act_spec=(batch_axes, "model", None))
    if "noact" in opts:
        # §Perf: drop the per-block activation resharding constraint — kills
        # the per-layer all-gather/all-to-all pair at the cost of replicated
        # saved remat inputs (only safe for d_model ≤ ~8k archs).
        cfg = cfg.replace(act_spec=(batch_axes, None, None))
    if shape.kind == "train":
        cfg = cfg.replace(remat=True)
    if shape.name == "long_500k" and arch in LONG_SWA:
        pass  # window applied by make_decode_step(window_override=...)
    if shape.kind == "decode":
        # delta-cache serve contract: caches are read-only scan xs, deltas
        # are the tiny ys — safe to keep the layer scan.
        cfg = cfg.replace(attn_chunk=8192)
    if for_cost:
        cfg = cfg.replace(scan_layers=False, unroll_loops=True,
                          attn_chunk=4096 if shape.kind != "decode" else 16384,
                          ssm_chunk=2048, mlstm_chunk=2048)
    return cfg


def build_step(arch: str, shape: InputShape, mesh, *, for_cost: bool = False,
               num_layers: int | None = None, variant: str = "base"):
    """Returns (fn, args, in_shardings, meta) or None if skipped."""
    if shape.name == "long_500k" and arch in LONG_SKIP:
        return None
    opts = set(variant.split("+"))
    cfg = prepare_cfg(arch, shape, mesh, for_cost=for_cost, variant=variant)
    if num_layers is not None:
        cfg = cfg.replace(num_layers=num_layers)
    meta = {"arch": arch, "shape": shape.name, "kind": shape.kind,
            "variant": variant}

    if shape.kind == "train":
        plan = None
        if "nofl" not in opts:
            plan = ds.fli.make_fl_plan(
                num_clients=int(np.prod([mesh.shape[a] for a in mesh.axis_names
                                         if a != "model"])),
                num_clusters=4, key=jax.random.PRNGKey(0))
        import jax.numpy as _jnp
        kw = {}
        if "bf16accum" in opts:
            kw["accum_dtype"] = _jnp.bfloat16
        if "cechunk" in opts:
            kw["ce_mode"] = "resharded"
        fn, args, shardings = ds.make_train_step(cfg, shape, mesh, plan=plan,
                                                 **kw)
        meta["microbatches"] = ds.auto_microbatches(cfg, shape, mesh)
        return fn, args, shardings, None, meta
    if shape.kind == "prefill":
        fn, args, shardings, out_specs = ds.make_prefill_step(cfg, shape, mesh)
        return fn, args, shardings, out_specs, meta
    # decode
    ov = SWA_WINDOW if (shape.name == "long_500k" and arch in LONG_SWA) else None
    meta["window_override"] = ov
    fn, args, shardings = ds.make_decode_step(
        cfg, shape, mesh, window_override=ov,
        replicate_cache_heads="cacherep" in opts)
    return fn, args, shardings, None, meta


def run_one(arch: str, shape_name: str, mesh, mesh_name: str,
            variant: str = "base") -> dict:
    shape = INPUT_SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "variant": variant, "status": "skip"}
    t0 = time.time()
    try:
        built = build_step(arch, shape, mesh, variant=variant)
        if built is None:
            rec["reason"] = LONG_SKIP.get(arch, "n/a")
            return rec
        fn, args, shardings, out_specs, meta = built
        rec.update(meta)
        with mesh:
            jit_kw = {"in_shardings": ds.sr.named(shardings, mesh)}
            if out_specs is not None:
                jit_kw["out_shardings"] = ds.sr.named(out_specs, mesh)
            if shape.kind == "train":
                # params & opt_state are donated (updated in place on TPU)
                jit_kw["donate_argnums"] = (0, 1)
            # decode: caches are READ-ONLY (delta contract) — no donation
            lowered = jax.jit(fn, **jit_kw).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            ma = compiled.memory_analysis()
            ca = compiled.cost_analysis() or {}
            hlo = compiled.as_text()
            colls = parse_collectives(hlo)
        rec.update({
            "status": "ok",
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "mem": {
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "peak_per_device": (ma.argument_size_in_bytes
                                    + ma.temp_size_in_bytes
                                    + ma.output_size_in_bytes
                                    - ma.alias_size_in_bytes),
            },
            "cost": {"flops": ca.get("flops", 0.0),
                     "bytes": ca.get("bytes accessed", 0.0)},
            "collectives": colls,
        })
    except Exception as e:  # noqa: BLE001 — dry-run reports failures
        rec["status"] = "fail"
        rec["error"] = f"{type(e).__name__}: {e}"[:2000]
        rec["traceback"] = traceback.format_exc()[-4000:]
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod1", choices=["pod1", "pod2", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--variant", default="base")
    ap.add_argument("--out", default="results/dryrun.json")
    args = ap.parse_args()

    archs = ARCH_NAMES if (args.all or args.arch is None) else [args.arch]
    shapes = (list(INPUT_SHAPES) if (args.all or args.shape is None)
              else [args.shape])
    meshes = (["pod1", "pod2"] if args.mesh == "both" else [args.mesh])

    out_path = Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    results = []
    if out_path.exists():
        results = json.loads(out_path.read_text())
    done = {(r["arch"], r["shape"], r["mesh"], r.get("variant", "base"))
            for r in results if r["status"] in ("ok", "skip")}

    for mesh_name in meshes:
        mesh = make_production_mesh(multi_pod=(mesh_name == "pod2"))
        for arch in archs:
            for shape_name in shapes:
                key = (arch, shape_name, mesh_name, args.variant)
                if key in done:
                    continue
                print(f"[dryrun] {arch} × {shape_name} × {mesh_name} "
                      f"({args.variant}) ...", flush=True)
                rec = run_one(arch, shape_name, mesh, mesh_name,
                              variant=args.variant)
                print(f"  -> {rec['status']} "
                      f"mem/device={rec.get('mem', {}).get('peak_per_device', 0)/2**30:.2f} GiB "
                      f"compile={rec.get('compile_s', 0)}s "
                      f"{rec.get('error', '')}", flush=True)
                results = [r for r in results
                           if (r["arch"], r["shape"], r["mesh"],
                               r.get("variant", "base")) != key]
                results.append(rec)
                out_path.write_text(json.dumps(results, indent=1))
                jax.clear_caches()

    n_ok = sum(r["status"] == "ok" for r in results)
    n_fail = sum(r["status"] == "fail" for r in results)
    n_skip = sum(r["status"] == "skip" for r in results)
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skip, {n_fail} fail")
    sys.exit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
