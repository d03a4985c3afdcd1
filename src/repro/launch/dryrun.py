import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).

"""Multi-pod dry-run: lower + compile every (architecture x input-shape) on
the production meshes, prove it fits (memory_analysis), and extract the
roofline raw terms (cost_analysis + collective bytes parsed from HLO).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3_8b \
        --shape train_4k [--multi-pod] [--out benchmarks/artifacts/dryrun]
One (arch, shape, mesh) combo per process — device count is process-global.
"""
import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax

from repro.configs import INPUT_SHAPES, get_config
from repro.launch import shardings as shd
from repro.launch import steps as st
from repro.launch.mesh import make_production_mesh


# ---------------------------------------------------------------------------
# HLO collective parsing (§ROOFLINE: collective_bytes is not in cost_analysis)
# ---------------------------------------------------------------------------
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes_from_hlo(hlo: str) -> dict:
    """Sum result bytes of every collective op, scaling ops inside while-loop
    bodies by the loop trip count (layer scans appear once in HLO text)."""
    comp_name = "entry"
    comp_colls = {comp_name: []}
    calls = []           # (caller_comp, callee_name, is_while_body)
    cond_consts = {}
    for line in hlo.splitlines():
        stripped = line.strip()
        m = re.match(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\([^)]*\)\s*->", stripped)
        if m and stripped.endswith("{"):
            comp_name = m.group(2)
            comp_colls.setdefault(comp_name, [])
            continue
        for kind in _COLLECTIVES:
            if re.search(rf"\b{kind}(-start|-done)?\(", stripped):
                lhs = stripped.split(f" {kind}", 1)[0]
                b = _shape_bytes(lhs)
                if kind == "all-gather" and "-done(" in stripped:
                    b = 0  # counted at -start
                comp_colls[comp_name].append((kind, b))
                break
        mw = re.search(r"while\(.*\).*condition=%?([\w.\-]+).*body=%?([\w.\-]+)",
                       stripped)
        if not mw:
            mw = re.search(r"while\(.*\).*body=%?([\w.\-]+).*condition=%?([\w.\-]+)",
                           stripped)
            if mw:
                cond, body = mw.group(2), mw.group(1)
            else:
                cond = body = None
        else:
            cond, body = mw.group(1), mw.group(2)
        if body:
            calls.append((comp_name, body, cond))
        mc = re.search(r"s32\[\]\s+constant\((\d+)\)", stripped)
        if mc:
            cond_consts.setdefault(comp_name, 0)
            cond_consts[comp_name] = max(cond_consts[comp_name],
                                         int(mc.group(1)))
        mcall = re.search(r"(?:call|fusion)\(.*\).*(?:to_apply|calls)=%?([\w.\-]+)",
                          stripped)
        if mcall:
            calls.append((comp_name, mcall.group(1), None))

    # multiply collective bytes in while bodies by their trip count
    multipliers = {c: 1 for c in comp_colls}
    for caller, body, cond in calls:
        if cond is not None:
            trip = cond_consts.get(cond, 1)
            multipliers[body] = max(multipliers.get(body, 1), max(trip, 1))
    # propagate one level (fusions called from while bodies)
    for caller, callee, cond in calls:
        if cond is None and callee in multipliers:
            multipliers[callee] = max(multipliers.get(callee, 1),
                                      multipliers.get(caller, 1))
    out = {k: 0 for k in _COLLECTIVES}
    out["total"] = 0
    for comp, ops in comp_colls.items():
        mult = multipliers.get(comp, 1)
        for kind, b in ops:
            out[kind] += b * mult
            out["total"] += b * mult
    return out


def _bf16_legalization_bytes(hlo: str) -> int:
    """Bytes of the CPU backend's bf16->f32 legalization copies (absent on
    TPU, where bf16 is native). Signature: XLA CPU materializes a
    `wrapped_convert` kLoop fusion producing an f32 tensor whose dims match a
    bf16 tensor (typically a while-loop carry of a donated bf16 argument).
    Each distinct fusion definition is one real buffer."""
    bf16_dims = set(re.findall(r"bf16\[([0-9,]+)\]", hlo))
    total = 0
    seen = set()
    for m in re.finditer(
            r"%(wrapped_convert[\w.]*) = f32\[([0-9,]+)\][^=]*fusion\(", hlo):
        name, dims = m.group(1), m.group(2)
        if name in seen or dims not in bf16_dims:
            continue
        seen.add(name)
        n = 1
        for d in dims.split(","):
            n *= int(d)
        if n * 4 > 5e7:
            total += n * 4
    return total


# ---------------------------------------------------------------------------
def run_combo(arch: str, shape_name: str, *, multi_pod: bool,
              out_dir: Path) -> dict:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh_tag = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
           "kind": shape.kind, "ok": False}
    ok, reason = st.supports_shape(cfg, shape)
    if not ok:
        rec.update(skipped=True, reason=reason)
        return rec

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    fsdp = shd.needs_fsdp(cfg, mesh, shape.kind)
    rec["fsdp"] = fsdp
    params_shape = st.params_structs(cfg)
    pspecs = shd.param_specs(cfg, params_shape, mesh, fsdp=fsdp)
    p_shard = shd.to_shardings(mesh, pspecs)
    bspecs = shd.batch_specs(cfg, shape, mesh)
    b_shard = {k: jax.NamedSharding(mesh, v) for k, v in bspecs.items()}
    batch = st.batch_structs(cfg, shape)

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            opt_shape = st.opt_structs(params_shape)
            ospecs = shd.opt_specs(pspecs, opt_shape)
            o_shard = shd.to_shardings(mesh, ospecs)
            fn = st.build_train_step(cfg, mesh=mesh)
            jfn = jax.jit(fn,
                          in_shardings=(p_shard, o_shard, b_shard),
                          out_shardings=(p_shard, o_shard, None),
                          donate_argnums=(0, 1))
            lowered = jfn.lower(params_shape, opt_shape, batch)
        elif shape.kind == "prefill":
            cache_shape = st.cache_structs(cfg, shape)
            cspecs = shd.cache_specs(cfg, cache_shape, mesh,
                                     global_batch=shape.global_batch)
            c_shard = shd.to_shardings(mesh, cspecs)
            fn = st.build_prefill_step(cfg, shape, mesh=mesh)
            jfn = jax.jit(fn, in_shardings=(p_shard, b_shard, c_shard),
                          out_shardings=(None, c_shard),
                          donate_argnums=(2,))
            lowered = jfn.lower(params_shape, batch, cache_shape)
        else:  # decode
            cache_shape = st.cache_structs(cfg, shape)
            cspecs = shd.cache_specs(cfg, cache_shape, mesh,
                                     global_batch=shape.global_batch)
            c_shard = shd.to_shardings(mesh, cspecs)
            fn = st.build_serve_step(cfg, shape, mesh=mesh)
            jfn = jax.jit(fn, in_shardings=(p_shard, c_shard,
                                            b_shard["token"]),
                          out_shardings=(None, c_shard),
                          donate_argnums=(1,))
            lowered = jfn.lower(params_shape, cache_shape, batch["token"])
        rec["lower_s"] = round(time.time() - t0, 2)

        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

    # --- memory analysis (proves it fits) ---
    try:
        ma = compiled.memory_analysis()
        rec["memory"] = {
            k: int(getattr(ma, k))
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes")
            if hasattr(ma, k)}
        arg = rec["memory"].get("argument_size_in_bytes", 0)
        tmp = rec["memory"].get("temp_size_in_bytes", 0)
        alias = rec["memory"].get("alias_size_in_bytes", 0)
        out_b = rec["memory"].get("output_size_in_bytes", 0)
        rec["memory"]["per_device_total"] = arg + tmp + max(out_b - alias, 0)
    except Exception as e:  # pragma: no cover
        rec["memory_error"] = str(e)

    # --- cost analysis (FLOPs / bytes for the roofline) ---
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        rec["cost"] = {k: float(v) for k, v in ca.items()
                       if isinstance(v, (int, float)) and (
                           k in ("flops", "bytes accessed")
                           or k.startswith("bytes accessed"))}
    except Exception as e:  # pragma: no cover
        rec["cost_error"] = str(e)

    # --- collective bytes from partitioned HLO ---
    try:
        hlo = compiled.as_text()
        rec["collectives"] = collective_bytes_from_hlo(hlo)
        rec["hlo_bytes"] = len(hlo)
        # The CPU backend legalizes bf16 loop carries/compute into f32
        # copies a TPU (native bf16) never materializes. Estimate the
        # overhead: unique f32 buffers whose dims exactly match a bf16
        # entry-parameter tensor are CPU-only duplicates.
        dup = _bf16_legalization_bytes(hlo)
        rec["cpu_bf16_legalization_bytes"] = dup
        if "memory" in rec:
            rec["memory"]["tpu_estimate"] = max(
                rec["memory"]["per_device_total"] - dup, 0)
    except Exception as e:  # pragma: no cover
        rec["collective_error"] = str(e)

    rec["ok"] = True
    rec["n_devices"] = mesh.size
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="benchmarks/artifacts/dryrun")
    args = ap.parse_args()
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    try:
        rec = run_combo(args.arch, args.shape, multi_pod=args.multi_pod,
                        out_dir=out_dir)
    except Exception as e:
        rec = {"arch": args.arch, "shape": args.shape,
               "mesh": "pod2x16x16" if args.multi_pod else "pod16x16",
               "ok": False, "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
    tag = f"{args.arch}.{args.shape}.{rec['mesh']}"
    (out_dir / f"{tag}.json").write_text(json.dumps(rec, indent=1))
    if rec.get("ok"):
        mem = rec.get("memory", {}).get("per_device_total", 0)
        print(f"OK {tag} compile={rec.get('compile_s')}s "
              f"mem/dev={mem/1e9:.2f}GB flops={rec.get('cost', {}).get('flops', 0):.3e} "
              f"coll={rec.get('collectives', {}).get('total', 0):.3e}B")
        print(json.dumps(rec.get("memory", {}), indent=1))
        print(json.dumps(rec.get("collectives", {}), indent=1))
    elif rec.get("skipped"):
        print(f"SKIP {tag}: {rec['reason']}")
    else:
        print(f"FAIL {tag}: {rec.get('error')}")
        print(rec.get("traceback", ""))


if __name__ == "__main__":
    main()
