"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. constructs abstract inputs (ShapeDtypeStruct, no allocation),
  3. ``jax.jit(step, in_shardings=..., out_shardings=...).lower().compile()``,
  4. records ``memory_analysis()`` (proves it fits), ``cost_analysis()``
     (FLOPs/bytes for §Roofline), and the collective schedule parsed from
     the compiled HLO (bytes per collective kind -- cost_analysis does not
     report these),
  5. writes one JSON artifact per cell under artifacts/dryrun/.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun [--arch A] [--shape S]
      [--mesh single|multi|both] [--force] [--pod-sync hoplite_chain]

A CPU-only tool (run it with ``JAX_PLATFORMS=cpu``): it forces 512 host
devices before jax is imported; the production meshes need 256 or 512.

A failure in any cell (sharding mismatch, OOM at compile, unsupported
collective) is a bug in the system -- the driver prints FAIL and a
nonzero exit code at the end.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import re
import sys
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, get_config, shapes_for
from repro.configs.base import SHAPES_BY_NAME
from repro.launch import hlo_cost
from repro.launch import specs as S
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.sharding import partitioning
from repro.train import step as TS

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "artifacts", "dryrun")

COLLECTIVE_RE = re.compile(
    r"=\s*([a-z0-9]+)\[([0-9,]*)\][^ ]*\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
)
GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")

DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "c64": 8, "c128": 16,
}


def parse_collectives(hlo_text: str) -> Dict[str, Any]:
    """Sum output bytes of every collective op, by kind, with group sizes."""
    per_kind: Dict[str, float] = {}
    per_kind_count: Dict[str, int] = {}
    total_link_bytes = 0.0
    for line in hlo_text.splitlines():
        m = COLLECTIVE_RE.search(line)
        if not m:
            continue
        dtype, dims, kind = m.groups()
        bytes_per = DTYPE_BYTES.get(dtype)
        if bytes_per is None:
            continue
        elems = 1
        if dims:
            for d in dims.split(","):
                elems *= int(d)
        size = elems * bytes_per
        g = GROUPS_RE.search(line)
        if g:
            n = len(g.group(1).split(","))
        else:
            gi = GROUPS_IOTA_RE.search(line)
            n = int(gi.group(2)) if gi else 2
        # bytes that actually cross links per device (ring algorithms)
        if kind == "all-reduce":
            link = 2 * size * (n - 1) / max(1, n)
        elif kind == "all-gather":
            link = size * (n - 1) / max(1, n)  # size = gathered output
        elif kind == "reduce-scatter":
            link = size * (n - 1)  # size = scattered output shard
        elif kind == "all-to-all":
            link = size * (n - 1) / max(1, n)
        else:  # collective-permute
            link = size
        per_kind[kind] = per_kind.get(kind, 0.0) + link
        per_kind_count[kind] = per_kind_count.get(kind, 0) + 1
        total_link_bytes += link
    return {
        "per_kind_bytes": per_kind,
        "per_kind_count": per_kind_count,
        "total_link_bytes": total_link_bytes,
    }


def micro_batches_for(cfg, shape) -> int:
    """Keep per-device microbatch ~1 row for big models (memory bound)."""
    if shape.kind != "train":
        return 1
    big = cfg.param_count() > 10e9
    return 16 if big else 4


def build_cell(cfg, shape, mesh, pod_sync: str, variant: str = ""):
    """Returns (function, example_args (abstract), in_shardings, out_shardings, donate)."""
    micro = micro_batches_for(cfg, shape)
    if "micro4" in variant:
        micro = 4
    if "micro8" in variant:
        micro = 8
    if "micro32" in variant:
        micro = 32
    opts = TS.TrainOptions(
        num_microbatches=micro,
        remat="dots" if "rematdots" in variant else "full",
        pod_sync=pod_sync if "pod" in mesh.axis_names else "gspmd",
        pod_compression="podcompress" in variant,
    )
    shopts = opts.sharding
    if shape.kind == "train":
        fn = TS.make_train_step(cfg, mesh, shape, opts)
        state, batch = S.train_inputs(cfg, shape)
        st_sh = TS.state_shardings(cfg, mesh, opts)
        bspecs = partitioning.batch_specs(cfg, mesh, shape, shopts)
        b_sh = {k: NamedSharding(mesh, v) for k, v in bspecs.items()}
        return fn, (state, batch), (st_sh, b_sh), (st_sh, None), None
    b_axes = partitioning._batch_axes(mesh, shape.global_batch, shopts)
    T.set_activation_sharding(b_axes, shopts.tp_axis)
    if shape.kind == "prefill":
        params, batch = S.prefill_inputs(cfg, shape)

        def fn(params, batch):
            return T.prefill(cfg, params, batch, cache_seq=shape.seq_len)

        skel = T.model_skel(cfg)
        p_sh = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s),
            partitioning.param_specs(cfg, skel, mesh, shopts),
        )
        bspecs = partitioning.batch_specs(cfg, mesh, shape, shopts)
        bspecs.pop("labels", None)
        b_sh = {k: NamedSharding(mesh, v) for k, v in bspecs.items() if k in batch}
        c_sh = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s),
            partitioning.cache_specs(cfg, mesh, shape.global_batch, shopts),
        )
        return fn, (params, batch), (p_sh, b_sh), (None, c_sh), None
    # decode
    params, token, t, caches = S.decode_inputs(cfg, shape)

    def fn(params, token, t, caches):
        return T.decode_step(cfg, params, token, t, caches)

    skel = T.model_skel(cfg)
    p_sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        partitioning.param_specs(cfg, skel, mesh, shopts),
    )
    tok_sh = NamedSharding(
        mesh, partitioning.token_batch_spec(mesh, shape.global_batch, shopts)
    )
    t_sh = NamedSharding(mesh, P())
    c_sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        partitioning.cache_specs(cfg, mesh, shape.global_batch, shopts),
    )
    return fn, (params, token, t, caches), (p_sh, tok_sh, t_sh, c_sh), (None, c_sh), 3


def apply_variant(variant: str) -> Dict[str, Any]:
    """Perf-iteration knobs (EXPERIMENTS §Perf): comma-separated flags:
    bf16partials | moedrop | rematdots | micro4 | micro8 | micro32 | podcompress."""
    import jax.numpy as jnp

    from repro.models import common as C
    from repro.models import moe as M

    applied = {}
    flags = [f for f in variant.split(",") if f] if variant else []
    for f in flags:
        if f == "bf16partials":
            C.set_matmul_partial_dtype(jnp.bfloat16)
        elif f == "moedrop":
            M.set_moe_mode("dropping")
        elif f in ("rematdots", "micro4", "micro8", "micro32", "podcompress"):
            pass  # handled in build_cell via applied
        else:
            raise ValueError(f"unknown variant flag {f!r}")
        applied[f] = True
    return applied


def run_cell(arch: str, shape_name: str, mesh_kind: str, pod_sync: str, force: bool, variant: str = "") -> Dict[str, Any]:
    sub = mesh_kind if not variant else f"{mesh_kind}-{variant.replace(',', '+')}"
    if pod_sync != "hoplite_chain":
        sub = f"{sub}-{pod_sync}"
    out_dir = os.path.join(os.path.abspath(ARTIFACT_DIR), sub)
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, f"{arch}__{shape_name}.json")
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            cached = json.load(f)
        if cached.get("ok"):
            print(f"[cached] {mesh_kind}/{arch}/{shape_name}")
            return cached

    applied = apply_variant(variant)
    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    record: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "mesh_shape": dict(zip(mesh.axis_names, [int(mesh.shape[a]) for a in mesh.axis_names])),
        "kind": shape.kind, "pod_sync": pod_sync, "variant": variant, "ok": False,
    }
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            fn, args, in_sh, out_sh, donate = build_cell(cfg, shape, mesh, pod_sync, variant)
            jit_kwargs = dict(in_shardings=in_sh, out_shardings=out_sh)
            if donate is not None:
                jit_kwargs["donate_argnums"] = (donate,)
            lowered = jax.jit(fn, **jit_kwargs).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            if isinstance(cost, (list, tuple)):  # jax 0.4.x: one dict per program
                cost = cost[0] if cost else {}
            hlo = compiled.as_text()
            coll = parse_collectives(hlo)
            walk = hlo_cost.analyze(hlo)
        record.update(
            ok=True,
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            memory={
                k: int(getattr(mem, k))
                for k in (
                    "argument_size_in_bytes",
                    "output_size_in_bytes",
                    "temp_size_in_bytes",
                    "generated_code_size_in_bytes",
                )
                if hasattr(mem, k)
            },
            cost={
                k: float(v)
                for k, v in (cost or {}).items()
                if isinstance(v, (int, float)) and k in ("flops", "bytes accessed", "transcendentals", "optimal_seconds")
            },
            collectives=coll,
            walker=walk,
            hlo_lines=len(hlo.splitlines()),
            num_devices=int(np.prod([mesh.shape[a] for a in mesh.axis_names])),
        )
        print(
            f"[ok] {mesh_kind}/{arch}/{shape_name}: compile={t_compile:.1f}s "
            f"temp={record['memory'].get('temp_size_in_bytes', 0)/2**30:.2f}GiB "
            f"flops={walk['flops']:.3g} "
            f"coll={walk['collective_link_bytes']/2**30:.2f}GiB"
        )
    except BaseException as e:  # noqa: BLE001
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
        print(f"[FAIL] {mesh_kind}/{arch}/{shape_name}: {type(e).__name__}: {str(e)[:200]}")
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--pod-sync", default="hoplite_chain")
    ap.add_argument("--variant", default="", help="comma-separated perf flags")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    archs = [args.arch] if args.arch else sorted(ARCHS)
    failures = []
    for arch in archs:
        cfg = get_config(arch)
        cell_shapes = [s.name for s in shapes_for(cfg)]
        if args.shape:
            cell_shapes = [s for s in cell_shapes if s == args.shape]
        for shape_name in cell_shapes:
            for mesh_kind in meshes:
                rec = run_cell(arch, shape_name, mesh_kind, args.pod_sync, args.force, args.variant)
                if not rec.get("ok"):
                    failures.append((mesh_kind, arch, shape_name))
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f_ in failures:
            print("  ", *f_)
        sys.exit(1)
    print("\nall dry-run cells passed")


if __name__ == "__main__":
    main()
