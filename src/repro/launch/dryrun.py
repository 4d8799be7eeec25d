import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# --- multi-pod AOT dry-run ------------------------------------------------
# Lowers + compiles every (architecture x input-shape x mesh) cell against
# the production mesh with ShapeDtypeStruct inputs (no allocation), prints
# memory_analysis / cost_analysis, parses collective bytes from the
# compiled HLO, and writes a JSON artifact per cell for the roofline
# benchmark.  Resumable: existing artifacts are skipped unless --force.
# ---------------------------------------------------------------------------
import argparse     # noqa: E402
import json         # noqa: E402
import time         # noqa: E402
import traceback    # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCH_IDS, SHAPES, get_config  # noqa: E402
from repro.launch import hlo as hlo_mod  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.steps import (init_train_state, make_prefill_step,  # noqa
                                make_serve_step, make_train_step,
                                TrainState)
from repro.models import build_model  # noqa: E402
from repro.models.common import ExecConfig  # noqa: E402
from repro.optim import AdamWState  # noqa: E402
from repro.parallel.sharding import batch_specs, cache_specs, \
    param_specs  # noqa: E402

ART = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

# ExecConfig field overrides applied by the §Perf hillclimb harness
# (benchmarks/perf_iter.py) — empty for the baseline dry-run.
EXEC_OVERRIDES: dict = {}

# Cells skipped per DESIGN.md §shape-cell-skips (pure full attention at
# 500k decode; enc-dec audio backbone bounded at 1500 frames).
LONG_OK = {"mamba2_780m", "zamba2_7b", "mixtral_8x7b", "gemma2_2b",
           "gemma3_27b"}


def cell_enabled(arch: str, shape_name: str) -> bool:
    if shape_name == "long_500k":
        return arch in LONG_OK
    return True


def _exec_config(cfg, multi_pod: bool, shape, counting: bool = False):
    """counting=True: the depth-variant compiles that feed the roofline —
    fully unrolled block loops so cost_analysis sees every FLOP.  The
    main (full-depth) compile only supplies memory_analysis and uses the
    compact scan formulation (same memory behaviour, much faster SPMD
    partitioning)."""
    batch_axes = ("pod", "data") if multi_pod else ("data",)
    fsdp_size = 32 if multi_pod else 16
    if shape.kind == "decode" and shape.global_batch % fsdp_size != 0:
        batch_axes = None   # long_500k B=1: shard the KV cache, not batch
    # Megatron-style sequence parallelism between blocks for full-sequence
    # passes (16x smaller layer carries / remat residuals).
    seq_axis = "model" if shape.kind in ("train", "prefill") else None
    moe_axis = None
    if cfg.moe is not None and cfg.moe.n_experts % 16 == 0:
        moe_axis = "model"   # matches parallel.sharding._ep_on_model
    block = 2048 if shape.seq_len >= 32768 else 1024
    # larger SSD chunks at long seq (better MXU utilisation per chunk,
    # and 4x fewer chunk bodies in the counting compiles)
    chunk = 1024 if shape.seq_len >= 32768 else 256
    ex = ExecConfig(param_dtype=jnp.bfloat16, compute_dtype=jnp.bfloat16,
                    remat="full", attn_block=block, ssd_chunk=chunk,
                    batch_axes=batch_axes, seq_axis=seq_axis,
                    backend="xla_blocked" if counting else "xla",
                    static_layer_pattern=True,
                    layer_unroll=counting,
                    moe_expert_axis=moe_axis)
    if EXEC_OVERRIDES:
        import dataclasses
        ex = dataclasses.replace(ex, **EXEC_OVERRIDES)
    return ex


def _depth_variants(cfg):
    """Two reduced-depth configs for the trip-count extrapolation.

    cost_analysis counts a lax.scan body ONCE regardless of trip count, so
    per-cell roofline terms are extrapolated from two depth points:
      term(L) = t1 + (L - L1) * (t2 - t1) / (L2 - L1).
    Period-structured archs step in whole periods; enc-dec scales both
    stacks together.
    """
    import dataclasses
    if cfg.family == "hybrid":
        p = cfg.hybrid_period
        return (dataclasses.replace(cfg, n_layers=p),
                dataclasses.replace(cfg, n_layers=2 * p),
                p, 2 * p, cfg.n_layers)
    if cfg.attn is not None and cfg.attn.local_global_period > 1:
        p = cfg.attn.local_global_period
        return (dataclasses.replace(cfg, n_layers=p),
                dataclasses.replace(cfg, n_layers=2 * p),
                p, 2 * p, cfg.n_layers)
    if cfg.family == "encdec":
        return (dataclasses.replace(cfg, n_layers=1, encoder_layers=1),
                dataclasses.replace(cfg, n_layers=2, encoder_layers=2),
                1, 2, cfg.n_layers)
    import dataclasses as dc
    return (dc.replace(cfg, n_layers=1), dc.replace(cfg, n_layers=2),
            1, 2, cfg.n_layers)


def _ns(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               cfg_override=None, layer_unroll=False):
    cfg = cfg_override if cfg_override is not None else get_config(arch)
    shape = SHAPES[shape_name]
    ex = _exec_config(cfg, multi_pod, shape, counting=layer_unroll)
    model = build_model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    if ex.moe_impl == "a2a":
        import dataclasses
        ex = dataclasses.replace(ex, mesh=mesh)

    params_shape = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ex))
    p_specs = param_specs(cfg, params_shape, mesh)
    p_sh = _ns(mesh, p_specs)

    if shape.kind == "train":
        step = make_train_step(cfg, ex)
        state_shape = jax.eval_shape(
            lambda: TrainState(
                params=params_shape,
                opt=AdamWState(
                    step=jax.ShapeDtypeStruct((), jnp.int32),
                    m=jax.tree.map(
                        lambda s: jax.ShapeDtypeStruct(s.shape,
                                                       jnp.float32),
                        params_shape),
                    v=jax.tree.map(
                        lambda s: jax.ShapeDtypeStruct(s.shape,
                                                       jnp.float32),
                        params_shape))))
        state_sh = TrainState(
            params=p_sh,
            opt=AdamWState(step=NamedSharding(mesh, P()),
                           m=jax.tree.map(lambda s: s, p_sh),
                           v=jax.tree.map(lambda s: s, p_sh)))
        batch_shape = model.input_specs(shape, ex, kind="train")
        bs = batch_specs(cfg, shape, mesh, kind="train")
        batch_sh = {k: NamedSharding(mesh, bs(k)) for k in batch_shape}
        with jax.set_mesh(mesh):
            lowered = jax.jit(step, in_shardings=(state_sh, batch_sh)
                              ).lower(state_shape, batch_shape)
    elif shape.kind == "prefill":
        step = make_prefill_step(cfg, ex)
        batch_shape = model.input_specs(shape, ex, kind="prefill")
        bs = batch_specs(cfg, shape, mesh, kind="prefill")
        batch_sh = {k: NamedSharding(mesh, bs(k)) for k in batch_shape}
        with jax.set_mesh(mesh):
            lowered = jax.jit(step, in_shardings=(p_sh, batch_sh)
                              ).lower(params_shape, batch_shape)
    else:  # decode
        step = make_serve_step(cfg, ex)
        specs = model.input_specs(shape, ex)
        c_rule = cache_specs(cfg, shape, mesh)
        cache_sh = jax.tree_util.tree_map_with_path(
            lambda p, l: NamedSharding(mesh, c_rule(p, l)), specs["cache"])
        # batch shards over fsdp axes only when divisible
        fsdp = tuple(a for a in mesh.axis_names if a != "model")
        fsdp_size = 1
        for a in fsdp:
            fsdp_size *= mesh.shape[a]
        tok_sh = NamedSharding(
            mesh, P(fsdp) if shape.global_batch % fsdp_size == 0
            else P())
        pos_sh = NamedSharding(mesh, P())
        with jax.set_mesh(mesh):
            lowered = jax.jit(step, in_shardings=(
                p_sh, cache_sh, tok_sh, pos_sh)).lower(
                    params_shape, specs["cache"], specs["tokens"],
                    specs["pos"])
    return lowered, mesh, cfg, shape


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             force: bool = False, verbose: bool = True):
    ART.mkdir(parents=True, exist_ok=True)
    out_path = ART / f"{arch}__{shape_name}__{mesh_kind}.json"
    if out_path.exists() and not force:
        if verbose:
            print(f"[skip] {out_path.name} exists")
        return json.loads(out_path.read_text())
    if not cell_enabled(arch, shape_name):
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
               "skipped": True,
               "reason": "long_500k inapplicable (see DESIGN.md)"}
        out_path.write_text(json.dumps(rec, indent=1))
        return rec

    multi = mesh_kind == "multi"
    t0 = time.time()
    lowered, mesh, cfg, shape = lower_cell(arch, shape_name, multi)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    text = compiled.as_text()
    coll = hlo_mod.parse_collectives(text)
    n_chips = 512 if multi else 256

    # --- depth extrapolation (scan bodies are cost-counted once) ---
    # The roofline table is single-pod (assignment §Roofline); multi-pod
    # cells prove the pod-axis sharding compiles and reuse the single-pod
    # per-device terms scaled by the chip-count ratio.
    single_art = ART / f"{arch}__{shape_name}__single.json"
    if os.environ.get("DRYRUN_SKIP_COUNTING"):
        # fallback fidelity: raw scan-counted terms scaled by the layer
        # (period) count — used when the unrolled counting compiles are
        # impractical on this host; flagged in the artifact.
        p = cfg.hybrid_period if cfg.family == "hybrid" else 1
        reps = max(cfg.n_layers // max(p, 1), 1)
        flops_x = float(cost.get("flops", 0.0)) * reps
        bytes_x = float(cost.get("bytes accessed", 0.0)) * reps
        wire_x = coll.total_wire * reps
        pts, l1, l2 = [], 0, 0
    elif multi and single_art.exists():
        prev = json.loads(single_art.read_text())
        if not prev.get("skipped"):
            scale = prev["n_chips"] / 512.0
            flops_x = prev["hlo_flops_per_device"] * scale
            bytes_x = prev["hlo_bytes_per_device"] * scale
            wire_x = prev["coll_wire_bytes_per_device"] * scale
            pts, l1, l2 = prev["depth_points"]["pts"], 0, 0
        else:
            flops_x = bytes_x = wire_x = 0.0
            pts, l1, l2 = [], 0, 0
    else:
        cfg1, cfg2, l1, l2, l_full = _depth_variants(cfg)
        pts = []
        for cvar in (cfg1, cfg2):
            lw, _, _, _ = lower_cell(arch, shape_name, multi,
                                     cfg_override=cvar, layer_unroll=True)
            cc = lw.compile()
            cst = cc.cost_analysis() or {}
            cl = hlo_mod.parse_collectives(cc.as_text())
            pts.append((float(cst.get("flops", 0.0)),
                        float(cst.get("bytes accessed", 0.0)),
                        cl.total_wire))

        def extrap(i):
            t1, t2 = pts[0][i], pts[1][i]
            return t1 + (l_full - l1) * (t2 - t1) / max(l2 - l1, 1)

        flops_x, bytes_x, wire_x = extrap(0), extrap(1), extrap(2)

    def _mem(attr):
        return float(getattr(mem, attr, 0) or 0)

    tokens = (shape.global_batch * shape.seq_len
              if shape.kind != "decode" else shape.global_batch)
    mult = 6.0 if shape.kind == "train" else 2.0
    model_flops = mult * cfg.active_param_count() * tokens

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "kind": shape.kind, "n_chips": n_chips,
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "hlo_flops_per_device": flops_x,
        "hlo_bytes_per_device": bytes_x,
        "coll_wire_bytes_per_device": wire_x,
        "raw_flops_per_device": float(cost.get("flops", 0.0)),
        "raw_bytes_per_device": float(cost.get("bytes accessed", 0.0)),
        "raw_wire_bytes_per_device": coll.total_wire,
        "depth_points": {"l1": l1, "l2": l2, "pts": pts},
        "coll_result_bytes_per_device": coll.total_result,
        "coll_breakdown": coll.wire_bytes,
        "coll_counts": coll.counts,
        "mem_argument_bytes": _mem("argument_size_in_bytes"),
        "mem_output_bytes": _mem("output_size_in_bytes"),
        "mem_temp_bytes": _mem("temp_size_in_bytes"),
        "mem_generated_code_bytes": _mem("generated_code_size_in_bytes"),
        "model_flops_step": model_flops,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "lower_s": t_lower, "compile_s": t_compile,
    }
    out_path.write_text(json.dumps(rec, indent=1))
    if verbose:
        print(f"[ok] {arch} {shape_name} {mesh_kind}: "
              f"flops/dev={rec['hlo_flops_per_device']:.3e} "
              f"bytes/dev={rec['hlo_bytes_per_device']:.3e} "
              f"wire/dev={rec['coll_wire_bytes_per_device']:.3e} "
              f"argbytes/dev={rec['mem_argument_bytes'] / 1e9:.2f}GB "
              f"temp/dev={rec['mem_temp_bytes'] / 1e9:.2f}GB "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
        print("  memory_analysis:", mem)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=[None] + list(SHAPES))
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = ARCH_IDS if (args.all or args.arch is None) \
        else [args.arch.replace("-", "_").replace(".", "_")]
    shapes = list(SHAPES) if (args.all or args.shape is None) \
        else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mk in meshes:
                try:
                    run_cell(arch, shape, mk, force=args.force)
                except Exception as e:  # noqa: BLE001
                    failures.append((arch, shape, mk, repr(e)))
                    print(f"[FAIL] {arch} {shape} {mk}: {e}")
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nALL CELLS OK")


if __name__ == "__main__":
    main()
