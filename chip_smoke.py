#!/usr/bin/env python3
"""Check that the system's main path runs on a TPU, through its own entry
points, and that what comes out is right.

    python chip_smoke.py              # one chip: the three phases below
    python chip_smoke.py --chips 4    # four chips: the sharded train step

One chip, three phases in this one process:

1. study   -- ``Study.run()`` on ``scenarios/paper_qwen3_validate.json``
              with the jax engines (``dse.batched_sim``, ``events.batch``)
              on the chip, held to the numpy engines: the same best record
              and frontier, step times within ``STEP_RTOL``.
2. kernels -- the four Pallas kernels compiled for the chip at the widths
              of shipped models, held to ``repro.kernels.ref`` in float32.
3. train   -- tinyllama_1_1b at full width, depth cut to fit one chip,
              a few steps through ``build_sharded_train``, ``DataPipeline``
              and ``FaultTolerantLoop`` with the Pallas kernels inside the
              compiled step; the first loss is held to the same forward
              with the XLA kernels.

``--chips 4`` runs only phase 3's step on a 2x2 ("data", "model") mesh of
four chips and on one chip, and holds their losses together.

The last line of stdout is ``{"ok": true, "device": {...}}`` and nothing
else; every other line comes before it.  A failed check raises and exits
non-zero without that line.  With no TPU, or with
``REPRO_KERNEL_BACKEND`` set, it exits non-zero before any phase.
Checkpoint directories go under ``--out`` (``artifacts/chip_smoke/``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
STUDY_SCENARIO = ROOT / "scenarios" / "paper_qwen3_validate.json"

# jax-vs-numpy step-time tolerance, the one tests/test_dse.py holds
STEP_RTOL = 1e-9
# kernel vs reference: max |out - ref| / max |ref|, float32 inputs.  A
# layout or indexing fault gives O(1); one bf16 pass of the MXU on a
# float32 product gives ~4e-3.
KERNEL_TOL = 1e-2
# first train loss, Pallas kernels vs XLA kernels, same params and batch
LOSS_RTOL = 1e-3
# one chip vs the 2x2 mesh: the same step with reductions split four ways
SHARDED_LOSS_RTOL = 1e-3

# Phase 3.  22 layers of fp32 params, grads and AdamW moments need 16.4
# GiB, more than one v5e's 16 GB.  Compiled for a v5e without the chip,
# 8 layers take 5.40 GiB of arguments plus 10.12 GiB of temporaries with
# no remat, and 5.40 + 3.94 GiB with full remat: 8 layers, full remat.
TRAIN_ARCH = "tinyllama_1_1b"
TRAIN_LAYERS = 8
TRAIN_REMAT = "full"
TRAIN_BATCH, TRAIN_SEQ = 2, 2048        # 4096 tokens per step
TRAIN_STEPS = 3


class SmokeError(RuntimeError):
    """A check failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def say(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def rel_err(out, ref) -> float:
    """max |out - ref| / max |ref| in float64."""
    import numpy as np
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.max(np.abs(out - ref)) / max(np.max(np.abs(ref)), 1e-30))


# ---------------------------------------------------------------------------
# Phase 1: the study on the chip
# ---------------------------------------------------------------------------
def _record_key(rec) -> tuple:
    return (json.dumps(rec.strategy, sort_keys=True),
            json.dumps(rec.mcm, sort_keys=True), rec.fabric,
            json.dumps(rec.topo, sort_keys=True))


def _max_rel(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if a.size == 0:
        return 0.0
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-300)))


def phase_study(path: Path = STUDY_SCENARIO, step_rtol: float = STEP_RTOL
                ) -> dict:
    """Run the scenario with the jax engines (cold, then warm) and with
    numpy; hold jax to numpy.  Host time (what the explorer takes) and
    simulated time (the modelled cluster's step) are named apart."""
    from repro.api import Scenario, Study
    from repro.events import batch as event_batch

    sc = Scenario.load(path)
    runs = {}
    for label, backend in (("jax_cold", "jax"), ("jax_warm", "jax"),
                           ("numpy", "numpy")):
        replay_traces0 = event_batch.jax_stats()["traces"]
        t0 = time.perf_counter()
        res = Study(sc.replace(backend=backend)).run()
        host_s = time.perf_counter() - t0
        m = res.provenance["metrics"]
        counters = m["counters"]
        n_val = res.provenance["validate"]["n_validated"]
        val_s = m["wall_s"]["validate"]
        runs[label] = res
        say("study", run=label, scenario=sc.name,
            host_s=f"{host_s:.6f}",
            points=m["points_evaluated"],
            points_per_host_s=f"{m['points_evaluated'] / host_s:.1f}",
            replayed=n_val,
            replayed_per_host_s=f"{n_val / val_s:.1f}",
            dse_compiles=m["jax"]["retraces"],
            replay_compiles=event_batch.jax_stats()["traces"]
            - replay_traces0,
            dse_jax_calls=counters.get("batched_sim.jax_calls", 0),
            replay_jax_calls=counters.get("batch_replay.jax_calls", 0))

    jx, npy = runs["jax_cold"], runs["numpy"]
    counters = jx.provenance["metrics"]["counters"]
    check(counters.get("batched_sim.jax_calls", 0) > 0,
          "the jax study made no batched_sim device call")
    check(counters.get("batch_replay.jax_calls", 0) > 0,
          "the jax study made no batch_replay device call")
    check(jx.best is not None and npy.best is not None, "no best record")
    check(_record_key(jx.best_record) == _record_key(npy.best_record),
          f"best records differ: {jx.best_record} vs {npy.best_record}")
    check([_record_key(r) for r in jx.records]
          == [_record_key(r) for r in npy.records],
          "record rankings differ between jax and numpy")
    check(jx.pareto == npy.pareto, "refined frontiers differ")
    step_err = _max_rel([r.metrics["step_time"] for r in jx.records],
                        [r.metrics["step_time"] for r in npy.records])
    val = [i for i, r in enumerate(npy.records)
           if "validated_step_time" in r.metrics]
    check(len(val) == sc.validate_top, f"{len(val)} records validated")
    val_err = _max_rel(
        [jx.records[i].metrics["validated_step_time"] for i in val],
        [npy.records[i].metrics["validated_step_time"] for i in val])
    best = npy.best_record
    say("study", check="jax_vs_numpy", records=len(jx.records),
        frontier=len(jx.pareto), best_strategy=json.dumps(best.strategy),
        best_fabric=best.fabric, best_mcm=json.dumps(best.mcm),
        best_sim_step_s=best.metrics["step_time"],
        step_time_max_rel_err=step_err,
        validated_step_time_max_rel_err=val_err, rtol=step_rtol)
    check(step_err <= step_rtol,
          f"step_time jax vs numpy: max rel err {step_err} > {step_rtol}")
    check(val_err <= step_rtol,
          f"validated_step_time jax vs numpy: max rel err {val_err} > "
          f"{step_rtol}")
    return {"step_err": step_err, "val_err": val_err,
            "records": len(jx.records), "frontier": list(jx.pareto)}


# ---------------------------------------------------------------------------
# Phase 2: the Pallas kernels on the chip
# ---------------------------------------------------------------------------
def model_kernel_shapes() -> dict:
    """Kernel shapes at the widths of shipped models."""
    from repro.configs import get_config
    tl = get_config("tinyllama_1_1b")
    mb = get_config("mamba2_780m")
    qw = get_config("qwen3_moe_235b_a22b")
    return {
        "flash_attention": {"model": tl.name, "b": 1,
                            "hq": tl.attn.n_heads, "hkv": tl.attn.n_kv_heads,
                            "s": 2048, "d": tl.attn.head_dim},
        "ssd": {"model": mb.name, "b": 1, "s": 2048,
                "h": mb.ssm.n_heads(mb.d_model), "p": mb.ssm.head_dim,
                "g": mb.ssm.n_groups, "n": mb.ssm.d_state,
                "chunk": mb.ssm.chunk},
        "rmsnorm": {"model": tl.name, "rows": 4096, "d": tl.d_model},
        "moe_gmm": {"model": qw.name, "t": 2048, "e": qw.moe.top_k,
                    "k": qw.d_model, "n": qw.moe.d_ff_expert,
                    "block_t": 128},
    }


def _compile(fn, args, interpret: bool):
    """Compile ``fn`` for ``args``; compiled for the chip (not interpret
    mode), it must hold a Pallas kernel."""
    import jax
    exe = jax.jit(fn).lower(*args).compile()
    check(interpret or "tpu_custom_call" in exe.as_text(),
          f"no Pallas kernel (tpu_custom_call) in {fn}")
    return exe


def phase_kernels(shapes: dict | None = None, interpret: bool = False,
                  tol: float = KERNEL_TOL, seed: int = 0) -> dict:
    """Each kernel compiled at ``shapes`` against ``kernels/ref.py``.
    ``interpret=True`` runs the kernels in the Pallas interpreter and the
    ops-level backward through the XLA kernels (for CPU tests)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref
    from repro.kernels.flash_attention import flash_attention_fwd
    from repro.kernels.moe_gmm import moe_gmm
    from repro.kernels.rmsnorm import rmsnorm
    from repro.kernels.ssd_scan import ssd_scan

    shapes = shapes or model_kernel_shapes()
    ops_backend = "xla" if interpret else "pallas"
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 32))

    def normal(shape, scale=1.0):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    def ref_call(fn, *args):
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*args)

    errs = {}

    sh = shapes["flash_attention"]
    q = normal((sh["b"], sh["hq"], sh["s"], sh["d"]))
    k = normal((sh["b"], sh["hkv"], sh["s"], sh["d"]))
    v = normal((sh["b"], sh["hkv"], sh["s"], sh["d"]))
    do = normal(q.shape)
    fwd = _compile(lambda q_, k_, v_: flash_attention_fwd(
        q_, k_, v_, causal=True, interpret=interpret)[0], (q, k, v),
        interpret)
    o_ref = ref_call(lambda q_, k_, v_: ref.attention_ref(q_, k_, v_),
                     q, k, v)
    errs["flash_attention_fwd"] = rel_err(fwd(q, k, v), o_ref)

    def grads(attn):
        return jax.grad(lambda q_, k_, v_: jnp.sum(attn(q_, k_, v_) * do),
                        argnums=(0, 1, 2))

    bwd = _compile(grads(lambda q_, k_, v_: ops.flash_attention(
        q_, k_, v_, causal=True, backend=ops_backend)), (q, k, v), interpret)
    g_ref = ref_call(grads(ref.attention_ref), q, k, v)
    errs["flash_attention_bwd"] = max(
        rel_err(a, b) for a, b in zip(bwd(q, k, v), g_ref))
    say("kernels", kernel="flash_attention", model=sh["model"],
        shape=f"q{q.shape}/kv{k.shape}", fwd_err=errs["flash_attention_fwd"],
        bwd_err=errs["flash_attention_bwd"], tol=tol)

    sh = shapes["ssd"]
    x = normal((sh["b"], sh["s"], sh["h"], sh["p"]))
    dt = jax.nn.softplus(normal((sh["b"], sh["s"], sh["h"])))
    a = -jnp.exp(normal((sh["h"],), 0.5))
    bm = normal((sh["b"], sh["s"], sh["g"], sh["n"]), 0.3)
    cm = normal((sh["b"], sh["s"], sh["g"], sh["n"]), 0.3)
    ssd = _compile(lambda *t: ssd_scan(*t, chunk=sh["chunk"],
                                       interpret=interpret),
                   (x, dt, a, bm, cm), interpret)
    y_ref = ref_call(lambda *t: ref.ssd_ref(*t)[0], x, dt, a, bm, cm)
    errs["ssd"] = rel_err(ssd(x, dt, a, bm, cm), y_ref)
    say("kernels", kernel="ssd", model=sh["model"], shape=f"x{x.shape}",
        groups=sh["g"], state=sh["n"], chunk=sh["chunk"], err=errs["ssd"],
        tol=tol)

    sh = shapes["rmsnorm"]
    x = normal((sh["rows"], sh["d"]))
    w = 1.0 + normal((sh["d"],), 0.1)
    rn = _compile(lambda x_, w_: rmsnorm(x_, w_, interpret=interpret),
                  (x, w), interpret)
    errs["rmsnorm"] = rel_err(rn(x, w), ref_call(ref.rmsnorm_ref, x, w))
    say("kernels", kernel="rmsnorm", model=sh["model"], shape=f"x{x.shape}",
        err=errs["rmsnorm"], tol=tol)

    sh = shapes["moe_gmm"]
    t, e, bt = sh["t"], sh["e"], sh["block_t"]
    check(t % (e * bt) == 0, "moe_gmm groups must be whole token blocks")
    x = normal((t, sh["k"]))
    w = normal((e, sh["k"], sh["n"]), sh["k"] ** -0.5)
    block_ids = jnp.repeat(jnp.arange(e, dtype=jnp.int32), t // e // bt)
    gmm = _compile(lambda x_, w_, g_: moe_gmm(x_, w_, g_, block_t=bt,
                                              interpret=interpret),
                   (x, w, block_ids), interpret)
    with jax.default_matmul_precision("highest"):
        y_ref = ref.moe_gmm_ref(x, w, [t // e] * e)
    errs["moe_gmm"] = rel_err(gmm(x, w, block_ids), y_ref)
    say("kernels", kernel="moe_gmm", model=sh["model"],
        shape=f"x{x.shape}/w{w.shape}", err=errs["moe_gmm"], tol=tol)

    for name, err in errs.items():
        check(math.isfinite(err) and err <= tol,
              f"{name}: error {err} vs kernels/ref.py exceeds {tol}")
    return errs


# ---------------------------------------------------------------------------
# Phase 3: the trainer on the chip
# ---------------------------------------------------------------------------
def train_config(arch: str, n_layers: int, reduced: bool = False):
    """The shipped config, at full width unless ``reduced`` (CPU tests),
    cut to ``n_layers``; returns (config, the shipped config)."""
    from repro.configs import get_config
    full = get_config(arch)
    cfg = full.reduced() if reduced else full
    return dataclasses.replace(cfg, n_layers=n_layers), full


def train_steps(cfg, ex, mesh, batch: int, seq: int, steps: int,
                ckpt_dir: Path, seed: int = 0, ref_loss: bool = False
                ) -> dict:
    """``steps`` steps of the step ``python -m repro.launch.train`` runs,
    on ``mesh``.  With ``ref_loss`` the first batch's loss is also taken
    from the same forward with the XLA kernels."""
    import jax

    from repro.checkpoint import CheckpointManager
    from repro.configs.base import ShapeConfig
    from repro.data import DataPipeline
    from repro.launch.steps import init_train_state
    from repro.launch.train import build_sharded_train
    from repro.models import build_model
    from repro.runtime import FaultTolerantLoop

    out = {}
    with jax.set_mesh(mesh):
        step_fn, state_sh = build_sharded_train(cfg, ex, mesh)
        state = jax.device_put(init_train_state(cfg, ex, seed=seed),
                               state_sh)
        pipeline = DataPipeline(cfg, ShapeConfig("chip_smoke", "train", seq,
                                                 batch), seed=seed, ex=ex)
        batch0 = pipeline.batch_at(0)
        if ref_loss:
            model = build_model(cfg)
            ex_xla = dataclasses.replace(ex, backend="xla")
            out["ref_loss"] = float(jax.jit(
                lambda p, b: model.loss(p, b, ex_xla)[0])(state.params,
                                                          batch0))
        t0 = time.perf_counter()
        compiled = step_fn.lower(state, batch0).compile()
        out["compile_s"] = time.perf_counter() - t0
        losses = []
        loop = FaultTolerantLoop(compiled, CheckpointManager(ckpt_dir),
                                 pipeline, checkpoint_every=steps + 1)
        state, last = loop.run(
            state, steps,
            on_metrics=lambda step, m, dt: losses.append(float(m["loss"])))
    check(last == steps, f"the loop stopped at step {last} of {steps}")
    out.update(losses=losses, step_s=list(loop.step_times),
               compiled=compiled, state=state)
    return out


def _memory_line(compiled) -> str:
    ma = compiled.memory_analysis()
    if ma is None:
        return "n/a"
    gib = float(1 << 30)
    return (f"args={ma.argument_size_in_bytes / gib:.2f}GiB "
            f"temp={ma.temp_size_in_bytes / gib:.2f}GiB")


def phase_train(arch: str = TRAIN_ARCH, n_layers: int = TRAIN_LAYERS,
                reduced: bool = False, remat: str = TRAIN_REMAT,
                batch: int = TRAIN_BATCH, seq: int = TRAIN_SEQ,
                steps: int = TRAIN_STEPS,
                out_dir: Path = ROOT / "artifacts" / "chip_smoke",
                expect_kernels: bool = True, loss_rtol: float = LOSS_RTOL,
                seed: int = 0) -> dict:
    """Train ``arch`` on one chip; the loss must be finite, the first one
    must match the XLA-kernel forward, and (on the chip) the compiled step
    must hold the Pallas kernels."""
    import jax

    from repro.launch.mesh import make_mesh_from_plan
    from repro.models.common import ExecConfig

    cfg, full = train_config(arch, n_layers, reduced)
    say("train", arch=arch, layers=f"{cfg.n_layers}/{full.n_layers}",
        cut="reduced widths" if reduced else "depth only",
        d_model=cfg.d_model,
        heads=f"{cfg.attn.n_heads}/{cfg.attn.n_kv_heads}",
        head_dim=cfg.attn.head_dim, d_ff=cfg.d_ff, vocab=cfg.vocab,
        remat=remat, tokens_per_step=batch * seq, steps=steps)
    ex = ExecConfig(remat=remat, attn_block=128)
    mesh = make_mesh_from_plan(tp=1, dp=1, devices=jax.devices()[:1])
    r = train_steps(cfg, ex, mesh, batch, seq, steps, Path(out_dir) / "ckpt",
                    seed=seed, ref_loss=True)
    has_kernels = "tpu_custom_call" in r["compiled"].as_text()
    loss_err = abs(r["losses"][0] - r["ref_loss"]) / abs(r["ref_loss"])
    stats = jax.devices()[0].memory_stats() or {}
    say("train", compile_s=f"{r['compile_s']:.3f}",
        memory=_memory_line(r["compiled"]).replace(" ", ","),
        peak_bytes_in_use=stats.get("peak_bytes_in_use", "n/a"),
        pallas_in_step=has_kernels,
        losses=",".join(f"{x:.6f}" for x in r["losses"]),
        ref_loss_xla_kernels=f"{r['ref_loss']:.6f}",
        loss_rel_err=loss_err, rtol=loss_rtol,
        host_step_s=",".join(f"{t:.4f}" for t in r["step_s"]))
    check(all(math.isfinite(x) for x in r["losses"]),
          f"non-finite loss: {r['losses']}")
    check(loss_err <= loss_rtol,
          f"first loss {r['losses'][0]} vs XLA-kernel forward "
          f"{r['ref_loss']}: rel err {loss_err} > {loss_rtol}")
    check(has_kernels or not expect_kernels,
          "no Pallas kernel (tpu_custom_call) in the compiled train step")
    return {"losses": r["losses"], "ref_loss": r["ref_loss"],
            "pallas_in_step": has_kernels}


def phase_train_sharded(arch: str = TRAIN_ARCH,
                        n_layers: int = TRAIN_LAYERS, reduced: bool = False,
                        remat: str = TRAIN_REMAT, batch: int = TRAIN_BATCH,
                        seq: int = TRAIN_SEQ, steps: int = TRAIN_STEPS,
                        out_dir: Path = ROOT / "artifacts" / "chip_smoke",
                        rtol: float = SHARDED_LOSS_RTOL, seed: int = 0
                        ) -> dict:
    """The train step on a 2x2 (data, model) mesh of four devices against
    the same step on one device."""
    import jax

    from repro.launch.hlo import parse_collectives
    from repro.launch.mesh import make_mesh_from_plan
    from repro.models.common import ExecConfig

    cfg, _ = train_config(arch, n_layers, reduced)
    devs = jax.devices()
    check(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    ex = ExecConfig(remat=remat, attn_block=128, batch_axes=("data",))
    say("train4", arch=arch, layers=cfg.n_layers, remat=remat,
        tokens_per_step=batch * seq, steps=steps, mesh="data=2,model=2")
    one = train_steps(cfg, ex, make_mesh_from_plan(1, 1, devices=devs[:1]),
                      batch, seq, steps, Path(out_dir) / "ckpt1", seed=seed)
    one.pop("state")
    four = train_steps(cfg, ex, make_mesh_from_plan(2, 2, devices=devs[:4]),
                       batch, seq, steps, Path(out_dir) / "ckpt4", seed=seed)

    per_dev = {}
    total = 0
    for leaf in jax.tree.leaves(four["state"].params):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            per_dev[shard.device.id] = (per_dev.get(shard.device.id, 0)
                                        + shard.data.nbytes)
    colls = parse_collectives(four["compiled"].as_text()).counts
    errs = [abs(a - b) / abs(b) for a, b in zip(four["losses"],
                                                 one["losses"])]
    say("train4", losses_1chip=",".join(f"{x:.6f}" for x in one["losses"]),
        losses_2x2=",".join(f"{x:.6f}" for x in four["losses"]),
        loss_max_rel_err=max(errs), rtol=rtol,
        param_bytes_total=total,
        param_bytes_per_device=json.dumps(per_dev, sort_keys=True)
        .replace(" ", ""),
        collectives=json.dumps(colls, sort_keys=True).replace(" ", ""),
        memory_2x2=_memory_line(four["compiled"]).replace(" ", ","),
        host_step_s_2x2=",".join(f"{t:.4f}" for t in four["step_s"]))
    check(all(math.isfinite(x) for x in one["losses"] + four["losses"]),
          "non-finite loss")
    check(max(errs) <= rtol,
          f"2x2 losses {four['losses']} vs one device {one['losses']}")
    check(len(per_dev) == 4 and max(per_dev.values()) < total / 2,
          f"parameters are not split over four devices: {per_dev}")
    check(sum(colls.values()) > 0, "no collective in the 2x2 step")
    return {"losses_1": one["losses"], "losses_4": four["losses"],
            "param_bytes": per_dev, "collectives": colls}


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded train step, 2x2 vs one chip")
    ap.add_argument("--out", type=Path,
                    default=ROOT / "artifacts" / "chip_smoke",
                    help="directory for checkpoints")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no src/repro beside {__file__}; run it from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    if os.environ.get("REPRO_KERNEL_BACKEND"):
        print("chip_smoke: REPRO_KERNEL_BACKEND is set; it would move the "
              "kernels off Pallas", file=sys.stderr)
        return 2
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU (jax platform {dev['platform']!r}); "
              f"nothing was run", file=sys.stderr)
        return 1
    from repro.runtime.compile_cache import use_compile_cache
    cache = use_compile_cache()
    check(dev["count"] >= args.chips,
          f"--chips {args.chips} but {dev['count']} device(s)")
    say("device", **dev, compile_cache=cache)

    t0 = time.perf_counter()
    if args.chips == 4:
        phase_train_sharded(out_dir=args.out)
    else:
        phase_study()
        phase_kernels()
        phase_train(out_dir=args.out)
    for mod in ("repro.launch.dryrun", "benchmarks.perf_iter"):
        check(mod not in sys.modules, f"{mod} was imported (sets XLA_FLAGS)")
    say("done", host_s=f"{time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
