"""Distribution-layer tests: sharding rules, ChipLight->mesh plan, and a
small-mesh end-to-end compile (8 fake devices, fast)."""
import os
import sys

import pytest

# 8 host devices for this module ONLY (subprocess isolation via pytest-run
# is unavailable; skip if jax was already initialised with 1 device by a
# previous module in the same process — covered standalone in CI loop).
if "XLA_FLAGS" not in os.environ and "jax" not in sys.modules:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import AxisType, NamedSharding, \
    PartitionSpec as P  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import ShapeConfig  # noqa: E402
from repro.core import chiplight_optimize  # noqa: E402
from repro.core.workload import Workload  # noqa: E402
from repro.launch.steps import TrainState, init_train_state, \
    make_train_step  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models.common import ExecConfig  # noqa: E402
from repro.optim import AdamWState  # noqa: E402
from repro.parallel import plan_from_design  # noqa: E402
from repro.parallel.sharding import param_specs, _sanitize  # noqa: E402

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 fake devices (run standalone)")


def _mesh():
    return jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))


def test_param_specs_cover_tree_and_divide():
    cfg = get_config("mixtral_8x7b").reduced()
    ex = ExecConfig()
    model = build_model(cfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ex))
    mesh = _mesh()
    specs = param_specs(cfg, shapes, mesh)
    n = 0
    for leaf, spec in zip(jax.tree.leaves(shapes),
                          jax.tree.leaves(
                              specs, is_leaf=lambda x: isinstance(x, P))):
        n += 1
        for d, entry in enumerate(spec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            assert leaf.shape[d] % size == 0, (spec, leaf.shape)
    assert n > 5


def test_sanitize_nulls_nondivisible():
    mesh = _mesh()
    spec = _sanitize(P("model", "data"), (51865, 64), mesh)
    assert spec == P(None, "data")


def test_sharded_train_step_runs_tiny():
    """Real (not AOT) sharded train step on 8 fake devices."""
    cfg = get_config("tinyllama_1_1b").reduced()
    ex = ExecConfig(attn_block=16, batch_axes=("data",))
    mesh = _mesh()
    model = build_model(cfg)
    step = make_train_step(cfg, ex)
    state = init_train_state(cfg, ex)
    shapes = jax.eval_shape(lambda: state.params)
    p_specs = param_specs(cfg, shapes, mesh)
    p_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), p_specs,
                        is_leaf=lambda x: isinstance(x, P))
    state_sh = TrainState(params=p_sh,
                          opt=AdamWState(step=NamedSharding(mesh, P()),
                                         m=p_sh, v=p_sh))
    shape = ShapeConfig("t", "train", 32, 4)
    batch = model.make_batch(jax.random.PRNGKey(0), shape, ex, "train")
    with jax.set_mesh(mesh):
        state = jax.device_put(state, state_sh)
        jitted = jax.jit(step, in_shardings=(state_sh, None))
        new_state, metrics = jitted(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))


def test_chiplight_plan_to_mesh_roundtrip():
    """The paper's technique as a first-class feature: DSE output ->
    ParallelPlan -> a mesh whose axes carry the strategy."""
    cfg = get_config("tinyllama_1_1b")
    w = Workload(model=cfg, seq_len=4096, global_batch=256)
    res = chiplight_optimize(w, total_tflops=3e4, dies_per_mcm=4, m0=6,
                             outer_iters=2, inner_budget=12)
    assert res.best is not None
    plan = plan_from_design(res.best)
    shape, axes = plan.mesh_shape()
    assert shape[0] * shape[1] == res.best.strategy.n_devices \
        // res.best.strategy.pp
    assert axes == ("data", "model")
    # strategy degrees survive the round trip
    assert plan.strategy.tp == res.best.strategy.tp
