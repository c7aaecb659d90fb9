"""Every step program the two engines can dispatch keeps a module name
that the benchmark's step metrics find (a rename would turn
`step.kernel_us_per_dispatch`, `step_roofline` and
`mesh.step_us_per_dispatch` to nothing without failing anything)."""

import fnmatch
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from gubernator_tpu.ops import bucket_kernel as bk
from gubernator_tpu.parallel.mesh import make_mesh
from gubernator_tpu.parallel.sharded_engine import ShardedDecisionEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
W = 64


def patterns(metric: str) -> list:
    path = os.path.join(ROOT, "benchmarks", "layer_metrics", metric + ".json")
    with open(path) as f:
        return json.load(f)["args"]["patterns"]


def pin(rows: int, *lead: int):
    return jax.ShapeDtypeStruct(lead + (rows, W), jnp.int32)


@pytest.fixture(scope="module")
def engines():
    mesh = make_mesh(jax.devices()[:4])
    return {
        "mesh": ShardedDecisionEngine(shard_capacity=1024, mesh=mesh),
        "single": ShardedDecisionEngine(
            shard_capacity=1024, mesh=mesh, single_program=True
        ),
    }


# name -> (where it lives, its program, rows of its input, leading axes)
ONE_CHIP = {
    "fused_step": (bk.PACKED_IN_ROWS, ()),
    "multi_fused_step": (bk.PACKED_IN_ROWS, (2,)),
    "uniform_step": (bk.UNIFORM_IN_ROWS, ()),
    "multi_uniform_step": (bk.UNIFORM_IN_ROWS, (2,)),
    "collapsed_step": (bk.COLLAPSED_IN_ROWS, ()),
}
SHARDED = {
    "mesh._packed_fused": (bk.PACKED_IN_ROWS, (4,)),
    "mesh._collapsed_fused": (bk.COLLAPSED_IN_ROWS, (4,)),
    "single._packed_fused": (bk.PACKED_IN_ROWS, (4,)),
    "single._collapsed_fused": (bk.COLLAPSED_IN_ROWS, (4,)),
    "single._flat_fused": (bk.PACKED_IN_ROWS, (1,)),
    "single._flat_collapsed_fused": (bk.COLLAPSED_IN_ROWS, (1,)),
}


def lowered(name: str, engines):
    if name in ONE_CHIP:
        rows, lead = ONE_CHIP[name]
        state = jax.eval_shape(lambda: bk.make_state(4096))
        return getattr(bk, name).lower(state, pin(rows, *lead))
    rows, lead = SHARDED[name]
    which, attr = name.split(".")
    engine = engines[which]
    return getattr(engine, attr).lower(engine._state, pin(rows, *lead))


@pytest.mark.parametrize("name", sorted(ONE_CHIP) + sorted(SHARDED))
def test_step_program_name_matches_a_benchmark_pattern(name, engines):
    text = lowered(name, engines).as_text()
    module = re.search(r"module @(\S+)", text).group(1)
    step = patterns("step.kernel_us_per_dispatch")
    assert any(fnmatch.fnmatchcase(module, p) for p in step), module
    assert patterns("step_roofline") == step
    if name.startswith("mesh.") or "_flat_" in name:
        # the mesh cell reads its own steps through its own metric
        mesh = patterns("mesh.step_us_per_dispatch")
        assert any(fnmatch.fnmatchcase(module, p) for p in mesh), module

