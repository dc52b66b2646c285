"""Mini dry-run integration test: lower+compile on a small forced-device mesh
in a SUBPROCESS (device count must be set before jax initializes; the main
test process keeps its single CPU device)."""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import SHAPES, get_config
from repro.launch import sharding as sh
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_auto_mesh
from repro.models.layers import set_logical_rules

mesh = make_auto_mesh((4, 2), ("data", "model"))
cfg = get_config("smollm-135m").reduced()
import dataclasses
shape = dataclasses.replace(SHAPES["train_4k"], seq_len=64, global_batch=8)
step, args, in_sp, out_sp, plan = steps_mod.build_step(cfg, shape, mesh)
set_logical_rules(plan.rules())

# Older JAX (0.4.x) accepts only Sharding objects in in_/out_shardings and has
# no jax.set_mesh; bind the specs to the mesh and use the mesh context manager.
def _to_sharding(sp):
    return NamedSharding(mesh, P() if sp is None else sp)
is_spec = lambda x: x is None or isinstance(x, P)
in_sh = jax.tree.map(_to_sharding, in_sp, is_leaf=is_spec)
out_sh = jax.tree.map(_to_sharding, out_sp, is_leaf=is_spec)
set_ctx = getattr(jax, "set_mesh", None)
with (set_ctx(mesh) if set_ctx else mesh):
    compiled = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh).lower(*args).compile()
cost = compiled.cost_analysis()
cost = cost[0] if isinstance(cost, (list, tuple)) else cost
mem = compiled.memory_analysis()
print(json.dumps({
    "flops": float(cost.get("flops", 0)),
    "temp": int(mem.temp_size_in_bytes),
    "ok": True,
}))
"""


@pytest.mark.slow
def test_mini_dryrun_compiles_on_forced_mesh():
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["ok"] and result["flops"] > 0


def test_mesh_constructors():
    # importing mesh module must not touch device state; host mesh builds
    from repro.launch.mesh import make_host_mesh
    m = make_host_mesh()
    assert set(m.axis_names) == {"data", "model"}


def test_collective_parser():
    from repro.launch.hlo import collective_stats
    hlo = """
  %ag = bf16[16,512]{1,0} all-gather(%x), replica_groups=[16,16]<=[256], dimensions={0}
  %ar = f32[128]{0} all-reduce(%y), replica_groups={{0,1,2,3}}, to_apply=%sum
  %cp = bf16[4,4]{1,0} collective-permute(%z), source_target_pairs={{0,1}}
"""
    stats = collective_stats(hlo, default_group=16)
    assert stats["all-gather"]["count"] == 1
    assert stats["all-gather"]["result_bytes"] == 16 * 512 * 2
    assert stats["all-reduce"]["result_bytes"] == 128 * 4
    # all-reduce wire = 2 * S * (N-1)/N with N=4
    assert stats["all-reduce"]["wire_bytes"] == int(2 * 512 * 3 / 4)
    assert stats["collective-permute"]["wire_bytes"] == 32
    assert stats["total_count"] == 3


def test_input_specs_cover_all_cells():
    from repro.configs import ASSIGNED_ARCHS, SHAPES, get_config
    from repro.launch.steps import input_specs
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        for shape in SHAPES.values():
            specs = input_specs(cfg, shape)
            assert specs, (arch, shape.name)
            for v in specs.values():
                assert all(d > 0 for d in v.shape)
