"""Sharding-spec validity for every arch at the production TP degree, the
loop-aware cost model units, and a subprocess multi-device dry-run smoke
(keeps XLA_FLAGS out of this process per the assignment)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ASSIGNED

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.mark.parametrize("arch", ASSIGNED)
def test_param_specs_divisible_at_tp16(arch):
    """Every sharded dim must divide by its mesh-axis size (16/16)."""
    from repro.launch.shardings import param_pspecs
    from repro.launch.steps import get_model
    model = get_model(arch)
    cfg = model.cfg
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                               jnp.bfloat16, tp=16))
    specs = param_pspecs(shapes, cfg, tp=16, fsdp_size=16, fsdp="data")
    flat_shapes = jax.tree.leaves(shapes)
    flat_specs = jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    assert len(flat_shapes) == len(flat_specs)
    n_sharded = 0
    for leaf, spec in zip(flat_shapes, flat_specs, strict=True):
        for i, ax in enumerate(spec):
            if ax is None:
                continue
            n_sharded += 1
            size = 16
            assert leaf.shape[i] % size == 0, (arch, leaf.shape, spec)
    assert n_sharded > 0, arch          # something actually shards


def test_jaxpr_costs_exact_on_matmul_and_scan():
    from repro.launch.costs import traced_costs

    def f(a, b):
        return a @ b

    a = jnp.zeros((8, 32), jnp.float32)
    b = jnp.zeros((32, 16), jnp.float32)
    c = traced_costs(f, a, b)
    assert c["flops"] == 2 * 8 * 32 * 16
    # scan multiplies by trip count (XLA cost_analysis famously does not)
    def g(a, b):
        def body(x, _):
            return x @ b, ()
        out, _ = jax.lax.scan(body, a, None, length=10)
        return out
    sq = jnp.zeros((32, 32), jnp.float32)
    c2 = traced_costs(g, jnp.zeros((8, 32)), sq)
    assert c2["flops"] == 10 * 2 * 8 * 32 * 32


def test_collective_parser_trip_multiplier():
    from repro.launch.costs import collective_bytes_loop_aware
    hlo = """
body.1 (p: (s32[], f32[64])) -> (s32[], f32[64]) {
  %ar = f32[64]{0} all-reduce(f32[64]{0} %x), replica_groups={}
}
cond.1 (p: (s32[], f32[64])) -> pred[] {
  %c = s32[] constant(28)
  ROOT %lt = pred[] compare(s32[] %i, s32[] %c), direction=LT
}
ENTRY main (a: f32[64]) -> f32[64] {
  %ag = f32[128]{0} all-gather(f32[64]{0} %a), dimensions={0}
  %w = (s32[], f32[64]) while((s32[], f32[64]) %t), condition=%cond.1, body=%body.1
}
"""
    out = collective_bytes_loop_aware(hlo)
    assert out["all-reduce"] == 28 * 64 * 4        # trip-multiplied
    assert out["all-gather"] == 64 * 4             # operand bytes, once


@pytest.mark.parametrize("case", [
    ("qwen3-0.6b", "decode_32k"),
    ("olmoe-1b-7b", "mixed_32k"),
    ("rwkv6-7b", "train_4k"),
])
def test_multi_device_cell_compiles_subprocess(case):
    """Real 8-device sharded lower+compile in a subprocess (XLA_FLAGS set
    only there). Shapes shrunk; mesh (2 data x 4 model)."""
    arch, shape = case
    code = f"""
import jax
import repro.launch.steps as steps
from repro.launch.shardings import named
steps.SHAPES['train_4k'] = dict(kind='train', seq=512, batch=8)
steps.SHAPES['decode_32k'] = dict(kind='decode', seq=1024, batch=8)
steps.SHAPES['mixed_32k'] = dict(kind='mixed', seq=1024, batch=8, chunk=64, streams=2)
mesh = jax.make_mesh((2, 4), ('data', 'model'),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cell, why = steps.build_cell({arch!r}, {shape!r}, mesh)
assert cell is not None, why
jitted = jax.jit(cell['fn'], in_shardings=named(mesh, cell['in_shardings']),
                 donate_argnums=cell['donate'])
compiled = jitted.lower(*cell['args']).compile()
print('COMPILED_OK', compiled.memory_analysis().temp_size_in_bytes >= 0)
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=560)
    assert "COMPILED_OK" in r.stdout, r.stderr[-2000:]


def test_mesh_factory():
    """make_production_mesh shapes/axes (single pod only on 1 device it
    cannot build — validated in the dry-run; here check the multi-pod
    factory arithmetic via a subprocess)."""
    code = """
from repro.launch.mesh import make_production_mesh
m1 = make_production_mesh()
assert m1.shape == {'data': 16, 'model': 16}, m1.shape
m2 = make_production_mesh(multi_pod=True)
assert m2.shape == {'pod': 2, 'data': 16, 'model': 16}, m2.shape
assert m2.size == 512
print('MESH_OK')
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert "MESH_OK" in r.stdout, r.stderr[-2000:]
