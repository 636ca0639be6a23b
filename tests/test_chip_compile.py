"""The engine's device programs compile for one TPU v5e chip at opt-125m's
published widths in bf16, and each fits that chip's memory at the page
pool chip_smoke.py serves with.

Nothing runs: the TPU compiler targets a chip that is only described.
Only one process at a time may load the TPU library, so the topology is
described inside a fixture, never while a module is imported, and every
compile of this kind stays in this file.
"""
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke  # noqa: E402
from repro.common.hw import TPU_V5E  # noqa: E402
from repro.core.engine import step_program_args  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.models.registry import get_model  # noqa: E402

PROGRAMS = ("prefill", "commit", "decode", "mixed", "mixed_prefill",
            "mixed_chunked")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def on_chip(one_chip):
    """Abstract arguments placed on the described chip."""
    def place(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            tree)
    return place


@pytest.fixture(scope="module")
def programs(on_chip):
    model = get_model(chip_smoke.ARCH)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(chip_smoke.SEED), jnp.bfloat16))
    progs = step_program_args(model.cfg, chip_smoke.serve_config(), params,
                              prefill_batch=chip_smoke.N_REQUESTS,
                              prefill_len=chip_smoke.PROMPT_LEN)
    assert set(progs) == set(PROGRAMS)
    return {name: (fn, on_chip(args)) for name, (fn, args) in progs.items()}


@pytest.mark.parametrize("name", PROGRAMS)
def test_engine_program_fits_one_v5e(programs, name):
    fn, args = programs[name]
    ma = fn.lower(*args).compile().memory_analysis()
    need = chip_smoke.footprint(ma)
    assert need <= chip_smoke.HBM_SHARE * TPU_V5E.hbm_bytes, (name, ma)


def test_flash_attention_kernel_compiles_at_opt125m_width(on_chip):
    """The Pallas flash kernel at one opt-125m prefill (12 heads x 64)."""
    q = on_chip(jax.ShapeDtypeStruct((1, chip_smoke.PROMPT_LEN, 12, 64),
                                     jnp.bfloat16))
    lens = on_chip(jax.ShapeDtypeStruct((1,), jnp.int32))
    compiled = ops.flash_attention.lower(q, q, q, lens, scale=0.125,
                                         interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
