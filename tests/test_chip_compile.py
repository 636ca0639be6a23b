"""The engine's device programs compile for one TPU v5e chip at opt-125m's
published widths in bf16, and each fits that chip's memory at the page
pool chip_smoke.py serves with. The programs that write the pool update it
in place: its bytes are aliased from the donated input to the output, and
no copy of it is held in temporaries.

Nothing runs: the TPU compiler targets a chip that is only described.
Only one process at a time may load the TPU library, so the topology is
described inside a fixture, never while a module is imported, and every
compile of this kind stays in this file.
"""
import dataclasses
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
LAYER_SCANS = ("decode", "mixed", "mixed_prefill", "mixed_chunked")
# largest share of the pool's bytes a layer-scan program may hold in
# temporaries: the pool is carried through the scan, never copied
POOL_TEMP_SHARE = 0.25


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
    """``(program, abstract args)`` of one program for one KV dtype."""
    model = get_model(chip_smoke.ARCH)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(chip_smoke.SEED), jnp.bfloat16))
    built = {}

    def get(name, kv_dtype="fp"):
        if kv_dtype not in built:
            serve = dataclasses.replace(chip_smoke.serve_config(),
                                        kv_dtype=kv_dtype)
            progs = step_program_args(model.cfg, serve, params,
                                      prefill_batch=chip_smoke.N_REQUESTS,
                                      prefill_len=chip_smoke.PROMPT_LEN)
            assert set(progs) == set(PROGRAMS)
            built[kv_dtype] = {n: (fn, on_chip(args))
                               for n, (fn, args) in progs.items()}
        return built[kv_dtype][name]
    return get


@pytest.fixture(scope="module")
def compiled(programs):
    """Each program compiled once."""
    cache = {}

    def get(name, kv_dtype="fp"):
        if (name, kv_dtype) not in cache:
            fn, args = programs(name, kv_dtype)
            cache[name, kv_dtype] = fn.lower(*args).compile()
        return cache[name, kv_dtype]
    return get


@pytest.fixture(scope="module")
def memory(compiled):
    """``memory_analysis()`` of each program."""
    return lambda name, kv_dtype="fp": compiled(name, kv_dtype).memory_analysis()


@pytest.mark.parametrize("name", PROGRAMS)
def test_engine_program_fits_one_v5e(memory, name):
    ma = memory(name)
    need = chip_smoke.footprint(ma)
    assert need <= chip_smoke.HBM_SHARE * TPU_V5E.hbm_bytes, (name, ma)


# the bf16 pools keep the ids the cases had before the int8 dict pools
POOL_CASES = ([pytest.param(n, "fp", id=n) for n in LAYER_SCANS]
              + [pytest.param(n, "int8", id=f"{n}-int8") for n in LAYER_SCANS])


@pytest.mark.parametrize("name,kv_dtype", POOL_CASES)
def test_layer_scan_updates_pool_in_place(programs, memory, name, kv_dtype):
    _, args = programs(name, kv_dtype)
    pool = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(args[2:4]))
    ma = memory(name, kv_dtype)
    # the device lays the int8 pools' f32 scale planes out with the pages
    # along the lanes, rounded up to whole 128-lane tiles
    slack = 0 if kv_dtype == "fp" else 0.001 * pool
    assert pool <= ma.alias_size_in_bytes <= pool + slack, (name, kv_dtype, ma)
    assert ma.temp_size_in_bytes <= POOL_TEMP_SHARE * pool, (name, kv_dtype, ma)


@pytest.mark.parametrize("name", ["decode", "mixed", "mixed_chunked"])
def test_decode_attention_reads_pages_through_the_kernel(programs, compiled,
                                                         name):
    """The served programs with decode rows attend through the Pallas
    decode kernel, which reads each row's live pages in place: no op
    gathers the decode rows' full tables ``[max_batch x max_pages_per_seq,
    page_size, KV_p x hd]`` out of the pool."""
    serve = chip_smoke.serve_config()
    _, args = programs(name)
    row = args[2].shape[-1]
    table = (f"bf16[{serve.max_batch * serve.max_pages_per_seq},"
             f"{serve.page_size},{row}]")
    text = compiled(name).as_text()
    assert "tpu_custom_call" in text
    assert table not in text


def test_flash_attention_kernel_compiles_at_opt125m_width(on_chip):
    """The Pallas flash kernel at one opt-125m prefill (12 heads x 64)."""
    q = on_chip(jax.ShapeDtypeStruct((1, chip_smoke.PROMPT_LEN, 12, 64),
                                     jnp.bfloat16))
    lens = on_chip(jax.ShapeDtypeStruct((1,), jnp.int32))
    compiled = ops.flash_attention.lower(q, q, q, lens, scale=0.125,
                                         interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
