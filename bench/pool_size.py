"""Size a configuration's page pool by compiling for a described v5e.

    JAX_PLATFORMS=cpu python bench/pool_size.py --config starcoder2-3b \
        --pages 2048 4096 6144

Compiles the one mixed-program shape a chunked cell runs (``n_streams``
chunks of ``chunk_tokens`` rows plus ``max_batch`` decode rows) and the
two greedy-sampler shapes, for a TPU v5e that is only described, at each
pool size, and prints ``memory_analysis()``: argument, output, alias and
temp bytes, and the footprint (arguments + unaliased outputs + temp).  A
pool whose program the compiler refuses prints the refusal.  Nothing
runs.
"""
from __future__ import annotations

import argparse
import math
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--pages", type=int, nargs="+", required=True)
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from harness.main import build_model
    from harness.spec import Bench
    from repro.configs import ServeConfig
    from repro.core.engine import step_program_args
    from repro.core.sampler import greedy_tokens
    from repro.models.transformer import pad_vocab

    jax.config.update("jax_enable_compilation_cache", False)
    bench = Bench(ROOT)
    cfg = bench.config(args.config)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def place(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)

    model = build_model(cfg)
    dtype = jnp.dtype(cfg["dtype"])
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), dtype))
    s = cfg["serve"]
    Vp = pad_vocab(cfg["vocab_size"])
    for rows in (s["n_streams"], s["max_batch"]):
        ma = jax.jit(greedy_tokens).lower(
            place(jax.ShapeDtypeStruct((rows, Vp), jnp.float32))).compile(
        ).memory_analysis()
        print(f"sampler rows={rows}: temp={ma.temp_size_in_bytes}")
    for n_pages in args.pages:
        serve = ServeConfig(**dict(s, n_pages=n_pages))
        progs = step_program_args(model.cfg, serve, params, prefill_batch=1,
                                  prefill_len=s["page_size"])
        fn, fargs = progs["mixed_chunked"]
        try:
            ma = fn.lower(*place(fargs)).compile().memory_analysis()
        except Exception as e:          # the compiler's refusal is the answer
            print(f"pages={n_pages}: refused: {str(e).splitlines()[0][:300]}")
            continue
        # the K and V pools as the program allocates them
        pool = sum(math.prod(a.shape) * a.dtype.itemsize
                   for a in jax.tree.leaves((fargs[2], fargs[3])))
        foot = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
        print(f"pages={n_pages}: pool={pool} argument={ma.argument_size_in_bytes} "
              f"output={ma.output_size_in_bytes} alias={ma.alias_size_in_bytes} "
              f"temp={ma.temp_size_in_bytes} footprint={foot} "
              f"temp/pool={ma.temp_size_in_bytes / pool:.3f}")


if __name__ == "__main__":
    main()
