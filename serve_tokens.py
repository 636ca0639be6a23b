"""The tokens the engine serves, for comparing two checkouts on the CPU.

    JAX_PLATFORMS=cpu python serve_tokens.py --out a.json      # checkout A
    JAX_PLATFORMS=cpu python serve_tokens.py --out b.json      # checkout B
    cmp a.json b.json

Serves the same six greedy requests (13-token prompts, 7 new tokens) in
every engine mode (sequential, splitwiser, splitwiser_mps, chunked), with
fp and int8 KV pages, f32 and bf16 weights, for each architecture reduced
to test size, and writes ``{"<arch>/<dtype>/<kv>/<mode>": {rid: tokens}}``
as sorted JSON. A change that only moves data gives a byte-identical file.
"""
import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCHS = ("qwen3-0.6b", "opt-125m", "starcoder2-3b")
MODES = ("sequential", "splitwiser", "splitwiser_mps", "chunked")


def served_tokens(archs=ARCHS) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import ServeConfig, get_config
    from repro.core.engine import Engine, Request
    from repro.core.sampler import SamplingParams
    from repro.models.registry import CACHE_KIND, FAMILY_MODULE, Model

    out = {}
    for arch in archs:
        cfg = get_config(arch).reduced()
        model = Model(arch, cfg, FAMILY_MODULE[cfg.family],
                      CACHE_KIND[cfg.family])
        prompts = np.random.RandomState(7).randint(
            0, cfg.vocab_size, (6, 13)).tolist()
        for dt, dtype in (("f32", jnp.float32), ("bf16", jnp.bfloat16)):
            params = model.init(jax.random.PRNGKey(0), dtype)
            for kv in ("fp", "int8"):
                for mode in MODES:
                    serve = ServeConfig(
                        mode=mode, max_batch=4, page_size=4, n_pages=64,
                        max_pages_per_seq=12, prefill_chunk=4, n_streams=2,
                        kv_dtype=kv, enable_prefix_cache=True,
                        chunk_tokens=8 if mode == "chunked" else 4)
                    eng = Engine(model, params, serve)
                    eng.run([Request(rid=i, prompt=list(p),
                                     sampling=SamplingParams(max_new_tokens=7))
                             for i, p in enumerate(prompts)])
                    out[f"{arch}/{dt}/{kv}/{mode}"] = {
                        o.rid: list(o.tokens) for o in eng.poll()}
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True, help="JSON file to write")
    ap.add_argument("--arch", action="append", choices=ARCHS,
                    help="architectures to serve (default: all)")
    args = ap.parse_args()
    out = served_tokens(tuple(args.arch or ARCHS))
    Path(args.out).write_text(json.dumps(out, sort_keys=True))
    print(f"{len(out)} runs -> {args.out}")


if __name__ == "__main__":
    main()
