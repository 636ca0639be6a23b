"""Moving the dense family's weight rules and counts out of the harness
and into ``bench/references/dense_transformer.py`` moved nothing: the
weights drawn from a seed are bit for bit, and the FLOPs and bytes of a
call are the numbers the harness gave before the move (recorded from
``bench/harness/weights.py`` ``make_params`` and ``bench/harness/costs.py``
as they stood then, on the CPU)."""
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchroot import REPO, TINY_CONFIG
from harness.main import build_model
from harness.spec import Bench
from harness.weights import make_params

dense = Bench(REPO).reference("dense_transformer")

WEIGHTS_SHA256 = {
    0: "55989f61afa3ef349f501f8aa5f0d12e98cf69e58f213ef5efdb7e9822e4b316",
    2**31 + 9: "4e617a0d0160b90fdc69c4ec03532e882c109d98b658a506a7ecb36306345fe6",
    5000000011: "bac797e886df4e54064054afc70c5a0f3ab9562b92e09f036e2fa3dfa0859de8",
}

# (chunks, decode kv lengths) of a call
CALLS = [([(512, 192)], [1000, 40]),
         ([(0, 256), (1536, 256)], [100 + 50 * i for i in range(64)]),
         ([], [2047] * 64),
         ([(1790, 2)], []),
         ([(0, 1)], [0])]
OPT_SIZES = {"n_layers": 12, "d_model": 768, "n_heads": 12, "n_kv_heads": 12,
             "head_dim": 64, "d_ff": 3072, "vocab_size": 50272}
# (flops, bf16 bytes) of each call, and K and V bytes of a token
COUNTS = {
    "starcoder2-3b": ([(1161155395584, 6113875968), (3544530812928, 9420650496),
                       (436073398272, 10085971968), (13136191488, 6114109440),
                       (12118081536, 6059120640)], 30720),
    "opt-125m": ([(37531607040, 311788032), (123816148992, 4277687808),
                  (20645412864, 5079062016), (549040128, 313188864),
                  (494247936, 247202304)], 36864),
    "tiny": ([(82699264, 619904), (358154240, 28205696), (76546048, 33710720),
              (2096640, 607104), (295936, 148864)], 256),
}


@pytest.mark.parametrize("seed", sorted(WEIGHTS_SHA256))
def test_tiny_weights_are_bit_identical(seed):
    model = build_model(TINY_CONFIG)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.bfloat16))
    params = make_params(shapes, TINY_CONFIG, seed, dense.leaf_std)
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).view(np.uint16).tobytes())
    assert h.hexdigest() == WEIGHTS_SHA256[seed]


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_call_counts_are_unchanged(name):
    cfg = {"starcoder2-3b": json.loads((REPO / "bench/configs/starcoder2-3b.json")
                                       .read_text()),
           "opt-125m": OPT_SIZES, "tiny": TINY_CONFIG}[name]
    want, kv = COUNTS[name]
    got = [(dense.call_flops(cfg, ch, d), dense.call_bytes(cfg, ch, d, 2))
           for ch, d in CALLS]
    assert got == want
    assert dense._kv_bytes_per_token(cfg, 2) == kv


@pytest.mark.parametrize("config", ["starcoder2-3b", "opt-125m"])
def test_every_leaf_of_a_repo_configuration_has_a_rule(config):
    """At full size, from shapes alone: every leaf of the program's tree
    has a rule, and one request of the longest lengths the configuration's
    mixes send fits its block table and the page pool."""
    bench = Bench(REPO)
    cfg = bench.config(config)
    family = bench.reference(cfg["reference"])
    model = build_model(cfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.bfloat16))
    for path, s in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        assert family.leaf_std(name, s.shape, cfg) > 0, name
    serve = cfg["serve"]
    for w in bench.all_cells():
        if bench.workload(w)["config"] == config:
            mix = bench.traffic(bench.workload(w)["traffic"])
            longest = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
            assert longest <= serve["page_size"] * serve["max_pages_per_seq"], w
    assert serve["n_pages"] >= serve["max_pages_per_seq"]
