"""Useful FLOPs and least bytes of one mixed call, as the dense family
counts them (``bench/references/dense_transformer.py``), against hand
counts for opt-125m (12 layers x 768, 12 heads x 64, d_ff 3072, vocab
50,272)."""
from benchroot import REPO
from harness import costs
from harness.spec import Bench

dense = Bench(REPO).reference("dense_transformer")

# opt-125m's published sizes (huggingface.co/facebook/opt-125m)
OPT = {"n_layers": 12, "d_model": 768, "n_heads": 12, "n_kv_heads": 12,
       "head_dim": 64, "d_ff": 3072, "vocab_size": 50272}


def test_gemm_flops_per_row_by_hand():
    # q, k, v, o: 4 * 768 * 768; MLP: 2 * 768 * 3072; two FLOPs a MAC
    per_layer = 2 * (4 * 768 * 768 + 2 * 768 * 3072)
    assert dense.gemm_flops_per_row(OPT) == 12 * per_layer == 169_869_312


def test_one_step_by_hand():
    # one chunk of 192 tokens from position 512, two decode rows at 1000, 40
    chunks, decodes = [(512, 192)], [1000, 40]
    rows = 194
    keys = 192 * 512 + 192 * 193 // 2 + 1001 + 41
    attn = 4 * 12 * 64 * keys * 12
    unembed = 3 * 2 * 768 * 50272
    want = rows * 169_869_312 + attn + unembed
    assert dense.call_flops(OPT, chunks, decodes) == want

    layer_w = 4 * 768 * 768 + 2 * 768 * 3072 + 2 * 768
    weights = (12 * layer_w + 50272 * 768 + 768) * 2
    kv_tok = 2 * 12 * 64 * 12 * 2               # k and v, 12 layers, bf16
    moved = weights + rows * 768 * 2 + (512 + 1040 + rows) * kv_tok
    assert dense.call_bytes(OPT, chunks, decodes, 2) == moved


def test_ideal_time_names_its_bound():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = costs.ideal_seconds(197e12, 1.0, peaks)
    assert (t, bound) == (1.0, "compute")
    t, bound = costs.ideal_seconds(1.0, 819e9, peaks)
    assert (t, bound) == (1.0, "memory")
