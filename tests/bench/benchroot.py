"""A tiny copy of the benchmark for CPU tests: the real ``bench/`` tree
with one extra configuration at toy widths and its cells.

A plain module, not a ``conftest.py``: the top-level tests import their
own ``conftest`` by name, and a second one here would shadow it."""
import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "bench"))

TINY_CONFIG = {
    "source": "test", "arch": "opt-125m", "reference": "dense_transformer",
    "dtype": "bfloat16", "n_layers": 2, "d_model": 64, "n_heads": 4,
    "n_kv_heads": 2, "head_dim": 16, "d_ff": 128, "vocab_size": 256,
    "rope_theta": 10000.0, "norm_eps": 1e-6, "mlp": "gelu_tanh",
    "norm": "rms_1plus", "bias": False, "positions": "rope",
    "sliding_window": None,
    "serve": {"mode": "chunked", "max_batch": 4, "page_size": 4,
              "n_streams": 2, "chunk_tokens": 16, "max_pages_per_seq": 16,
              "n_pages": 128},
}
TINY_LENGTHS = {"prompt_tokens": {"median": 20, "sigma": 0.5, "min": 4, "max": 40},
                "output_tokens": {"median": 8, "sigma": 0.5, "min": 2, "max": 16}}


def make_tiny_root(dst: Path, gap_limit: float = 0.01) -> Path:
    """A benchmark root under ``dst`` holding the real ``bench/`` files plus
    ``tiny.chat`` and ``tiny.backlog`` cells at toy sizes."""
    shutil.copytree(REPO / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    (dst / "bench" / "configs" / "tiny.json").write_text(json.dumps(TINY_CONFIG))
    chat = dict(json.loads((REPO / "bench/traffic/chat.json").read_text()),
                **TINY_LENGTHS, drain_s=20)
    backlog = dict(json.loads((REPO / "bench/traffic/backlog.json").read_text()),
                   **TINY_LENGTHS, warm_steps=10, drain_s=20, block=8)
    (dst / "bench" / "traffic" / "tiny_chat.json").write_text(json.dumps(chat))
    (dst / "bench" / "traffic" / "tiny_backlog.json").write_text(json.dumps(backlog))
    (dst / "bench" / "cells" / "tiny.chat.json").write_text(json.dumps(
        {"rate_per_s": 8.0, "logit_gap_limit": gap_limit, "min_compared_tokens": 20}))
    (dst / "bench" / "cells" / "tiny.backlog.json").write_text(json.dumps(
        {"logit_gap_limit": gap_limit, "min_compared_tokens": 20}))
    spec["workloads"] = [
        {"name": "tiny.chat", "config": "tiny", "traffic": "tiny_chat", "chips": 1,
         "why": "test"},
        {"name": "tiny.backlog", "config": "tiny", "traffic": "tiny_backlog",
         "chips": 1, "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            kind = "chat" if any(w.endswith(".chat") for w in m["workloads"]) else "backlog"
            m["workloads"] = [f"tiny.{kind}"]
    (dst / "BENCHMARK.json").write_text(json.dumps(spec))
    return dst
