"""What every family's counts share, and the least time of a call.

The useful FLOPs and least bytes of one mixed-program call are the
family's: ``call_flops(config, chunks, decodes)`` and ``call_bytes(config,
chunks, decodes, itemsize)`` of the module a configuration names under
``reference`` (``bench/references/<name>.py``).  A call is given as the
rows it really served, never the program's padded shapes: ``chunks`` of
(start position, tokens) and the kv lengths of its decode rows.
"""
from __future__ import annotations

from typing import Tuple

Chunk = Tuple[int, int]        # (start position, tokens)


def chunk_keys(start: int, n: int) -> int:
    """Keys attended by a chunk of ``n`` queries from position ``start``."""
    return n * start + n * (n + 1) // 2


def ideal_seconds(flops: float, nbytes: float, peaks: dict) -> Tuple[float, str]:
    """The least time of a call on the chip, and which bound sets it."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
