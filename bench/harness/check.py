"""The comparison that decides ``correct``.

After the window has closed, a sample of the requests the window served
is drawn from the seed, with the longest of them always in it.  The plain
reference (``bench/references/<name>.py``) runs once over each prompt
followed by its served tokens, and each served token's *gap* is read: how
far the reference's logit of that token lies below the reference's best
logit at that position.  A greedy program that computes what the
reference computes serves the best token, or one that rounding puts level
with it, so its widest gap stays small.  The number compared is the
widest gap over the sample.

The control is the reference itself computed in fp8 (``quant="fp8"``):
at the same positions of the same sequences, the token that the fp8
logits put first is read against the f32 reference in the same way.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence

import numpy as np


@dataclass
class Served:
    rid: int
    prompt: List[int]
    tokens: List[int]


def draw_sample(served: Sequence[Served], seed: int, k: int) -> List[Served]:
    """``k`` requests drawn from the seed, the longest always among them."""
    if not served:
        return []
    longest = max(served, key=lambda s: (len(s.prompt) + len(s.tokens), s.rid))
    rest = sorted((s for s in served if s is not longest), key=lambda s: s.rid)
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 7])
    pick = rng.permutation(len(rest))[: max(0, k - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


def _gap_fns():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gaps_of(ref, toks):
        """ref [A, V]; toks [A] -> best - ref[tok] per row."""
        return jnp.max(ref, -1) - jnp.take_along_axis(ref, toks[:, None], 1)[:, 0]

    @jax.jit
    def control_gaps(ref, ctl):
        return gaps_of(ref, jnp.argmax(ctl, -1).astype(jnp.int32))

    return gaps_of, control_gaps


def widest_gaps(reference, params, config: dict, sample: Sequence[Served],
                t_pad: int, a_pad: int, control: bool = False) -> dict:
    """The widest gap of the served tokens (``program``) and, with
    ``control``, of the fp8 reference's first choices (``control``);
    ``<side>_off_top`` counts the positions where that side's token is not
    the reference's first choice."""
    import jax.numpy as jnp
    gaps_of, control_gaps = _gap_fns()
    sides = ("program", "control") if control else ("program",)
    worst = {k: 0.0 for k in sides}
    worst.update({f"{k}_off_top": 0 for k in sides})
    n_tokens = 0
    for s in sample:
        seq = list(s.prompt) + list(s.tokens[:-1])
        n = len(s.tokens)
        if len(seq) > t_pad or n > a_pad:
            raise ValueError(f"request {s.rid}: {len(seq)} positions, "
                             f"{n} served tokens exceed the padded shapes")
        toks = np.zeros(t_pad, np.int32)
        toks[: len(seq)] = seq
        at = np.zeros(a_pad, np.int32)
        at[:n] = len(s.prompt) - 1 + np.arange(n)
        served = np.zeros(a_pad, np.int32)
        served[:n] = s.tokens
        ref = reference.logits_at(params, config, toks, at)
        g = np.asarray(gaps_of(ref, jnp.asarray(served)))[:n]
        worst["program"] = max(worst["program"], float(g.max()))
        worst["program_off_top"] += int((g > 0).sum())
        if control:
            ctl = reference.logits_at(params, config, toks, at, quant="fp8")
            c = np.asarray(control_gaps(ref, ctl))[:n]
            worst["control"] = max(worst["control"], float(c.max()))
            worst["control_off_top"] += int((c > 0).sum())
        n_tokens += n
    worst["tokens"] = n_tokens
    return worst


def verdict(gap: Optional[float], limit: float) -> bool:
    return gap is not None and np.isfinite(gap) and gap <= limit
