"""Per-request token sampling: greedy / temperature / top-k / top-p.

``SamplingParams`` travels with each :class:`~repro.core.engine.Request`
(vLLM-style); the engine lowers a batch of heterogeneous requests into
per-row parameter *arrays* and dispatches ONE jitted kernel
(:func:`sample_tokens`) — no static-argument retraces per knob
combination, so mixed batches (greedy next to temperature-0.8 next to
top-k) share a single compile per shape.

Determinism: row ``i``'s PRNG key is derived from
``(seed, rid, position)`` — the request's own seed, its id, and the
index of the token being sampled — never from engine state.  Sampled
outputs are therefore independent of batch composition, engine mode,
and preemption/resume history (the properties
``tests/test_api.py`` pins down).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class SamplingParams:
    """Per-request generation parameters (attached to ``Request.sampling``).

    ``temperature == 0`` means greedy (argmax); ``top_k == 0`` and
    ``top_p == 1.0`` disable their filters.  ``eos_id`` /
    ``stop_token_ids`` end generation early with
    ``finish_reason="stop"``; ``max_new_tokens`` ends it with
    ``finish_reason="length"``.
    """
    max_new_tokens: int = 16
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    eos_id: Optional[int] = None
    stop_token_ids: Tuple[int, ...] = ()
    seed: int = 0

    def __post_init__(self):
        if self.max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {self.max_new_tokens}")
        if self.temperature < 0.0:
            raise ValueError(f"temperature must be >= 0, got {self.temperature}")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")
        if self.eos_id is not None and (
                not isinstance(self.eos_id, int)
                or isinstance(self.eos_id, bool) or self.eos_id < 0):
            raise ValueError(
                f"eos_id must be a non-negative int or None, got "
                f"{self.eos_id!r}")
        if not isinstance(self.stop_token_ids, tuple) or any(
                not isinstance(t, int) or isinstance(t, bool) or t < 0
                for t in self.stop_token_ids):
            raise ValueError(
                f"stop_token_ids must be a tuple of non-negative ints, got "
                f"{self.stop_token_ids!r}")
        if not isinstance(self.seed, int) or isinstance(self.seed, bool) \
                or self.seed < 0:
            raise ValueError(
                f"seed must be a non-negative int (PRNGKey seed), got "
                f"{self.seed!r}")

    @property
    def stop_set(self) -> frozenset:
        s = frozenset(self.stop_token_ids)
        return s if self.eos_id is None else s | {self.eos_id}


@jax.jit
def greedy_tokens(logits):
    """Fast path for all-greedy batches (the serving hot path): plain
    argmax, skipping the sort/softmax/categorical machinery entirely.
    Bit-identical to sample_tokens rows with temperature <= 0."""
    with jax.named_scope("sample"):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _row_key(seed, rid, pos):
    """Independent stream per (request seed, request id, token index)."""
    return jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(seed), rid), pos)


@jax.jit
def sample_tokens(logits, temperature, top_k, top_p, seed, rid, pos):
    """logits [B, V] + per-row parameter arrays [B] -> tokens [B] int32.

    Every row is processed with its own knobs in one program: rows with
    ``temperature <= 0`` take the exact argmax (bit-identical to a pure
    greedy engine); the rest are temperature-scaled, top-k- then
    top-p-masked, and sampled from their ``(seed, rid, pos)`` stream.
    """
    with jax.named_scope("sample"):
        V = logits.shape[-1]
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

        lg = logits / jnp.maximum(temperature, 1e-6)[:, None]
        # top-k: mask strictly below each row's k-th largest logit (k=0 -> off)
        sorted_lg = jnp.sort(lg, axis=-1)[:, ::-1]
        kth = jnp.take_along_axis(
            sorted_lg, jnp.clip(top_k - 1, 0, V - 1)[:, None], axis=-1)
        lg = jnp.where((top_k[:, None] > 0) & (lg < kth), -1e30, lg)
        # top-p (nucleus) on the top-k-masked logits (p=1 -> off)
        sorted_lg = jnp.sort(lg, axis=-1)[:, ::-1]
        cum = jnp.cumsum(jax.nn.softmax(sorted_lg, axis=-1), axis=-1)
        cutoff_idx = jnp.sum(cum < top_p[:, None], axis=-1, keepdims=True)
        cutoff = jnp.take_along_axis(sorted_lg,
                                     jnp.clip(cutoff_idx, 0, V - 1), axis=-1)
        lg = jnp.where((top_p[:, None] < 1.0) & (lg < cutoff), -1e30, lg)

        keys = jax.vmap(_row_key)(seed, rid, pos)
        sampled = jax.vmap(lambda k, row: jax.random.categorical(k, row))(
            keys, lg)
        return jnp.where(temperature <= 0.0, greedy, sampled.astype(jnp.int32))
