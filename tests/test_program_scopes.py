"""The ``jax.named_scope`` regions of the device program (``embed``,
``qkv``, ``kv_write``, ``chunk_attn``, ``decode_attn``, ``o_proj``,
``ffn``, ``logits``; ``sample`` in the sampler) name its ops in the
profile and change nothing else: the tiny programs compile to the same
HLO with and without them once metadata and instruction numbering are
set aside."""
import contextlib
import re

import jax
import pytest

from conftest import reduced_model
from repro.configs import ServeConfig
from repro.core import sampler
from repro.core.engine import step_program_args

SERVE = ServeConfig(mode="chunked", max_batch=3, page_size=4, n_pages=64,
                    max_pages_per_seq=12, prefill_chunk=4, n_streams=2,
                    chunk_tokens=8)
SCOPES = {"mixed_chunked": ("embed", "qkv", "kv_write", "chunk_attn",
                            "decode_attn", "o_proj", "ffn", "logits"),
          "decode": ("embed", "qkv", "kv_write", "decode_attn", "o_proj",
                     "ffn", "logits")}
DEBUG_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def canonical(hlo: str) -> str:
    """Compiled HLO text without its debug tables and op metadata, with
    every ``%name`` renumbered in order of first appearance."""
    paras = [p for p in hlo.split("\n\n") if not p.startswith(DEBUG_TABLES)]
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", "\n\n".join(paras))
    names: dict = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(0), f"%v{len(names)}"),
                  text)


def compiled_text(program: str) -> str:
    model = reduced_model("qwen3-0.6b")
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    prog, args = step_program_args(model.cfg, SERVE, params, prefill_batch=2,
                                   prefill_len=8)[program]
    return prog.lower(*args).compile().as_text()


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """Compile afresh: the persistent cache's key leaves op metadata out,
    so a program compiled once with the scopes would be read back for
    the one without them."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def no_scopes(monkeypatch):
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())


@pytest.mark.parametrize("program", sorted(SCOPES))
def test_scopes_change_only_metadata(monkeypatch, program):
    scoped = compiled_text(program)
    for name in SCOPES[program]:
        assert f"/{name}/" in scoped, name
    no_scopes(monkeypatch)
    plain = compiled_text(program)
    assert not any(f"/{name}/" in plain for name in SCOPES[program])
    assert canonical(scoped) == canonical(plain)


@pytest.mark.parametrize("fn", ["greedy_tokens", "sample_tokens"])
def test_sampler_scope_changes_only_metadata(monkeypatch, fn):
    f = getattr(sampler, fn).__wrapped__
    B, V = 3, 32
    args = [jax.ShapeDtypeStruct((B, V), "float32")]
    if fn == "sample_tokens":
        args += [jax.ShapeDtypeStruct((B,), d) for d in
                 ("float32", "int32", "float32", "int32", "int32", "int32")]

    def compile_fresh():    # a new function each time: no cached trace
        return jax.jit(lambda *a: f(*a)).lower(*args).compile().as_text()
    scoped = compile_fresh()
    assert "/sample/" in scoped
    no_scopes(monkeypatch)
    plain = compile_fresh()
    assert "/sample/" not in plain
    assert canonical(scoped) == canonical(plain)
