"""Bring-up smoke of the serving engine on one TPU chip.

    python chip_smoke.py

Drives the engine's main path once at opt-125m's published widths
(12 layers x 768, 12 heads x 64, vocabulary 50,272) in bf16, with random
weights made from ``SEED``.  Phases run in order; each prints what it
found, and any failure exits non-zero:

  device     ``jax.devices()``; anything but a TPU is refused.
  compile    lowers and compiles every program shape the engine runs here
             (``step_program_args``) and prints ``memory_analysis()`` and
             the compile seconds.  The page pool (``SERVE_KW["n_pages"]``)
             is sized so that each program's footprint stays under
             ``HBM_SHARE`` of the device's memory; a program over it fails.
  serve      every engine mode serves ``N_REQUESTS`` prompts of
             ``PROMPT_LEN`` tokens to ``NEW_TOKENS`` new tokens through
             ``repro.launch.serve.build_engine``; each request must finish
             with its token count and in-vocabulary tokens.
  reference  the engine's jitted prefill and decode programs against an f32
             forward of the same weights; the largest logit error must stay
             within ``REF_TOL``.

The last line of stdout is one JSON object naming the device.  bf16
greedy streams of random weights differ between modes after a few tokens
(near-tied logits), so modes are not compared with each other here; the
CPU tests assert cross-mode token identity.
"""
from __future__ import annotations

import gc
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "opt-125m"
SEED = 0
MODES = ("sequential", "splitwiser", "splitwiser_mps", "chunked")
# 4096 pages of 16 tokens = 2.25 GiB of bf16 KV at opt-125m's widths
SERVE_KW = dict(max_batch=8, page_size=16, n_pages=4096, n_streams=2,
                prefill_chunk=128, max_pages_per_seq=32)
N_REQUESTS = 8           # one sequential prefill batch: fills max_batch
PROMPT_LEN = 256         # page-aligned, so every prefill has one shape
NEW_TOKENS = 16
HBM_SHARE = 0.5          # largest share of device memory one program may need
REF_DECODE_STEPS = 4
# Largest |logit| error of the bf16 programs against the f32 reference,
# relative to the reference's largest |logit|.  On the CPU at these widths
# and 2-6 layers bf16 rounding gives 0.0064-0.0069, and a decode that
# attends one position off gives 0.128.
REF_TOL = 0.03


def footprint(ma) -> int:
    """Device bytes one program needs while it runs, from its
    ``memory_analysis()``: arguments, outputs not aliased to a donated
    argument, and temporaries."""
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def fail(msg: str):
    sys.exit(f"chip_smoke: FAILED: {msg}")


def phase_device():
    import jax
    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}")
    if dev.platform != "tpu":
        fail(f"no TPU found (JAX's devices are {dev.platform!r}); "
             "this smoke runs only on a TPU")
    return dev


def serve_config():
    from repro.configs import ServeConfig
    # chunked is the one mode with a shape of its own (chunk_tokens rows);
    # every other field is what build_engine sets for every mode
    return ServeConfig(mode="chunked", **SERVE_KW)


def phase_compile(dev, model):
    import jax
    import jax.numpy as jnp

    from repro.core.engine import step_program_args
    limit = dev.memory_stats()["bytes_limit"]
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(SEED), jnp.bfloat16))
    programs = step_program_args(model.cfg, serve_config(), params,
                                 prefill_batch=N_REQUESTS,
                                 prefill_len=PROMPT_LEN)
    for name, (fn, args) in programs.items():
        t0 = time.perf_counter()
        ma = fn.lower(*args).compile().memory_analysis()
        dt = time.perf_counter() - t0
        need = footprint(ma)
        print(f"compile {name}: {dt:.2f} s  argument={ma.argument_size_in_bytes} "
              f"output={ma.output_size_in_bytes} alias={ma.alias_size_in_bytes} "
              f"temp={ma.temp_size_in_bytes} footprint={need} "
              f"({need / limit:.3f} of bytes_limit={limit})")
        if need > HBM_SHARE * limit:
            fail(f"{name} needs {need} bytes, over {HBM_SHARE} of {limit}: "
                 "shrink SERVE_KW['n_pages']")


def phase_serve(dev, mode):
    import jax
    import jax.numpy as jnp

    from repro.core.engine import Request
    from repro.core.sampler import SamplingParams
    from repro.data import report_tokens
    from repro.launch.serve import build_engine

    engine, cfg = build_engine(ARCH, mode, reduced=False, seed=SEED,
                               dtype=jnp.bfloat16, **SERVE_KW)
    prompts = report_tokens(N_REQUESTS, PROMPT_LEN, cfg.vocab_size)
    reqs = [Request(rid=i, prompt=p,
                    sampling=SamplingParams(max_new_tokens=NEW_TOKENS))
            for i, p in enumerate(prompts)]
    t0 = time.perf_counter()
    metrics = engine.run(reqs)
    jax.block_until_ready((engine.k_pages, engine.v_pages))
    wall = time.perf_counter() - t0
    outputs = engine.poll()
    if len(outputs) != N_REQUESTS:
        fail(f"{mode}: {len(outputs)} of {N_REQUESTS} requests finished")
    for o in outputs:
        if o.finish_reason != "length" or len(o.tokens) != NEW_TOKENS:
            fail(f"{mode}: request {o.rid} ended {o.finish_reason!r} "
                 f"after {len(o.tokens)} of {NEW_TOKENS} tokens")
        if not all(0 <= t < cfg.vocab_size for t in o.tokens):
            fail(f"{mode}: request {o.rid} sampled a token outside the "
                 f"vocabulary: {o.tokens}")
    peak = dev.memory_stats()["peak_bytes_in_use"]
    print(f"serve {mode}: {len(outputs)}/{N_REQUESTS} requests x "
          f"{NEW_TOKENS} tokens  steps={metrics.n_steps} "
          f"wall={wall:.3f} s (compiles included)  "
          f"peak_bytes_in_use={peak}")
    del engine
    gc.collect()


def phase_reference(model):
    """Prefill R prompts of different lengths and decode REF_DECODE_STEPS
    greedy tokens with the engine's programs, then compare every step's
    logits with an f32 forward of the upcast weights over the same
    tokens."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.engine import build_step_programs, init_pool, pool_layout
    from repro.models import transformer as T

    cfg, serve = model.cfg, serve_config()
    ps, W, R = serve.page_size, serve.max_pages_per_seq, serve.max_batch
    params = model.init(jax.random.PRNGKey(SEED), jnp.bfloat16)
    progs = build_step_programs(cfg, serve.kv_dtype)
    n_pages, _ = pool_layout(cfg, serve, jnp.bfloat16)
    kpg, vpg = init_pool(cfg, serve, n_pages, jnp.bfloat16)

    rng = np.random.default_rng(SEED + 1)
    lens = np.linspace(PROMPT_LEN, PROMPT_LEN // 4, R).astype(np.int32)
    tokens = np.zeros((R, PROMPT_LEN), np.int32)
    for i, n in enumerate(lens):
        tokens[i, :n] = rng.integers(0, cfg.vocab_size, n)
    logits, (k, v) = progs.prefill(params, jnp.asarray(tokens),
                                   jnp.asarray(lens))
    # row i owns pages i*W .. i*W+W-1; pages past its prompt go to trash,
    # as the engine's commit does
    n_per = PROMPT_LEN // ps
    bt = np.arange(R * W, dtype=np.int32).reshape(R, W)
    dest = np.full((R, n_per), n_pages - 1, np.int32)
    for i, n in enumerate(lens):
        used = -(-int(n) // ps)
        dest[i, :used] = bt[i, :used]
    kpg, vpg = progs.commit(kpg, vpg, T.kv_to_pages(k, ps),
                            T.kv_to_pages(v, ps), jnp.asarray(dest.ravel()))

    got = [np.asarray(logits, np.float32)]
    seq_lens = lens.copy()
    out_toks = []
    active = jnp.ones((R,), bool)
    for _ in range(REF_DECODE_STEPS):
        tok = got[-1][:, :cfg.vocab_size].argmax(-1).astype(np.int32)
        out_toks.append(tok)
        logits, (kpg, vpg) = progs.decode(
            params, jnp.asarray(tok), kpg, vpg, jnp.asarray(bt),
            jnp.asarray(seq_lens), active)
        got.append(np.asarray(logits, np.float32))
        seq_lens = seq_lens + 1
    got = np.stack(got, 1)[..., :cfg.vocab_size]      # [R, 1+steps, V]

    # causal: zeros after each row's tokens never reach compared positions
    T_ref = PROMPT_LEN + REF_DECODE_STEPS
    seqs = np.zeros((R, T_ref), np.int32)
    pos = np.zeros((R, 1 + REF_DECODE_STEPS), np.int32)
    for i, n in enumerate(lens):
        seqs[i, :n] = tokens[i, :n]
        seqs[i, n:n + REF_DECODE_STEPS] = [t[i] for t in out_toks]
        pos[i] = n - 1 + np.arange(1 + REF_DECODE_STEPS)
    params32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)

    @jax.jit
    def reference(p, s, at):
        full, _ = T.train_logits(p, cfg, {"tokens": s})
        return jnp.take_along_axis(full, at[..., None], axis=1)

    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference(params32, jnp.asarray(seqs),
                                    jnp.asarray(pos)))[..., :cfg.vocab_size]
    if not np.isfinite(got).all():
        fail("reference: the engine's logits are not all finite")
    err = float(np.abs(got - want).max())
    scale = float(np.abs(want).max())
    rel = err / scale
    print(f"reference: {R} prompts of {lens.min()}-{lens.max()} tokens, "
          f"prefill + {REF_DECODE_STEPS} decode steps  max|err|={err:.5f}  "
          f"max|ref|={scale:.5f}  rel={rel:.5f}  tol={REF_TOL}")
    if rel > REF_TOL:
        fail(f"reference: relative logit error {rel:.5f} over {REF_TOL}")


def main():
    if not (SRC / "repro").is_dir():
        fail(f"no repro package under {SRC}: run from a checkout of the repo")
    sys.path.insert(0, str(SRC))
    dev = phase_device()

    import jax

    from repro.common.compile_cache import configure_compile_cache
    from repro.models.registry import get_model
    print(f"compile cache: {configure_compile_cache()}")
    model = get_model(ARCH)
    phase_compile(dev, model)
    for mode in MODES:
        phase_serve(dev, mode)
    phase_reference(model)
    devs = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
