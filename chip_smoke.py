#!/usr/bin/env python3
"""Chip smoke test: serve smollm-135m at full width on one TPU.

    python chip_smoke.py

One process, phases in order; any failure exits non-zero:

1. device: print platform, device kind and count; refuse anything that
   is not a TPU (no CPU fallback).
2. lower: compile the engine's jitted ``decode_multi_paged`` and
   ``prefill_wave`` at smollm-135m full width in bf16 and require a
   ``tpu_custom_call`` in each (the Pallas paged kernels, not the
   gather oracles).
3. kernels: run both paged kernels on seeded bf16 inputs at smollm-135m
   and chatglm-6b widths against the f32 gather oracles; max error must
   stay within ``BF16_TOL``.
4. serve: ``run_paged_engine_backend`` with ``magnus-paged``, the prefix
   cache and seed 0 over a few seconds of Poisson traffic, smollm-135m
   unreduced in bf16.  Every request must get exactly
   ``min(gen_length, max_gen)`` tokens and the pool must drain.
5. logits: one request's engine logits (after admission and after its
   first decode window) against a plain f32 forward (full-sequence
   ``prefill``, no pages) on the same seeded weights, as the relative
   L2 error ``|engine - ref| / |ref|`` over the real vocab.  An f32
   engine at the highest matmul precision must stay within
   ``F32_LOGITS_RTOL``; the bf16 serving engine's drift is printed and
   bounded by ``BF16_LOGITS_RTOL``.

Compile seconds (XLA compiles plus persistent-cache loads, with the
count of programs the cache supplied) and served counts are printed
before the last line, which is one JSON object:
``{"ok": true, "device": {...}}``.  No speed is measured.
"""
from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "smollm-135m"
# max |kernel - oracle| for bf16 inputs and bf16 outputs (the bound the
# interpret-mode kernel tests use)
BF16_TOL = 5e-2
# |engine - f32 forward| / |f32 forward| (L2) over the real vocab.
# f32 engine, highest matmul precision: only summation order differs
F32_LOGITS_RTOL = 1e-3
# bf16 engine: this random-weight model amplifies bf16 rounding through
# its 30 layers (max error 33-43% of the largest logit on a v5e).  Two
# unrelated logit vectors of equal norm are sqrt(2) apart: the bound
# catches a broken path, not rounding
BF16_LOGITS_RTOL = 0.7
# (name, Hq, Hkv, D) at published widths
KERNEL_WIDTHS = (("smollm-135m", 9, 3, 64), ("chatglm-6b", 32, 32, 128))
# the serve launcher's engine geometry (launch/serve.py defaults)
SLOTS, NUM_BLOCKS, BLOCK_TOKENS, MAX_LEN, MAX_GEN = 16, 128, 16, 200, 32
RATE, DURATION, SEED = 3.0, 3.0, 0


class CompileClock:
    """From jax.monitoring: seconds spent getting executables (an XLA
    compile or a persistent-cache load), how many, and how many of them
    the persistent cache supplied."""

    def __init__(self):
        from jax import monitoring
        self.seconds, self.count, self.cache_hits = 0.0, 0, 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.count += 1

    def _on_event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def within(what: str, errs: dict, tol: float) -> bool:
    """Print each error against ``tol``; False (and a FAIL line) if any
    exceeds it or is not a number."""
    for name, err in errs.items():
        log(f"{what} {name}: err={err:.3e} (tol {tol})")
    bad = [n for n, e in errs.items() if not e <= tol]
    if bad:
        log(f"FAIL: {what} error above {tol}: {bad}")
    return not bad


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def lower_engine_steps(cfg, dtype, sharding) -> dict:
    """Compile the engine's jitted ``decode_multi_paged`` (an 8-step
    window) and ``prefill_wave`` (4 rows at the largest suffix bucket)
    for ``sharding``'s device from shapes alone (no weights are made;
    a described, unattached chip works too) and return each compiled
    program's text."""
    import jax
    import jax.numpy as jnp
    from repro.models import model as M
    from repro.models.transformer import cast_params
    from repro.serving.engine import _jitted

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    def on_device(tree):
        return jax.tree.map(lambda a: spec(a.shape, a.dtype), tree)

    max_blocks = -(-(MAX_LEN + MAX_GEN) // BLOCK_TOKENS)
    params = on_device(jax.eval_shape(lambda: cast_params(
        M.init_params(cfg, jax.random.PRNGKey(SEED)), dtype)))
    pages = on_device(jax.eval_shape(lambda: M.init_paged_cache(
        cfg, NUM_BLOCKS, BLOCK_TOKENS, dtype)))
    logits = spec((SLOTS, cfg.padded_vocab), dtype)
    tables = spec((SLOTS, max_blocks), jnp.int32)
    per_slot = spec((SLOTS,), jnp.int32)
    active = spec((SLOTS,), jnp.bool_)
    jt = _jitted(cfg, dtype)
    window, wave_batch, suffix = 8, 4, 256
    out = {}
    out["decode_multi_paged"] = jt["decode_multi_paged"].lower(
        params, pages=pages,
        batch={"logits": logits, "positions": per_slot,
               "block_tables": tables, "active": active},
        num_steps=window).compile().as_text()
    row = spec((wave_batch,), jnp.int32)
    out["prefill_wave"] = jt["prefill_wave"].lower(
        params, pages=pages,
        state={"tables": tables, "positions": per_slot, "active": active,
               "logits": logits},
        batch={"tokens": spec((wave_batch, suffix), jnp.int32),
               "lengths": row, "prefix_lens": row,
               "attn_tables": spec((wave_batch, max_blocks), jnp.int32),
               "tables": spec((wave_batch, max_blocks), jnp.int32),
               "write_lens": row, "cow_src": row, "cow_dst": row,
               "slots": row, "row_sel": row, "positions": row}
    ).compile().as_text()
    return out


def kernel_errors() -> dict:
    """Max |kernel - f32 oracle| of both paged kernels at each width of
    ``KERNEL_WIDTHS``, on seeded bf16 inputs with ragged lengths."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.decode_attention.kernel import (
        paged_decode_attention_kernel, paged_prefix_prefill_attention_kernel)
    from repro.kernels.decode_attention.ref import (
        paged_decode_attention_ref, paged_prefix_prefill_attention_ref)

    bt, nb, mb = BLOCK_TOKENS, 512, 16
    rng = np.random.default_rng(SEED)
    f32 = functools.partial(jax.tree.map, lambda a: a.astype(jnp.float32)
                            if a.dtype == jnp.bfloat16 else a)

    def oracle(fn, *args):
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*f32(args))

    def normal(shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    errs = {}
    for name, hq, hkv, d in KERNEL_WIDTHS:
        kp, vp = normal((nb, hkv, bt, d)), normal((nb, hkv, bt, d))
        # decode: one query per slot, lengths 1 .. mb * bt, distinct pages
        b = SLOTS
        tables = jnp.asarray(rng.permutation(np.arange(1, nb))[:b * mb]
                             .reshape(b, mb), jnp.int32)
        lens = jnp.asarray(rng.integers(1, mb * bt + 1, b), jnp.int32)
        q = normal((b, hq, d))
        out = jax.jit(paged_decode_attention_kernel)(q, kp, vp, tables, lens)
        ref = oracle(paged_decode_attention_ref, q, kp, vp, tables, lens)
        errs[f"decode/{name}"] = float(jnp.max(jnp.abs(
            out.astype(jnp.float32) - ref)))
        # prefix prefill at the largest suffix bucket: a miss row, a
        # partial-block prefix, a full table and a one-token suffix
        b, s = 4, 256
        plens = jnp.asarray([0, 37, mb * bt, 100], jnp.int32)
        slens = np.asarray([s, 200, 64, 1], np.int32)
        q = normal((b, s, hq, d))
        ks, vs = normal((b, s, hkv, d)), normal((b, s, hkv, d))
        args = (q, ks, vs, kp, vp, tables[:b], plens, jnp.asarray(slens))
        out = jax.jit(paged_prefix_prefill_attention_kernel)(*args)
        ref = oracle(paged_prefix_prefill_attention_ref, *args)
        # rows past a suffix length are padding: compare the valid ones
        errs[f"prefix_prefill/{name}"] = max(
            float(jnp.max(jnp.abs(out[i, :n].astype(jnp.float32)
                                  - ref[i, :n])))
            for i, n in enumerate(slens))
    return errs


def serve() -> tuple:
    """One full-width bf16 run of the paged serve launcher; returns
    (summary, the workload it served)."""
    from repro.launch.serve import run_paged_engine_backend
    from repro.workload.generator import poisson_workload
    out = run_paged_engine_backend(
        ARCH, RATE, DURATION, "magnus-paged", SEED, num_blocks=NUM_BLOCKS,
        block_tokens=BLOCK_TOKENS, max_concurrency=SLOTS, prefix_cache=True,
        full_width=True, dtype="bfloat16")
    return out, poisson_workload(RATE, DURATION, seed=SEED, max_len=MAX_LEN,
                                 max_gen=MAX_GEN)


def logits_error(cfg, dtype, precision=None) -> dict:
    """Relative L2 error of one request's engine logits (engine in
    ``dtype`` at matmul ``precision``) against a plain f32 forward
    (full-sequence ``prefill``, highest precision) on the same seeded
    weights, right after admission and after its first decode window."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import model as M
    from repro.serving.engine import PagedContinuousEngine, _bucket
    from repro.workload.generator import poisson_workload

    v = cfg.vocab_size
    req = poisson_workload(RATE, DURATION, seed=SEED, max_len=MAX_LEN,
                           max_gen=MAX_GEN)[0]
    req.gen_length = req.predicted_gen_length = MAX_GEN
    with jax.default_matmul_precision(precision):
        eng = PagedContinuousEngine(
            cfg, seed=SEED, dtype=dtype, max_concurrency=SLOTS,
            num_blocks=NUM_BLOCKS, block_tokens=BLOCK_TOKENS,
            max_len=MAX_LEN, max_gen=MAX_GEN)
        if eng.join_many([req]) != 1:
            raise RuntimeError("engine refused the logits-check request")
        slot = next(i for i, a in enumerate(eng.active) if a is not None)
        ids = eng._prompt_ids(req)
        got = [np.asarray(eng.logits[slot, :v], np.float32)]
        eng.step_window(max_steps=8)       # well short of its 32 tokens
        toks = list(eng.active[slot]["generated"])
        got.append(np.asarray(eng.logits[slot, :v], np.float32))
        while eng.num_active:
            eng.step_window()
        eng.assert_drained()

    params = M.init_params(cfg, jax.random.PRNGKey(SEED))   # f32

    @jax.jit
    def forward(params, tokens, lengths):
        return M.prefill(params, cfg, {"tokens": tokens, "lengths": lengths},
                         act_dtype=jnp.float32)[0]

    errs = {}
    with jax.default_matmul_precision("highest"):
        for name, seq, have in (("admit", ids, got[0]),
                                (f"decode+{len(toks)}", ids + toks, got[1])):
            tokens = np.zeros((1, _bucket(len(seq))), np.int32)
            tokens[0, :len(seq)] = seq
            lengths = np.asarray([len(seq)], np.int32)
            ref = np.asarray(forward(params, tokens, lengths)[0, :v],
                             np.float32)
            errs[name] = float(np.linalg.norm(have - ref)
                               / max(float(np.linalg.norm(ref)), 1e-30))
    return errs


def main() -> int:
    dev = device_info()
    log(f"device platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    if dev["platform"] != "tpu":
        log(f"FAIL: found platform {dev['platform']!r}, need 'tpu' "
            "(no CPU fallback)")
        return 2

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from repro.compile_cache import enable_compile_cache
    from repro.configs import get_config

    log(f"compile cache {enable_compile_cache()}")
    clock = CompileClock()
    cfg = get_config(ARCH)
    bf16 = jnp.bfloat16
    t_all = time.perf_counter()

    t0, c0 = time.perf_counter(), clock.seconds
    programs = lower_engine_steps(cfg, bf16,
                                  SingleDeviceSharding(jax.devices()[0]))
    for name, text in programs.items():
        if "tpu_custom_call" not in text:
            log(f"FAIL: {name} at {ARCH} full width holds no "
                "tpu_custom_call (the Pallas kernel was not taken)")
            return 1
        log(f"lower {name}: tpu_custom_call present")
    log(f"lower phase {time.perf_counter() - t0:.3f}s "
        f"(compile {clock.seconds - c0:.3f}s)")

    t0, c0 = time.perf_counter(), clock.seconds
    if not within("kernel", kernel_errors(), BF16_TOL):
        return 1
    log(f"kernel phase {time.perf_counter() - t0:.3f}s "
        f"(compile {clock.seconds - c0:.3f}s)")

    t0, c0 = time.perf_counter(), clock.seconds
    out, wl = serve()
    n_wl = len(wl)
    log(f"serve: requests={out['requests']}/{n_wl} "
        f"off_script={out['off_script']} tokens="
        f"{sum(min(r.gen_length, MAX_GEN) for r in wl)} "
        f"steps={out['steps']} prefill_dispatches="
        f"{out['prefill_dispatches']} prefix_hits={out['prefix_hits']} "
        f"peak_concurrency={out['peak_concurrency']} "
        f"evictions={out['evictions']}")
    if out["requests"] != n_wl or out["off_script"] or n_wl == 0:
        log("FAIL: not every request got its scripted token count")
        return 1
    log(f"serve phase {time.perf_counter() - t0:.3f}s "
        f"(compile {clock.seconds - c0:.3f}s)")

    t0, c0 = time.perf_counter(), clock.seconds
    if not within("logits f32 engine",
                  logits_error(cfg, jnp.float32, precision="highest"),
                  F32_LOGITS_RTOL):
        return 1
    if not within("logits bf16 engine", logits_error(cfg, bf16),
                  BF16_LOGITS_RTOL):
        return 1
    log(f"logits phase {time.perf_counter() - t0:.3f}s "
        f"(compile {clock.seconds - c0:.3f}s)")

    log(f"total {time.perf_counter() - t_all:.3f}s, compile "
        f"{clock.seconds:.3f}s over {clock.count} programs "
        f"({clock.cache_hits} from the persistent cache)")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
