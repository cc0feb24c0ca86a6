#!/usr/bin/env python3
"""Time the paged decode attention kernel alone on a TPU, at smollm-135m
widths, over slot counts, with context lengths drawn from the LMaaS mix.

    PYTHONPATH=src python benchmarks/paged_decode_sweep.py \
        [--slots 16 64 128] [--seed 0] [--profile]

Each slot holds one request of the mix (``bench/traffic/lmaas-steady``):
its prompt plus a uniform share of its generation, the context a decode
step sees.  Pages are scattered over a pool of ``slots x max_blocks + 1``
blocks (block 0 is the null block that pads every table).  One jitted
call runs the kernel 30 times in a ``fori_loop``, as the layer scan
does, and the time a call is its wall time over 30, after a warm-up.
Each line of output is one JSON object: the per-call time, the live
pages, the KV bytes of the real lengths over 819 GB/s as a share of
that time (the kernel's roofline share), and the largest error against
a float32 oracle; with ``--profile``, also the device time a call of the
Pallas kernels and of the other ops of the loop body, and the costliest
ops, from a profiler trace of one more run.  Refuses to run without a
TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "bench", "traffic"))

HQ, HKV, D, BT = 9, 3, 64, 16              # smollm-135m, the cells' pages
MAX_LEN, MAX_GEN = 512, 1024
LAYERS = 30
HBM_BYTES_PER_S = 819e9                    # TPU v5e (bench/peaks.json)


def contexts(slots: int, seed: int) -> list:
    import numpy as np
    import generator
    mix = generator.load_mix("lmaas-steady")
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(slots):
        s = generator._draw_spec(mix, rng, MAX_GEN)
        instr = len(mix["tasks"][s.task]["instruction"].split())
        prompt = min(1 + instr + s.uil, MAX_LEN)
        out.append(prompt + int(rng.integers(0, s.gen)) + 1)
    return out


def device_split(fn, arg) -> dict:
    """Device time of one run of ``fn(arg)``, a call of ``LAYERS``
    kernel calls: the kernel's ops and every other leaf op, per call."""
    import glob
    import tempfile
    import jax
    from jax.profiler import ProfileData
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            fn(arg).block_until_ready()
        path = sorted(glob.glob(os.path.join(d, "plugins", "profile", "*",
                                             "*.xplane.pb")))[-1]
        pd = ProfileData.from_file(path)
        ops = []
        for plane in pd.planes:
            if plane.name == "/device:TPU:0":
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        ops += [(float(e.start_ns), float(e.duration_ns),
                                 e.name) for e in line.events]
    ops.sort()
    kern = other = 0.0
    by_op = {}
    for j, (t, dur, name) in enumerate(ops):
        if j + 1 < len(ops) and ops[j + 1][0] < t + dur:
            continue                    # holds other ops: a loop's event
        if "tpu_custom_call" in name:
            kern += dur
        else:
            other += dur
        label = name.split(" = ")[0].strip()
        by_op[label] = by_op.get(label, 0.0) + dur
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:6]
    return {"kernel_us_per_call": kern / 1e3 / LAYERS,
            "other_us_per_call": other / 1e3 / LAYERS,
            "top_ops_us_per_call": [(k, v / 1e3 / LAYERS) for k, v in top]}


def run(slots: int, seed: int, profile: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.decode_attention import kernel as K
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref
    mb = -(-(MAX_LEN + MAX_GEN) // BT)
    nb = slots * mb + 1
    rng = np.random.default_rng(seed)
    lens = contexts(slots, seed)
    tables = np.zeros((slots, mb), np.int32)
    ids = rng.permutation(np.arange(1, nb))
    used = 0
    for i, n in enumerate(lens):
        pages = -(-n // BT)
        tables[i, :pages] = ids[used:used + pages]
        used += pages

    def normal(shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    q = normal((slots, HQ, D))
    kp, vp = normal((nb, HKV, BT, D)), normal((nb, HKV, BT, D))
    tables, lengths = jnp.asarray(tables), jnp.asarray(lens, jnp.int32)
    kern = K.paged_decode_attention_kernel

    @jax.jit
    def layers(q):
        return jax.lax.fori_loop(
            0, LAYERS, lambda i, x: kern(x, kp, vp, tables, lengths), q)

    layers(q).block_until_ready()
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        layers(q).block_until_ready()
        times.append(time.perf_counter() - t0)
    per_call = sorted(times)[len(times) // 2] / LAYERS
    out = jax.jit(kern)(q, kp, vp, tables, lengths)
    f32 = [a.astype(jnp.float32) for a in (q, kp, vp)]
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(paged_decode_attention_ref)(*f32, tables, lengths)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
    split = device_split(layers, q) if profile else {}
    kv_bytes = 2 * HKV * D * 2 * sum(lens) + 2 * slots * HQ * D * 2
    return {"slots": slots,
            "pages_per_step": (K.decode_pages_per_step(HKV * BT * D * 2, mb)
                               if hasattr(K, "decode_pages_per_step")
                               else None),
            "live_pages": int(sum(-(-n // BT) for n in lens)),
            "mean_context": sum(lens) / slots,
            "us_per_call": per_call * 1e6,
            "roofline_pct": 100.0 * kv_bytes / HBM_BYTES_PER_S / per_call,
            "max_err": err, **split}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--slots", type=int, nargs="+", default=[16, 64, 128])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--profile", action="store_true")
    args = ap.parse_args()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1
    for slots in args.slots:
        rec = run(slots, args.seed, args.profile)
        rec["device"] = dev.device_kind
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
