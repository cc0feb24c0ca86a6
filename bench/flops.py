"""Operations and bytes the serving work needs, from its shapes.

Counts are of useful work only: active rows, real context lengths, the
real vocabulary, one logits row per admitted prompt.  Padding, idle slots
and recomputation are not counted, so a share of the peak computed from
them cannot pass 100% unless the time leaves part of the work out.

Sizes ``m`` use the configuration file's keys; ``b`` is the bytes of one
stored element (2 for bf16).
"""
from __future__ import annotations

from typing import Iterable, Tuple


def _dims(m: dict):
    return (m["num_hidden_layers"], m["hidden_size"],
            m["num_attention_heads"], m["num_key_value_heads"],
            m["head_dim"], m["intermediate_size"], m["vocab_size"])


def layer_matmul_params(m: dict) -> int:
    """Weights one token multiplies in one layer (projections and MLP)."""
    _, d, hq, hkv, hd, ff, _ = _dims(m)
    return d * (hq + 2 * hkv) * hd + hq * hd * d + 3 * d * ff


def decode_window(m: dict, k: int, rows: int, ctx: int, b: int = 2) -> dict:
    """A fused decode window of ``k`` steps over ``rows`` active requests
    whose contexts sum to ``ctx`` tokens when the window starts.  At step
    ``i`` each row attends over its context plus ``i + 1`` tokens.

    ``attn_*``: the paged decode attention kernel (all layers);
    ``model_flops``: the whole step (projections, MLP, attention, head)."""
    L, d, hq, hkv, hd, _, v = _dims(m)
    span = k * ctx + rows * k * (k + 1) // 2     # sum of attended lengths
    attn_flops = 4 * hq * hd * L * span
    attn_bytes = L * (2 * hkv * hd * b * span + k * rows * 2 * hq * hd * b)
    tokens = k * rows
    model_flops = tokens * 2 * (L * layer_matmul_params(m) + d * v) \
        + attn_flops
    return {"attn_flops": attn_flops, "attn_bytes": attn_bytes,
            "model_flops": model_flops, "tokens": tokens}


def prefill_wave(m: dict, rows: Iterable[Tuple[int, int]],
                 b: int = 2) -> dict:
    """An admission wave; ``rows`` are (suffix tokens run, cached prefix
    tokens).  Suffix queries attend causally among themselves and to the
    whole cached prefix, which the kernel reads from the page pool.

    ``attn_*``: the prefix-prefill attention kernel (all layers);
    ``model_flops``: the wave's useful work, with one logits row a row."""
    L, d, hq, hkv, hd, _, v = _dims(m)
    attn_flops = attn_bytes = model_flops = tokens = 0
    for s, p in rows:
        f = 2 * hq * hd * L * s * (2 * p + s + 1)
        attn_flops += f
        attn_bytes += L * b * hd * (2 * hkv * p + s * (2 * hq + 2 * hkv))
        model_flops += 2 * s * L * layer_matmul_params(m) + 2 * d * v + f
        tokens += s
    return {"attn_flops": attn_flops, "attn_bytes": attn_bytes,
            "model_flops": model_flops, "tokens": tokens}


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """Least time on the chip: the larger of compute and memory bounds."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
