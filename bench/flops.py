"""Operations and bytes the serving work needs, from its shapes.

Counts are of useful work only: active rows, real context lengths, the
real vocabulary, one logits row per admitted prompt.  Padding, idle slots
and recomputation are not counted, so a share of the peak computed from
them cannot pass 100% unless the time leaves part of the work out.

The counts are the configuration's architecture's (``arch.py``): sizes
``m`` are the configuration file; ``b`` is the bytes of one stored
element (2 for bf16).
"""
from __future__ import annotations

from typing import Iterable, Tuple

import arch


def decode_window(m: dict, k: int, rows: int, ctx: int, b: int = 2) -> dict:
    """A fused decode window of ``k`` steps over ``rows`` active requests
    whose contexts sum to ``ctx`` tokens when the window starts.

    ``attn_flops``, ``attn_bytes``: the paged decode attention kernel (all
    layers); ``model_flops``: the whole step; ``tokens``: tokens made."""
    return arch.of(m).decode_window(m, k, rows, ctx, b)


def prefill_wave(m: dict, rows: Iterable[Tuple[int, int]],
                 b: int = 2) -> dict:
    """An admission wave; ``rows`` are (suffix tokens run, cached prefix
    tokens).  ``attn_*``: the prefix-prefill attention kernel (all
    layers); ``model_flops``: the wave's useful work; ``tokens``: suffix
    tokens run."""
    return arch.of(m).prefill_wave(m, rows, b)


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """Least time on the chip: the larger of compute and memory bounds."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
