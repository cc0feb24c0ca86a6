"""Prefix cache: share of the prompt tokens admitted in the window that
the radix cache served from shared pages instead of a prefill (%)."""
import readers as R


def read(ctx, name):
    rows = [r for t, rows in ctx["probe"].waves if R.in_window(ctx, t)
            for r in rows]
    total = sum(s + p for s, p in rows)
    return 100.0 * sum(p for _, p in rows) / total if total else None
