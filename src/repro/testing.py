"""Deterministic fallback for ``hypothesis`` on bare environments.

Tier-1 must collect and run with only jax/numpy/pytest installed
(ROADMAP "Tier-1 verify" on a fresh container), but the property tests
are written against hypothesis's ``@given``/``strategies`` API.  When
hypothesis is importable the tests use it unchanged; when it is not,
this module provides a seeded, minimal re-implementation of the subset
the suite uses (``integers``, ``floats``, ``booleans``, ``lists``,
``tuples``, ``sampled_from``) so the properties still execute on random
inputs — without shrinking, the database, or deadline handling.
Explicit ``@example`` cases run first, as with hypothesis.

Usage (in test modules)::

    try:
        from hypothesis import given, settings
        from hypothesis import strategies as st
    except ImportError:
        from repro.testing import given, settings
        from repro.testing import strategies as st
"""
from __future__ import annotations

import contextlib
import functools
import random
import types
from typing import Any, Callable

_DEFAULT_EXAMPLES = 25
_SEED = 0


@contextlib.contextmanager
def count_compiles():
    """Count XLA backend compiles inside the block via ``jax.monitoring``
    (the recompile-audit tier; ISSUE 2).  Yields a dict whose ``"n"`` is
    incremented once per ``backend_compile`` — cache hits don't fire.
    Unregisters exactly its own callback on exit (falling back to
    ``clear_event_listeners`` only if the private unregister API is
    gone), so nesting and other listeners survive."""
    from jax import monitoring
    from jax._src import monitoring as monitoring_impl

    counts = {"n": 0}

    def _on_event(name, *args, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            counts["n"] += 1

    monitoring.register_event_duration_secs_listener(_on_event)
    try:
        yield counts
    finally:
        unregister = getattr(
            monitoring_impl,
            "_unregister_event_duration_listener_by_callback", None)
        if unregister is not None:
            unregister(_on_event)
        else:                                   # pragma: no cover
            monitoring.clear_event_listeners()


class Strategy:
    """A draw function rng -> value (the whole hypothesis API we need)."""

    def __init__(self, draw: Callable[[random.Random], Any]):
        self.draw = draw

    def map(self, f: Callable[[Any], Any]) -> "Strategy":
        return Strategy(lambda rng: f(self.draw(rng)))

    def filter(self, pred: Callable[[Any], bool],
               max_tries: int = 100) -> "Strategy":
        def draw(rng: random.Random):
            for _ in range(max_tries):
                v = self.draw(rng)
                if pred(v):
                    return v
            raise ValueError("filter predicate never satisfied")
        return Strategy(draw)


def integers(min_value: int = -2 ** 31, max_value: int = 2 ** 31) -> Strategy:
    return Strategy(lambda rng: rng.randint(min_value, max_value))


def floats(min_value: float = 0.0, max_value: float = 1.0,
           **_ignored) -> Strategy:
    return Strategy(lambda rng: rng.uniform(min_value, max_value))


def booleans() -> Strategy:
    return Strategy(lambda rng: bool(rng.getrandbits(1)))


def sampled_from(seq) -> Strategy:
    seq = list(seq)
    return Strategy(lambda rng: seq[rng.randrange(len(seq))])


def tuples(*strategies: Strategy) -> Strategy:
    return Strategy(lambda rng: tuple(s.draw(rng) for s in strategies))


def lists(elements: Strategy, min_size: int = 0,
          max_size: int = 10) -> Strategy:
    def draw(rng: random.Random):
        n = rng.randint(min_size, max_size)
        return [elements.draw(rng) for _ in range(n)]
    return Strategy(draw)


def given(*arg_strategies: Strategy, **kw_strategies: Strategy):
    """Run the test once per generated example (seeded, reproducible)."""
    def deco(fn):
        def run(*args, **kwargs):
            n = getattr(run, "_max_examples",
                        getattr(fn, "_max_examples", _DEFAULT_EXAMPLES))
            for ex_args, ex_kwargs in getattr(fn, "_examples", ()):
                fn(*args, *ex_args, **kwargs, **ex_kwargs)
            rng = random.Random(_SEED)
            for _ in range(n):
                drawn = [s.draw(rng) for s in arg_strategies]
                kdrawn = {k: s.draw(rng) for k, s in kw_strategies.items()}
                fn(*args, *drawn, **kwargs, **kdrawn)
        # NOT functools.wraps: copying __wrapped__ would make pytest
        # introspect fn's signature and demand the drawn args as fixtures
        run.__name__ = fn.__name__
        run.__doc__ = fn.__doc__
        run.__module__ = fn.__module__
        run.hypothesis_shim = True
        return run
    return deco


def example(*args, **kwargs):
    """Record an explicit example, run before the drawn ones (apply it
    below ``@given``, as with hypothesis)."""
    def deco(fn):
        fn._examples = [(args, kwargs)] + list(getattr(fn, "_examples", ()))
        return fn
    return deco


def settings(max_examples: int = _DEFAULT_EXAMPLES, **_ignored):
    """Record max_examples on the (possibly already-wrapped) test."""
    def deco(fn):
        fn._max_examples = max_examples
        return fn
    return deco


# ``from repro.testing import strategies as st`` mirror of the real layout
strategies = types.SimpleNamespace(
    integers=integers, floats=floats, booleans=booleans,
    sampled_from=sampled_from, tuples=tuples, lists=lists,
    Strategy=Strategy)
st = strategies
