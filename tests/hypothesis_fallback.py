"""Degraded stand-in for the optional ``hypothesis`` dependency.

The property tests only use ``@given`` with ``st.integers`` /
``st.sampled_from`` strategies, and ``@example`` for explicit cases.
When hypothesis is not installed, this module provides the same
decorator surface but materializes a fixed, seeded set of example cases
instead of doing adaptive search — the properties are still exercised
(including range endpoints), just without shrinking or example
databases.

Usage in a test module::

    try:
        from hypothesis import given, settings
        from hypothesis import strategies as st
    except ImportError:                      # optional dep
        from hypothesis_fallback import given, settings, st
"""
from __future__ import annotations

import functools
import inspect

import numpy as np

DEFAULT_MAX_EXAMPLES = 10


class _Strategy:
    """A deterministic example generator: endpoints first, then seeded
    draws — mirrors hypothesis's bias toward boundary values."""

    def __init__(self, endpoints, draw):
        self.endpoints = list(endpoints)
        self.draw = draw

    def examples(self, rng, k):
        out = list(self.endpoints[:k])
        while len(out) < k:
            out.append(self.draw(rng))
        return out


class _Strategies:
    @staticmethod
    def integers(min_value, max_value):
        return _Strategy(
            [min_value, max_value],
            lambda rng: int(rng.randint(min_value, max_value + 1)))

    @staticmethod
    def sampled_from(elements):
        elements = list(elements)
        return _Strategy(
            elements[:1],
            lambda rng: elements[rng.randint(len(elements))])

    @staticmethod
    def floats(min_value, max_value):
        return _Strategy(
            [min_value, max_value],
            lambda rng: float(rng.uniform(min_value, max_value)))


st = _Strategies()


def settings(max_examples=DEFAULT_MAX_EXAMPLES, **_ignored):
    """Records max_examples for ``given``; other kwargs (deadline, ...)
    are meaningless without real hypothesis and ignored."""
    def deco(fn):
        fn._fallback_max_examples = max_examples
        return fn
    return deco


def example(**kwargs):
    """Records one explicit case for ``given``, run before the drawn ones."""
    def deco(fn):
        fn._fallback_examples = [kwargs] + getattr(fn, "_fallback_examples",
                                                   [])
        return fn
    return deco


def given(**strategies):
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            # read at call time: @settings may be applied above @given and
            # would then set the attribute after this decorator runs
            n = getattr(wrapper, "_fallback_max_examples",
                        getattr(fn, "_fallback_max_examples",
                                DEFAULT_MAX_EXAMPLES))
            rng = np.random.RandomState(0)
            columns = {name: s.examples(rng, n)
                       for name, s in strategies.items()}
            for case in getattr(fn, "_fallback_examples", []):
                fn(*args, **kwargs, **case)
            for i in range(n):
                fn(*args, **kwargs, **{k: v[i] for k, v in columns.items()})

        # hide the strategy params from pytest's fixture resolution
        sig = inspect.signature(fn)
        wrapper.__signature__ = sig.replace(parameters=[
            p for name, p in sig.parameters.items() if name not in strategies])
        del wrapper.__wrapped__
        return wrapper
    return deco
