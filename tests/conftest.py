import os
import sys

import pytest

# Make `import repro` work regardless of how pytest is invoked.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


# ---------------------------------------------------------------------------
# hypothesis fallback: property tests skip individually when hypothesis is
# not installed (tier-1 containers), while every plain test in the same
# module still runs. Test modules use:
#
#     try:
#         from hypothesis import example, given, settings, strategies as st
#     except ImportError:
#         from conftest import example, given, settings, st
# ---------------------------------------------------------------------------


class _SkipStrategies:
    """Stand-in for ``hypothesis.strategies``: any strategy constructor
    returns None (only ever passed to the stub ``given`` below)."""

    def __getattr__(self, name):
        return lambda *a, **k: None


st = _SkipStrategies()


def settings(*_a, **_k):
    return lambda f: f


example = settings


def given(*_a, **_k):
    def deco(f):
        # zero-arg replacement: no fixture resolution, just a clean skip
        def skipper():
            pytest.skip("hypothesis not installed")

        skipper.__name__ = f.__name__
        skipper.__doc__ = f.__doc__
        return skipper

    return deco
