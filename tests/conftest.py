"""Shared test configuration.

Registers a deterministic fallback implementation of the small
``hypothesis`` API surface these tests use when the real package is not
installed (see requirements-dev.txt).  The fallback draws a fixed,
per-test pseudo-random sample set — no shrinking, no database — which is
enough to keep the property tests meaningful in minimal containers
instead of failing at collection with ModuleNotFoundError.

It also pins JAX to the CPU unless the caller chose a platform: the
suite runs Pallas kernels in interpret mode on emulated devices, and a
CPU-pinned process keeps no persistent compile cache in the checkout.
"""
from __future__ import annotations

import functools
import inspect
import os
import sys
import types
import zlib

import numpy as np

os.environ.setdefault("JAX_PLATFORMS", "cpu")

try:  # real hypothesis wins whenever it is available
    import hypothesis  # noqa: F401
except ModuleNotFoundError:
    class _UnsatisfiedAssumption(Exception):
        pass

    class _Strategy:
        def __init__(self, draw_fn):
            self._draw_fn = draw_fn

        def _draw(self, rng):
            return self._draw_fn(rng)

    def _integers(min_value, max_value):
        return _Strategy(lambda rng: int(rng.integers(min_value,
                                                      max_value + 1)))

    def _booleans():
        return _Strategy(lambda rng: bool(rng.integers(0, 2)))

    def _floats(min_value, max_value):
        return _Strategy(lambda rng: float(rng.uniform(min_value,
                                                       max_value)))

    def _sampled_from(elements):
        seq = list(elements)
        return _Strategy(lambda rng: seq[int(rng.integers(0, len(seq)))])

    def _lists(elements, min_size=0, max_size=None):
        hi = max_size if max_size is not None else min_size + 5

        def draw(rng):
            size = int(rng.integers(min_size, hi + 1))
            return [elements._draw(rng) for _ in range(size)]
        return _Strategy(draw)

    def _composite(fn):
        def builder(*args, **kwargs):
            def draw_sample(rng):
                return fn(lambda s: s._draw(rng), *args, **kwargs)
            return _Strategy(draw_sample)
        return builder

    def _settings(**kwargs):
        def deco(fn):
            fn._fallback_settings = dict(kwargs)
            return fn
        return deco

    def _assume(condition):
        if not condition:
            raise _UnsatisfiedAssumption()
        return True

    def _given(*strategies):
        def deco(fn):
            n_examples = getattr(fn, "_fallback_settings",
                                 {}).get("max_examples", 20)
            seed0 = zlib.crc32(fn.__qualname__.encode("utf-8"))

            @functools.wraps(fn)
            def wrapper(*args, **kwargs):
                for i in range(n_examples):
                    rng = np.random.default_rng((seed0 + i) % 2**32)
                    values = [s._draw(rng) for s in strategies]
                    try:
                        fn(*args, *values, **kwargs)
                    except _UnsatisfiedAssumption:
                        continue
                    except Exception as e:
                        raise AssertionError(
                            f"falsifying example (hypothesis fallback, "
                            f"draw {i}): {values!r}") from e

            # hide the strategy parameters from pytest's fixture resolution
            del wrapper.__wrapped__
            wrapper.__signature__ = inspect.Signature()
            return wrapper
        return deco

    _st = types.ModuleType("hypothesis.strategies")
    _st.integers = _integers
    _st.booleans = _booleans
    _st.floats = _floats
    _st.sampled_from = _sampled_from
    _st.lists = _lists
    _st.composite = _composite

    _hyp = types.ModuleType("hypothesis")
    _hyp.given = _given
    _hyp.settings = _settings
    _hyp.assume = _assume
    _hyp.strategies = _st
    _hyp.__fallback__ = True

    sys.modules["hypothesis"] = _hyp
    sys.modules["hypothesis.strategies"] = _st
