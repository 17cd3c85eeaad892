# NOTE: no XLA_FLAGS here on purpose — smoke tests and benches must see the
# single real CPU device; only dryrun.py forces 512 placeholder devices.
import functools
import sys
import types

import numpy as np
import pytest

# ---------------------------------------------------------------------------
# hypothesis fallback shim: this container cannot pip-install hypothesis, and
# without it 5 of 10 test modules die at import. When the real package is
# absent we register a minimal stand-in that degrades @given property tests
# to a fixed-seed multi-example run, so the real assertions still execute.
# Only the strategy surface these tests use is implemented (integers, floats,
# lists, tuples, sampled_from, booleans).
# ---------------------------------------------------------------------------
try:
    import hypothesis  # noqa: F401
except ImportError:
    _N_EXAMPLES = 5          # fixed-seed examples per property test

    class _Strategy:
        def __init__(self, draw):
            self._draw = draw

        def draw(self, rng):
            return self._draw(rng)

    def _integers(min_value, max_value):
        return _Strategy(
            lambda rng: int(rng.integers(min_value, max_value + 1)))

    def _floats(min_value, max_value, **_kw):
        return _Strategy(lambda rng: float(rng.uniform(min_value, max_value)))

    def _booleans():
        return _Strategy(lambda rng: bool(rng.integers(0, 2)))

    def _sampled_from(seq):
        seq = list(seq)
        return _Strategy(lambda rng: seq[int(rng.integers(0, len(seq)))])

    def _lists(elem, min_size=0, max_size=10, unique=False, **_kw):
        def draw(rng):
            n = int(rng.integers(min_size, max_size + 1))
            out = []
            while len(out) < n:
                x = elem.draw(rng)
                if not (unique and x in out):
                    out.append(x)
            return out
        return _Strategy(draw)

    def _tuples(*elems):
        return _Strategy(lambda rng: tuple(e.draw(rng) for e in elems))

    def _settings(**kw):
        def deco(fn):
            fn._stub_settings = kw
            return fn
        return deco

    def _given(*strategies):
        def deco(fn):
            max_examples = getattr(fn, "_stub_settings",
                                   {}).get("max_examples", _N_EXAMPLES)
            n_examples = min(int(max_examples), _N_EXAMPLES)

            @functools.wraps(fn)
            def wrapper(*args, **kwargs):
                rng = np.random.default_rng(0)
                for _ in range(n_examples):
                    drawn = [s.draw(rng) for s in strategies]
                    fn(*args, *drawn, **kwargs)

            # pytest follows __wrapped__ to introspect fixture params; the
            # drawn params must not look like fixtures
            del wrapper.__wrapped__
            return wrapper
        return deco

    _st = types.ModuleType("hypothesis.strategies")
    _st.integers = _integers
    _st.floats = _floats
    _st.booleans = _booleans
    _st.sampled_from = _sampled_from
    _st.lists = _lists
    _st.tuples = _tuples

    _hyp = types.ModuleType("hypothesis")
    _hyp.given = _given
    _hyp.settings = _settings
    _hyp.strategies = _st
    _hyp.__stub__ = True

    sys.modules["hypothesis"] = _hyp
    sys.modules["hypothesis.strategies"] = _st


@pytest.fixture
def rng():
    return np.random.default_rng(0)
