"""Test session config. NOTE: no XLA_FLAGS here by design — unit/smoke
tests run on the single real CPU device; multi-device scenarios re-exec
themselves in a subprocess (tests/multidev_scenario.py).

``hypothesis`` is optional: when it is not installed (bare interpreter,
minimal CI images) we install a deterministic stand-in into ``sys.modules``
*before* test modules import it. The stand-in replays each ``@given`` test
over a small fixed grid of strategy samples — weaker than real
property-based search, but it keeps the full tier-1 suite collecting and
exercising the same assertions everywhere.
"""

import sys
import types

try:  # pragma: no cover - trivially absent on bare interpreters
    import hypothesis  # noqa: F401
except ImportError:
    class _Strategy:
        """A fixed, deterministic sample list standing in for a strategy."""

        def __init__(self, samples):
            self.samples = list(samples)

    def _integers(lo, hi):
        span = hi - lo
        return _Strategy([lo, hi, lo + span // 2, lo + span // 3, lo + (2 * span) // 3])

    def _floats(lo, hi):
        span = hi - lo
        return _Strategy([lo, hi, lo + 0.5 * span, lo + 0.25 * span, lo + 0.75 * span])

    def _sampled_from(seq):
        return _Strategy(seq)

    def _given(**strategies):
        names = list(strategies)
        n = max(len(s.samples) for s in strategies.values())

        def deco(fn):
            # NB: no functools.wraps — pytest must see a zero-arg signature,
            # not the original one (whose params would be mistaken for fixtures).
            def wrapper():
                for i in range(n):
                    drawn = {k: strategies[k].samples[i % len(strategies[k].samples)] for k in names}
                    fn(**drawn)

            wrapper.__name__ = fn.__name__
            wrapper.__doc__ = fn.__doc__
            wrapper.__module__ = fn.__module__
            wrapper.hypothesis_stub = True
            return wrapper

        return deco

    def _settings(**_kwargs):
        return lambda fn: fn

    _hyp = types.ModuleType("hypothesis")
    _st = types.ModuleType("hypothesis.strategies")
    _st.integers = _integers
    _st.floats = _floats
    _st.sampled_from = _sampled_from
    _hyp.given = _given
    _hyp.settings = _settings
    _hyp.strategies = _st
    _hyp.__stub__ = True
    sys.modules["hypothesis"] = _hyp
    sys.modules["hypothesis.strategies"] = _st

import jax

jax.config.update("jax_enable_x64", False)
