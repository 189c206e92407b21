"""Test bootstrap: make the suite collect on a bare container.

The suite uses ``hypothesis`` for lightweight property tests.  CI installs it
from ``requirements-dev.txt``; on a bare container (no network, no wheel) we
fall back to a tiny deterministic shim that covers exactly the API surface
the tests use — ``@given`` with keyword strategies, ``@settings``, and the
``integers`` / ``sampled_from`` / ``floats`` / ``booleans`` strategies.

The shim is *not* hypothesis: no shrinking, no database, no adaptive search.
It draws ``max_examples`` deterministic samples (boundary values first, then
a seeded PRNG keyed on the test name) so failures are reproducible run-to-run.
"""
from __future__ import annotations

import itertools
import random
import sys
import types


def _install_hypothesis_shim() -> None:
    class _Strategy:
        def __init__(self, draw, boundary=()):
            self._draw = draw
            self._boundary = tuple(boundary)

        def example_at(self, rng: random.Random, i: int):
            if i < len(self._boundary):
                return self._boundary[i]
            return self._draw(rng)

    def integers(min_value: int, max_value: int) -> _Strategy:
        return _Strategy(
            lambda rng: rng.randint(min_value, max_value),
            boundary=(min_value, max_value),
        )

    def sampled_from(elements) -> _Strategy:
        elements = list(elements)
        return _Strategy(lambda rng: rng.choice(elements), boundary=elements[:2])

    def floats(min_value: float, max_value: float, **_kw) -> _Strategy:
        return _Strategy(
            lambda rng: rng.uniform(min_value, max_value),
            boundary=(min_value, max_value),
        )

    def booleans() -> _Strategy:
        return _Strategy(lambda rng: rng.random() < 0.5, boundary=(False, True))

    def just(value) -> _Strategy:
        return _Strategy(lambda rng: value, boundary=(value,))

    class settings:  # noqa: N801 - mirrors hypothesis' lowercase class
        def __init__(self, max_examples: int = 10, **_kw):
            self.max_examples = max_examples

        def __call__(self, fn):
            fn._shim_settings = self
            return fn

    def given(**strategies):
        def deco(fn):
            def runner(*args, **kwargs):
                cfg = getattr(fn, "_shim_settings", None) or getattr(
                    runner, "_shim_settings", None
                )
                n = cfg.max_examples if cfg else 10
                rng = random.Random(fn.__qualname__)
                for i in range(n):
                    drawn = {k: s.example_at(rng, i) for k, s in strategies.items()}
                    try:
                        fn(*args, **dict(kwargs, **drawn))
                    except Exception as e:  # re-raise with the failing example
                        raise AssertionError(
                            f"{fn.__qualname__} failed on shim example {drawn!r}"
                        ) from e

            runner.__name__ = fn.__name__
            runner.__qualname__ = fn.__qualname__
            runner.__doc__ = fn.__doc__
            runner.__module__ = fn.__module__
            runner.hypothesis_shim = True
            return runner

        return deco

    mod = types.ModuleType("hypothesis")
    mod.given = given
    mod.settings = settings
    mod.HealthCheck = types.SimpleNamespace(all=lambda: [])
    st_mod = types.ModuleType("hypothesis.strategies")
    for name, obj in [
        ("integers", integers),
        ("sampled_from", sampled_from),
        ("floats", floats),
        ("booleans", booleans),
        ("just", just),
    ]:
        setattr(st_mod, name, obj)
    mod.strategies = st_mod
    sys.modules["hypothesis"] = mod
    sys.modules["hypothesis.strategies"] = st_mod


try:  # pragma: no cover - exercised implicitly by collection
    import hypothesis  # noqa: F401
except ModuleNotFoundError:  # bare container: install the deterministic shim
    _install_hypothesis_shim()

