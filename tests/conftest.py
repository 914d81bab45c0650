"""Test-suite plumbing: a deterministic fallback `hypothesis` shim, and
jax's persistent compilation cache put back after every test.

The container image may lack the real `hypothesis` package and nothing can be
pip-installed, so when the import fails we register a minimal stand-in that
covers exactly the API surface these tests use (`given`, `settings`,
`strategies.integers`). Property tests then run a fixed number of
deterministically-seeded examples — no shrinking, but the same oracles are
exercised. With real hypothesis installed the shim is inert.
"""
from __future__ import annotations

import functools
import inspect
import sys
import types
import zlib

import pytest

_CACHE_OPTIONS = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
                  "jax_persistent_cache_min_entry_size_bytes",
                  "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture(autouse=True)
def _restore_compilation_cache():
    """The CLI entry points turn the persistent compilation cache on for the
    whole process (`repro.runtime.compile_cache.configure`). Restore jax's
    cache options after each test, so later tests in the same worker do not
    write every compile to disk."""
    import jax

    saved = {k: getattr(jax.config, k) for k in _CACHE_OPTIONS}
    yield
    if any(getattr(jax.config, k) != v for k, v in saved.items()):
        from jax.experimental.compilation_cache import compilation_cache as cc

        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()


def _install_hypothesis_stub() -> None:
    import numpy as np

    class _Strategy:
        def __init__(self, sample):
            self.sample = sample

    def integers(min_value, max_value):
        return _Strategy(
            lambda rng: int(rng.integers(min_value, max_value + 1)))

    def floats(min_value=0.0, max_value=1.0, **_):
        return _Strategy(
            lambda rng: float(rng.uniform(min_value, max_value)))

    def booleans():
        return _Strategy(lambda rng: bool(rng.integers(0, 2)))

    def sampled_from(options):
        seq = list(options)
        return _Strategy(lambda rng: seq[int(rng.integers(0, len(seq)))])

    _DEFAULT_EXAMPLES = 10

    def given(*arg_strategies, **kw_strategies):
        def decorate(fn):
            sig = inspect.signature(fn)
            params = list(sig.parameters)
            # hypothesis semantics: positional strategies fill the RIGHTMOST
            # parameters (fixtures stay on the left).
            pos_names = params[len(params) - len(arg_strategies):]
            bound = dict(zip(pos_names, arg_strategies))
            bound.update(kw_strategies)

            @functools.wraps(fn)
            def runner(*args, **kwargs):
                n = getattr(runner, "_stub_max_examples", _DEFAULT_EXAMPLES)
                seed = zlib.crc32(fn.__qualname__.encode())
                rng = np.random.default_rng(seed)
                for _ in range(n):
                    drawn = {k: s.sample(rng) for k, s in bound.items()}
                    fn(*args, **kwargs, **drawn)

            # hide strategy-bound params from pytest's fixture resolution
            runner.__signature__ = sig.replace(parameters=[
                p for name, p in sig.parameters.items() if name not in bound
            ])
            return runner

        return decorate

    def settings(**kw):
        def decorate(fn):
            fn._stub_max_examples = kw.get("max_examples", _DEFAULT_EXAMPLES)
            return fn

        return decorate

    hyp = types.ModuleType("hypothesis")
    strat = types.ModuleType("hypothesis.strategies")
    strat.integers = integers
    strat.floats = floats
    strat.booleans = booleans
    strat.sampled_from = sampled_from
    hyp.given = given
    hyp.settings = settings
    hyp.strategies = strat
    hyp.__is_repro_stub__ = True
    sys.modules["hypothesis"] = hyp
    sys.modules["hypothesis.strategies"] = strat


try:  # pragma: no cover - depends on the environment
    import hypothesis  # noqa: F401
except ImportError:
    _install_hypothesis_stub()
