"""Shared test infrastructure.

Two jobs:

* **Hypothesis fallback** — property tests (`tests/test_manager.py`,
  `test_scaling.py`, `test_maximal_rectangles.py`, ...) are written against
  the real ``hypothesis`` API.  On containers without it, a minimal
  deterministic shim is installed into ``sys.modules`` *before* collection:
  each ``@given`` test runs ``max_examples`` seeded-random draws.  The shim
  covers only the strategy surface this repo uses (integers, floats,
  booleans, sampled_from, lists, tuples, composite); it does no shrinking,
  but failures reproduce exactly because every draw is seeded from the test
  name and example index.
* **Tiny model fixtures** — deterministic, CPU-cheap model configs
  (vocab 64, d_model 32) used by tier-1 serving/engine tests so one jit
  compile costs milliseconds, not minutes.
* **One seed to replay them all** — ``--repro-seed N`` (default 0) feeds
  every random source the suite owns: the shim's per-example draws, the
  real hypothesis profile (registered derandomized, so failures replay
  without a database), and the ``repro_rng`` fixture that seeds the
  random workload generators.  A tier-1 failure reproduces with the same
  ``--repro-seed`` it failed under.
"""

from __future__ import annotations

import functools
import os
import sys
import types
import zlib

import pytest

# Forced host devices: the sharded-pod / tensor-parallel tests build meshes
# over XLA host platform devices, which must exist before jax initializes.
# Appended (not overwritten) so an explicit user topology wins.
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip()

# --------------------------------------------------------------------------
# Hypothesis shim (installed only when the real package is absent)
# --------------------------------------------------------------------------


def _install_hypothesis_shim() -> None:
    import numpy as np

    class Strategy:
        """A sampler: ``example(rng) -> value``."""

        def __init__(self, sample):
            self._sample = sample

        def example(self, rng):
            return self._sample(rng)

        def map(self, fn):
            return Strategy(lambda rng: fn(self._sample(rng)))

        def filter(self, pred, _tries: int = 100):
            def sample(rng):
                for _ in range(_tries):
                    v = self._sample(rng)
                    if pred(v):
                        return v
                raise ValueError("filter predicate too strict for shim")
            return Strategy(sample)

    def integers(min_value, max_value):
        return Strategy(
            lambda rng: int(rng.integers(min_value, max_value + 1)))

    def floats(min_value, max_value, **_kw):
        return Strategy(lambda rng: float(rng.uniform(min_value, max_value)))

    def booleans():
        return Strategy(lambda rng: bool(rng.integers(0, 2)))

    def sampled_from(elements):
        seq = list(elements)
        return Strategy(lambda rng: seq[int(rng.integers(0, len(seq)))])

    def just(value):
        return Strategy(lambda rng: value)

    def lists(elements, *, min_size=0, max_size=None, **_kw):
        hi = max_size if max_size is not None else min_size + 10
        return Strategy(lambda rng: [
            elements.example(rng)
            for _ in range(int(rng.integers(min_size, hi + 1)))
        ])

    def tuples(*strategies):
        return Strategy(lambda rng: tuple(s.example(rng)
                                          for s in strategies))

    def one_of(*strategies):
        return Strategy(lambda rng: strategies[
            int(rng.integers(0, len(strategies)))].example(rng))

    def composite(fn):
        @functools.wraps(fn)
        def builder(*args, **kwargs):
            return Strategy(
                lambda rng: fn(lambda s: s.example(rng), *args, **kwargs))
        return builder

    def _seed(name: str, example: int) -> int:
        # REPRO_SEED is the module global set by --repro-seed; read at
        # call time so the option (parsed after this shim installs) wins.
        return zlib.crc32(f"{REPRO_SEED}:{name}:{example}".encode())

    def given(*strategies, **kw_strategies):
        def deco(fn):
            @functools.wraps(fn)
            def wrapper(*args, **kwargs):
                n = getattr(wrapper, "_shim_max_examples", 20)
                for i in range(n):
                    rng = np.random.default_rng(_seed(fn.__name__, i))
                    drawn = [s.example(rng) for s in strategies]
                    kw = {k: s.example(rng)
                          for k, s in kw_strategies.items()}
                    try:
                        fn(*args, *drawn, **kwargs, **kw)
                    except _ShimAssume:
                        continue  # assume() rejected this example
                    except Exception as e:
                        raise AssertionError(
                            f"property falsified on example {i} "
                            f"(seeded, reproducible): args={drawn!r} "
                            f"kwargs={kw!r}") from e
                wrapper.hypothesis_ran = n
            wrapper._shim_max_examples = 20
            wrapper.is_hypothesis_test = True
            # Strategy-supplied params must not look like pytest fixtures:
            # positional strategies fill the rightmost params, kw strategies
            # their named ones; anything left over (e.g. fixtures) stays.
            import inspect

            params = list(inspect.signature(fn).parameters.values())
            if strategies:
                params = params[:-len(strategies)]
            params = [p for p in params if p.name not in kw_strategies]
            wrapper.__signature__ = inspect.Signature(params)
            del wrapper.__wrapped__
            return wrapper
        return deco

    def settings(max_examples=None, deadline=None, **_kw):
        def deco(fn):
            if max_examples is not None:
                fn._shim_max_examples = max_examples
            return fn
        return deco

    hyp = types.ModuleType("hypothesis")
    hyp.given = given
    hyp.settings = settings
    hyp.assume = lambda cond: None if cond else (_ for _ in ()).throw(
        _ShimAssume())
    hyp.HealthCheck = types.SimpleNamespace(too_slow=None, filter_too_much=None)
    hyp.__is_repro_shim__ = True

    st = types.ModuleType("hypothesis.strategies")
    st.integers = integers
    st.floats = floats
    st.booleans = booleans
    st.sampled_from = sampled_from
    st.just = just
    st.lists = lists
    st.tuples = tuples
    st.one_of = one_of
    st.composite = composite
    hyp.strategies = st

    sys.modules["hypothesis"] = hyp
    sys.modules["hypothesis.strategies"] = st


class _ShimAssume(Exception):
    pass


try:  # pragma: no cover - depends on container contents
    import hypothesis  # noqa: F401
except ModuleNotFoundError:
    _install_hypothesis_shim()


# --------------------------------------------------------------------------
# One seed for every random source (--repro-seed)
# --------------------------------------------------------------------------

REPRO_SEED = 0


def pytest_addoption(parser):
    parser.addoption(
        "--repro-seed", action="store", type=int, default=0,
        help="Seed for the hypothesis shim, hypothesis profile, and the "
             "repro_rng workload-generator fixture (deterministic replay)")


def pytest_configure(config):
    global REPRO_SEED
    REPRO_SEED = int(config.getoption("--repro-seed"))
    hyp = sys.modules.get("hypothesis")
    if hyp is not None and not getattr(hyp, "__is_repro_shim__", False):
        # Real hypothesis: pin a derandomized profile so tier-1 runs are
        # reproducible without an example database; the seed feeds the
        # shim and repro_rng (hypothesis derives its own from the test).
        hyp.settings.register_profile(
            "repro", hyp.settings(derandomize=True, print_blob=True))
        hyp.settings.load_profile("repro")


@pytest.fixture
def repro_seed(request) -> int:
    """The suite-wide ``--repro-seed`` value."""
    return REPRO_SEED


@pytest.fixture
def repro_rng(request):
    """Per-test numpy Generator derived from ``--repro-seed`` and the
    test's node id — every random workload generator seeds from this so
    one command-line flag replays a failure exactly."""
    import numpy as np

    return np.random.default_rng(
        zlib.crc32(f"{REPRO_SEED}:{request.node.nodeid}".encode()))


# --------------------------------------------------------------------------
# Tiny deterministic model fixtures (tier-1 speed)
# --------------------------------------------------------------------------

TINY_VOCAB = 64
TINY_SEED = 1234


def tiny_config(**overrides):
    """Dense config small enough that jit compiles in milliseconds."""
    from repro.models.config import ModelConfig

    base = dict(
        name="tiny-dense",
        family="dense",
        n_layers=2,
        d_model=32,
        n_heads=4,
        n_kv_heads=2,
        d_ff=64,
        vocab_size=TINY_VOCAB,
        vocab_pad_multiple=32,
        rope_theta=10_000.0,
    )
    base.update(overrides)
    return ModelConfig(**base)


@pytest.fixture(scope="session")
def tiny_model():
    from repro.models import build_model

    return build_model(tiny_config())


@pytest.fixture(scope="session")
def tiny_params(tiny_model):
    import jax

    return tiny_model.init(jax.random.key(TINY_SEED))


@pytest.fixture(scope="session")
def smoke():
    """The repo-root ``chip_smoke.py`` script, imported as a module."""
    import importlib.util
    from pathlib import Path

    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    module = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = module  # dataclasses resolve it by name
    spec.loader.exec_module(module)
    return module
