"""Test harness: simulate an 8-device TPU mesh on CPU.

The reference has zero tests (SURVEY.md §4); this suite is the from-scratch
strategy it prescribes: unit tests per component, sharding-equivalence tests
(N-device step == single-device step) on a virtual device mesh, golden-loss
regression, and end-to-end train→checkpoint→resume→serve smokes.

Env vars must be set before jax initializes, hence module scope here.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags += " --xla_force_host_platform_device_count=8"
if "xla_backend_optimization_level" not in flags:
    # The suite is XLA-compile-bound on small runners and tests OUR code,
    # not XLA's optimizer: backend opt level 0 cuts cold-compile wall time
    # ~30% with identical test outcomes (numerics still honor
    # jax_default_matmul_precision below). Remove via
    # XLA_FLAGS=--xla_backend_optimization_level=1 if ever suspect.
    flags += " --xla_backend_optimization_level=0"
os.environ["XLA_FLAGS"] = flags

import jax  # noqa: E402
import pytest  # noqa: E402

# The CPU backend downcasts fp32 matmul inputs under the default precision
# (≈bf16, ~7e-3 error); correctness tests need true fp32 matmuls.
jax.config.update("jax_default_matmul_precision", "highest")

# The suite is XLA-compile-bound on a small runner; the persistent cache
# (same place as every entry point's: utils.platform) replays every test's
# compiles after the first run. Floor of 0.5 s: test-sized programs
# compile in 0.5–5 s each, and the hundreds below that are cheaper to
# redo than to look up.
from dlti_tpu.utils.platform import enable_compilation_cache  # noqa: E402

enable_compilation_cache(min_compile_secs=0.5)


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture()
def time_limit():
    """``with time_limit(seconds):`` raises TimeoutError in the test's own
    (main) thread when the block has not ended by then: every test that
    starts a profiler, or waits for a process, bounds it with this."""
    import contextlib
    import signal

    @contextlib.contextmanager
    def limit(seconds: int):
        def on_alarm(signum, frame):
            raise TimeoutError(f"no end within {seconds} s")

        was = signal.signal(signal.SIGALRM, on_alarm)
        signal.alarm(seconds)
        try:
            yield
        finally:
            signal.alarm(0)
            signal.signal(signal.SIGALRM, was)

    return limit


@pytest.fixture()
def rng():
    return jax.random.PRNGKey(0)


@pytest.fixture(scope="session")
def fetch_first_engine():
    """``InferenceEngine`` in the order it had before its loop ran a round
    ahead of the host, as the tests' own hook (no option of the program):
    every round is fetched in the step that launched it, so every plan is
    made from the host's tokens and no row ever rides. What the loop that
    runs ahead is held to, stream for stream."""
    from dlti_tpu.serving import InferenceEngine

    class FetchFirst(InferenceEngine):
        def step(self):
            out = super().step()
            if self._inflight is not None:
                pending, self._inflight = self._inflight, None
                out = out + self._decode_complete(pending)
            return out

    return FetchFirst


@pytest.fixture
def engine_log(caplog):
    """``caplog`` that also hears the package's logger (``dlti_tpu``, which
    does not propagate to the root)."""
    from dlti_tpu.utils.logging import get_logger

    logger = get_logger()
    logger.addHandler(caplog.handler)
    yield caplog
    logger.removeHandler(caplog.handler)


def make_packed_segments(b, s, n_docs=3, seed=0):
    """Shared packed-batch layout for attention tests: contiguous docs
    1..n_docs with random cut points, trailing padding id 0."""
    import numpy as np
    import jax.numpy as jnp

    gen = np.random.default_rng(seed)
    segs = np.zeros((b, s), dtype=np.int32)
    for row in range(b):
        cuts = np.sort(gen.choice(np.arange(4, s - 4), n_docs, replace=False))
        prev, sid = 0, 1
        for c in cuts:
            segs[row, prev:c] = sid
            prev, sid = c, sid + 1
    return jnp.asarray(segs)
