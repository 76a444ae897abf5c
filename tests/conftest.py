"""Test fixtures: virtual 8-device CPU mesh + seed discipline.

Mirrors the reference's test infrastructure (reference:
tests/python/unittest/common.py:164 @with_seed, conftest.py:133
function_scope_seed): every test runs with a known seed, printed on failure
for reproduction. Multi-device tests use XLA's host-platform device
simulation — the TPU-world analog of the reference's
`tools/launch.py --launcher local` multi-process rigs (SURVEY §4).
"""
import os

# Tests always run on the virtual 8-device CPU mesh (set MXNET_TEST_ON_TPU=1
# to exercise real hardware): the environment is set here, before the first
# `import jax` of the session.
if not os.environ.get("MXNET_TEST_ON_TPU"):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8")
    os.environ["JAX_PLATFORMS"] = "cpu"
    # XLA:CPU logs two ~3 KB ERROR lines per persistent-cache hit
    # ("Target machine feature +prefer-no-scatter is not supported on the
    # host machine": its own tuning pseudo-features, compared against
    # cpuid). With the compile cache armed at import that is megabytes of
    # captured stderr under every failing test; Python exceptions still
    # carry the real errors.
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

import numpy as onp
import pytest


@pytest.fixture
def program_report():
    """Factory running the mx.analysis program lint over a
    CompiledTrainStep for one example batch — what the tier-1
    structural assertions in test_fused_step.py / test_zero_shard.py
    use to pin collective/donation expectations per mode."""
    from mxnet_tpu.analysis import program as aprog

    def make(step, *args, **kwargs):
        return aprog.analyze_step(step, *args, **kwargs)

    return make


@pytest.fixture
def rows_match():
    """``rows_match(got, want)``: a request's rows served in one shape
    bucket against the same rows served in another (a micro-batch of 2,
    4 or 8 against a single dispatch). Different programs: XLA:CPU picks
    each bucket's matmul (a matrix-vector product at 1 row) and its
    accumulation order by the shape, so the sums may round differently.
    Measured (PR 30, this XLA:CPU): 3.7e-9 on outputs up to 0.079, half
    an ulp of the largest (22 ulp of the smallest element, where the
    terms cancel), in the batcher's, the supervisor's and the fleet's
    tests alike. Held to 4 ulp of the largest output, which still tells
    two weight versions apart.
    Where the bucket is fixed, one program against itself, ``==`` stays
    (test_decode.py: a request alone against the same request in a
    continuous batch, through one compiled step)."""

    def match(got, want):
        got, want = onp.asarray(got), onp.asarray(want)
        atol = 4 * 2.0 ** -23 * float(onp.abs(want).max())
        return got.shape == want.shape and \
            bool((onp.abs(got - want) <= atol).all())

    return match


@pytest.fixture(scope="session")
def lint_allowlist():
    """The checked-in blessed-violation list for the source-lint sweep
    (tests/fixtures/lint_allowlist.txt)."""
    from mxnet_tpu.analysis.lint import load_allowlist
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures", "lint_allowlist.txt")
    return load_allowlist(path)


@pytest.fixture(autouse=True)
def function_scope_seed(request):
    """Seed every test; print the seed on failure so it can be reproduced
    with MXNET_TEST_SEED (reference common.py:195)."""
    env_seed = os.environ.get("MXNET_TEST_SEED")
    seed = int(env_seed) if env_seed else onp.random.randint(0, 2**31)
    import mxnet_tpu as mx
    mx.random.seed(seed)
    yield
    if request.node.rep_call.failed if hasattr(request.node, "rep_call") else False:
        print(f"\nTest failed with seed {seed}; rerun with MXNET_TEST_SEED={seed}")


@pytest.hookimpl(tryfirst=True, hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    rep = outcome.get_result()
    setattr(item, "rep_" + rep.when, rep)
