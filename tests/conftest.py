"""Test configuration: force an 8-device virtual CPU mesh before jax loads.

Mirrors the strategy SURVEY.md §4 prescribes: multi-device behavior
(DDP psum, SyncBatchNorm stat merge, mesh dryruns) is validated on a faked
host-platform mesh — something the reference could not do (it needed 2 real
GPUs, tests/L1/cross_product_distributed/run.sh).
"""

import os

# Tests run on the virtual CPU mesh by default.  Setting
# APEX_TPU_TEST_BACKEND=tpu skips the CPU forcing so kernel tests compile
# through Mosaic on real hardware (prove the Pallas families lower, not
# only interpret).
_TPU_TESTS = os.environ.get("APEX_TPU_TEST_BACKEND") == "tpu"

if not _TPU_TESTS:
    # force 8 host devices before the first jax.devices() call
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402  (import after env setup)

if not _TPU_TESTS:
    assert jax.default_backend() == "cpu", (
        "tests must run on the CPU mesh; a backend was already "
        "initialized before conftest ran")
    assert len(jax.devices()) >= 8
else:
    # parity tests compare Pallas kernels against dense jnp math; the
    # TPU's default bf16 matmul passes on fp32 inputs would put ~1e-3 of
    # noise on both sides of every assert_allclose
    jax.config.update("jax_default_matmul_precision", "highest")

import pytest  # noqa: E402

# Files whose tests are meaningful on a single-chip TPU run (kernel
# lowering / long-context parity).  Everything else assumes the 8-device
# CPU mesh and is skipped in TPU mode rather than erroring inside
# Mesh/shard_map construction.
_TPU_OK_FILES = {"test_pallas_kernels.py", "test_flash_long.py"}


def pytest_collection_modifyitems(config, items):
    if not _TPU_TESTS or len(jax.devices()) >= 8:
        return
    skip = pytest.mark.skip(
        reason="needs the 8-device CPU mesh; run without "
               "APEX_TPU_TEST_BACKEND=tpu")
    for item in items:
        if item.path.name not in _TPU_OK_FILES:
            item.add_marker(skip)


@pytest.fixture(autouse=True)
def _reset_amp_policy():
    """O1 amp.initialize installs a process-wide cast policy (the analogue
    of the reference's global monkey-patching); never let one test's
    policy leak into the next."""
    yield
    from apex_tpu.amp import policy
    policy.set_policy(policy.NoPolicy())


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


@pytest.fixture(scope="session")
def mesh8():
    from jax.sharding import Mesh
    import numpy as np
    return Mesh(np.array(jax.devices()[:8]), ("data",))


@pytest.fixture(scope="session")
def one_chip():
    """The sharding of one chip of a described (not attached) v5e:2x2: what
    a program is lowered with to be compiled for the TPU off the chip."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:                      # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def past_the_cache():
    """A compile past the persistent cache, which cannot read an entry
    compiled for a described chip back."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()             # or the cache in use stays in use
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def for_the_chip(monkeypatch, past_the_cache):
    """Kernels as the chip runs them (Mosaic, not the interpreter), compiled
    past the persistent cache (``past_the_cache``)."""
    from apex_tpu.ops import dispatch
    monkeypatch.setattr(dispatch, "backend", lambda: "tpu")


# Persistent XLA compilation cache (VERDICT r3 item 9: suite cost): the
# suite's dominant cost is recompiling the same resnet/bert/flash graphs
# in every worker every run.  A shared on-disk cache makes warm runs and
# cross-worker repeats near-free; utils.compile_cache says where it lives
# (JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_compile_cache).
# Disable with APEX_TPU_NO_COMPILE_CACHE=1 (e.g. if the XLA:CPU AOT
# loader's machine-feature check ever misfires).
if not os.environ.get("APEX_TPU_NO_COMPILE_CACHE"):
    from apex_tpu.utils import configure_compile_cache
    configure_compile_cache()
    # APEX_TPU_COMPILE_CACHE_MIN_S=0 makes EVERY compile cacheable —
    # tests/ci/double_run.py needs that so its run-2 cache-HIT
    # measurement (the compilation ledger's positive gate) isn't
    # spoiled by sub-threshold toy compiles that were never written
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs",
        float(os.environ.get("APEX_TPU_COMPILE_CACHE_MIN_S", "0.5")))


def pytest_sessionfinish(session, exitstatus):
    """Dump the compilation ledger at session end when asked
    (APEX_TPU_COMPILATION_LEDGER_DUMP=path): tests/ci/double_run.py
    reads the two runs' dumps to assert the warm run's serving
    compiles were persistent-cache HITS — a positive measurement of
    the AOT reload actually happening, on top of the runs passing."""
    path = os.environ.get("APEX_TPU_COMPILATION_LEDGER_DUMP")
    if path:
        from apex_tpu.observability import compilation
        compilation.get_ledger().dump(path)


def assert_trees_close(a, b, atol):
    """Pytree comparison with structure check and key-path error labels
    (shared by the tensor/pipeline parallel parity tests)."""
    import numpy as _np
    fa = jax.tree_util.tree_flatten_with_path(a)[0]
    fb = jax.tree_util.tree_flatten_with_path(b)[0]
    assert [jax.tree_util.keystr(p) for p, _ in fa] == \
        [jax.tree_util.keystr(p) for p, _ in fb]
    for (pa, xa), (_, xb) in zip(fa, fb):
        _np.testing.assert_allclose(
            _np.asarray(xa), _np.asarray(xb), atol=atol,
            err_msg=jax.tree_util.keystr(pa))
