"""Test fixtures.

Mirrors the reference's ``python/ray/tests/conftest.py``:
``ray_start_regular`` (reference ``conftest.py:245``) boots a real
single-node runtime in-process; ``ray_start_cluster`` (``conftest.py:326``)
gives the fake multi-node Cluster.  JAX tests run on a virtual 8-device CPU
mesh (``xla_force_host_platform_device_count``) per SURVEY §4's TPU note.
"""

import os

# The tests run on the CPU: eight virtual devices stand in for a mesh, and
# "chips" are resource counts the node hands out as environment variables.
# Both settings must be in place before the first jax backend starts, and
# worker subprocesses inherit them through os.environ.  (Workers that are
# granted no chip are held to the CPU by the node anyway; this also keeps
# the pytest process itself and fake-chip workers there.)
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("RAY_TPU_LOG_LEVEL", "WARNING")
# The suite runs over the TYPED wire protocol (also the production
# default since the packed hot-frame codec landed) so every packed and
# protobuf arm is exercised by every cluster test — see _private/wire.py.
os.environ.setdefault("RAY_TPU_WIRE", "proto")
# ... and with a SHARDED head dispatch (also the production default):
# the whole actor/gang/concurrency-group surface runs at shard count 4,
# pinned explicitly so a default change can't silently shrink coverage.
os.environ.setdefault("RAY_TPU_HEAD_SHARDS", "4")

import pytest  # noqa: E402

import ray_tpu  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: heavy sanitizer/chaos runs excluded from tier-1")


def pytest_collection_modifyitems(config, items):
    # tier-1 is a plain `pytest tests/` — slow tests must opt in via an
    # -m expression that names "slow" or RAY_TPU_RUN_SLOW=1, or the TSan
    # build+run pushes the suite past its wall-clock cap (an unrelated
    # -m filter must not pull them in as a side effect)
    if ("slow" in (config.option.markexpr or "")
            or os.environ.get("RAY_TPU_RUN_SLOW")):
        return
    skip = pytest.mark.skip(
        reason="slow: run with -m slow or RAY_TPU_RUN_SLOW=1")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture
def ray_start_regular():
    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_2_tpus():
    """Single node with 2 fake TPU chips (chips are only env-assigned)."""
    ray_tpu.init(num_cpus=4, num_tpus=2)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 2, "num_tpus": 0})
    yield cluster
    cluster.shutdown()


# -- the served-model tests' fixtures (tests/family_harness.py) ---------------

@pytest.fixture
def lowered_for_tpu(monkeypatch):
    """``lax.platform_dependent`` takes its ``tpu`` branch, and Pallas calls
    run in the TPU interpreter: the decode program a chip would run, here.
    The harness keeps the programs traced inside it apart from the CPU's
    (``family_harness.PATH``)."""
    import family_harness
    from jax.experimental.pallas import tpu as pltpu

    from ray_tpu.models import generate as gen

    monkeypatch.setattr(
        gen.lax, "platform_dependent",
        lambda *args, tpu, default: tpu(*args))
    monkeypatch.setattr(family_harness, "PATH", "lowered_for_tpu")
    with pltpu.force_tpu_interpret_mode():
        yield


@pytest.fixture(params=["cpu", "lowered_for_tpu"])
def positions(request):
    """A chunk case runs twice: as the CPU runs it (masked einsums over the
    slab, a slice update a slot) and as a chip does (the ragged read and the
    flush kernel, through ``lowered_for_tpu``).  Gives the cache length a
    case asks for as the path needs it: the kernels are chosen for a cache of
    whole 128-position tiles, as the engine's always is."""
    if request.param == "cpu":
        return lambda n: n
    request.getfixturevalue("lowered_for_tpu")
    return lambda n: -(-n // 128) * 128


@pytest.fixture
def kept_engine_programs(monkeypatch):
    """Every ``GenerationEngine`` built under this fixture takes its jitted
    programs from one memo over ``llm.engine_programs`` (a function of the
    config and its arguments alone), so that engines of one config and
    arguments compile once a worker and not once a case.  The served-model
    files ask for it module-wide (``pytestmark``)."""
    import family_harness

    from ray_tpu.serve import llm

    monkeypatch.setattr(llm, "engine_programs", family_harness.kept_programs())
