"""Test config: force CPU backend with 8 virtual devices.

This is the reference's "distributed tests without a cluster" mechanism
rebuilt for XLA (SURVEY §4: fake_cpu_device / subprocess clusters ->
host-platform simulated mesh). ``JAX_PLATFORMS`` and ``XLA_FLAGS`` are set
before the first ``import jax``; nothing else is needed.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")

# fault-injection tests trigger flight-recorder crash dumps (engine
# resets, rollbacks, hangs); keep their artifacts out of the repo tree
import tempfile  # noqa: E402

os.environ.setdefault("PT_FLIGHT_DIR",
                      tempfile.mkdtemp(prefix="pt_flight_tests_"))

import jax  # noqa: E402

assert jax.default_backend() == "cpu", "tests must run on the CPU backend"
assert len(jax.devices()) == 8, "expected 8 virtual CPU devices"

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture()
def interpret_pallas():
    """Run the Pallas kernels (flash attention, the cache write and read:
    all reach ``pallas_call`` through the one ``pallas`` module) in interpret
    mode (the CPU has no Mosaic); yields the list of pallas_call
    invocations so a test can see that the kernels were really traced."""
    from unittest import mock

    from paddle_tpu.kernels import cache_read
    from paddle_tpu.kernels import flash_attention as fa

    orig = fa.pl.pallas_call
    calls = []

    def interp(*a, **k):
        calls.append(getattr(a[0], "func", a[0]).__name__)
        k["interpret"] = True
        return orig(*a, **k)

    # the reads are jitted on their own: no trace of one crosses this fixture
    reads = (cache_read.read_by_position, cache_read.read_latent_by_position)
    for read in reads:
        read.clear_cache()
    with mock.patch.object(fa.pl, "pallas_call", interp):
        yield calls
    for read in reads:
        read.clear_cache()


@pytest.fixture()
def show_the_gate_a_tpu(monkeypatch):
    """A callable after which the gates of the decode step's cache
    kernels (``kv_cache._rows_by_dma`` for the write,
    ``kv_cache._reads_by_position`` and ``_latent_reads_by_position`` for
    the read) see a TPU backend, for
    the length of a gate's own call only: nothing else in the process
    takes the CPU for a TPU."""
    from unittest import mock

    from paddle_tpu.models import kv_cache

    def shown(real):
        def gate(*args):
            with mock.patch.object(jax, "default_backend", lambda: "tpu"):
                return real(*args)

        return gate

    def show():
        for name in ("_rows_by_dma", "_reads_by_position",
                     "_latent_reads_by_position"):
            monkeypatch.setattr(kv_cache, name,
                                shown(getattr(kv_cache, name)))

    return show


@pytest.fixture()
def as_on_tpu(show_the_gate_a_tpu, interpret_pallas):
    """The gate sees a TPU backend; the kernels it then chooses run
    interpreted, and the fixture's value lists them."""
    show_the_gate_a_tpu()
    return interpret_pallas


@pytest.fixture(scope="module")
def one_chip():
    """A described v5e chip: the TPU's compiler is installed here and
    refuses what the chip's would (a slice off the tiling, too much
    VMEM), which interpret mode cannot show."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent cache
    # and cannot be read back without the chip
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'` (ROADMAP): long decode/bench subprocess
    # tests opt out of the 870 s budget with this marker
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 time budget")


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    """Tier-1 time-budget report: the slowest tests of this run, so the
    next offender to move behind the ``slow`` marker is visible in every
    CI log instead of requiring a separate ``--durations`` run. Call +
    setup + teardown are summed per test (a fixture-heavy test is just
    as much over budget as a slow body)."""
    durations: dict = {}
    for reports in terminalreporter.stats.values():
        for rep in reports:
            dur = getattr(rep, "duration", None)
            nodeid = getattr(rep, "nodeid", None)
            if dur is None or not nodeid:
                continue
            durations[nodeid] = durations.get(nodeid, 0.0) + dur
    if not durations:
        return
    top = sorted(durations.items(), key=lambda kv: -kv[1])[:10]
    total = sum(durations.values())
    tr = terminalreporter
    tr.write_sep("=", "slowest tests (tier-1 time budget)")
    for nodeid, dur in top:
        tr.write_line(f"{dur:8.2f}s  {nodeid}")
    tr.write_line(f"{total:8.2f}s  total across {len(durations)} tests")


@pytest.fixture(autouse=True)
def _seed_all():
    import paddle_tpu

    paddle_tpu.seed(2024)
    np.random.seed(2024)
    yield
    # tests that build a global mesh (init_mesh/fleet.init) must not leak it
    # into mesh-free tests: pjit'd single-device steps would suddenly see a
    # distributed mesh and fail on sharding mismatches
    from paddle_tpu.distributed.mesh import set_mesh

    set_mesh(None)
    # likewise the process-wide PS context: restore sync mode and drop any
    # cached communicators (they may wrap clients a fixture already closed)
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.distributed.ps import get_ps_context

    try:
        get_ps_context().configure_mode(DistributedStrategy())
    except Exception:
        pass  # a dead communicator flush must not fail the NEXT test


def pytest_sessionfinish(session, exitstatus):
    """Reap orphaned shard-server subprocesses (VERDICT r4 weak #7: eight
    graph_server orphans observed 16h after an aborted run). PDEATHSIG +
    the servers' ppid watchdog prevent new leaks; this sweeps anything
    that predates them or slipped both nets. Only processes reparented to
    init (ppid 1) are touched — live sessions still own their servers."""
    import re

    try:
        pid_dirs = os.listdir("/proc")
        with open("/proc/1/cmdline", "rb") as f:
            init_cmd = f.read().replace(b"\0", b" ").decode(errors="replace")
    except OSError:
        return  # no procfs (macOS): nothing to sweep
    if "python" in init_cmd:
        # PID 1 is itself a python process (container entrypoint) — its
        # ppid==1 children may be LIVE servers it legitimately owns, not
        # orphans (see procutil.start_ppid_watchdog's warning)
        return
    for pid_dir in pid_dirs:
        if not pid_dir.isdigit():
            continue
        try:
            with open(f"/proc/{pid_dir}/cmdline", "rb") as f:
                cmd = f.read().replace(b"\0", b" ").decode(errors="replace")
            with open(f"/proc/{pid_dir}/stat") as f:
                stat = f.read()
            # field 4 (ppid) comes after the parenthesised comm, which may
            # itself contain spaces — split after the LAST ')'
            ppid = int(stat.rsplit(")", 1)[1].split()[1])
        except (OSError, ValueError, IndexError):
            continue  # raced with exit / unparseable
        if ppid == 1 and re.search(
                r"paddle_tpu\.distributed\.ps\.(graph_server|server)", cmd):
            try:
                os.kill(int(pid_dir), 9)
            except OSError:
                pass
