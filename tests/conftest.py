"""Test bootstrap: force JAX onto a virtual 4-device CPU mesh BEFORE any
jax import, so TPU-path kernels and multi-chip sharding run hermetically
(`python chip_smoke.py --chips 4` is the same mesh on real chips).

Four devices, not more: the driver runs six of these processes side by
side, every collective needs all of a process's device threads at its
rendezvous at once, and XLA:CPU aborts the process when one of them is
not there within its time limit."""

import faulthandler
import os

os.environ["JAX_PLATFORMS"] = "cpu"
# Several test processes run at once; XLA:CPU AOT loads from a cache
# directory under concurrent write have segfaulted, and a cache shared
# across runs makes what a test compiles depend on what ran before it.
# The suite relies on the in-process jit table instead.
os.environ["SPARK_RAPIDS_TPU_DISABLE_COMPILE_CACHE"] = "1"
# A full suite process drives the kernel's vm.max_map_count (65530)
# into the ground: glibc malloc serves every large XLA:CPU buffer with
# its own anonymous mmap, and ~600 jitted programs' worth of arrays put
# the process at ~36k maps by mid-suite and over the limit around the
# window tests — at which point ANY native allocation (a compile, a
# cache serialize, a cache read) segfaults.  mallopt(M_MMAP_MAX, 0)
# routes large allocations through the heap instead; map count stays
# flat and the crashes disappear.  (Root-caused from three distinct
# fatal stacks that all struck at the same process age.)
import ctypes

try:
    _libc = ctypes.CDLL("libc.so.6", use_errno=True)
    _libc.mallopt(-4, 0)        # M_MMAP_MAX = 0
except Exception:               # non-glibc platforms: keep defaults
    pass
# XLA's warnings, errors and fatal messages stay visible: when it aborts
# the process (a collective's rendezvous timeout, say) its last line is
# the only statement of why.  pytest captures them; run with -s to see
# them in a process that died.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "1")
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    xla_flags += " --xla_force_host_platform_device_count=4"
if "xla_cpu_collective_call_terminate_timeout_seconds" not in xla_flags:
    # shared host: a device thread that waits for a core is late, not
    # lost.  XLA:CPU's default kills the process after 40 s; the tests'
    # own time limit (below) is what ends a collective that is stuck.
    xla_flags += (" --xla_cpu_collective_call_warn_stuck_timeout_seconds=60"
                  " --xla_cpu_collective_call_terminate_timeout_seconds=300")
if "xla_cpu_enable_fast_math" not in xla_flags:
    # fast-math breaks IEEE inf/nan semantics (floor(inf) -> nan)
    xla_flags += " --xla_cpu_enable_fast_math=false"
if "xla_cpu_parallel_codegen_split_count" not in xla_flags:
    # a full-suite process JITs hundreds of programs; XLA:CPU's parallel
    # LLVM codegen has crashed nondeterministically deep into such runs
    # (segfault inside backend_compile_and_load) — serialize it
    xla_flags += " --xla_cpu_parallel_codegen_split_count=1"
if "xla_cpu_use_thunk_runtime" not in xla_flags:
    # the thunk runtime JITs one LLVM module PER KERNEL (~16k modules x
    # 3 mappings for this suite), blowing through the kernel's
    # vm.max_map_count (65530) mid-run — at which point any native
    # allocation segfaults.  The legacy runtime emits one module per
    # executable: map count stays ~2k for the same suite.
    xla_flags += " --xla_cpu_use_thunk_runtime=false"
os.environ["XLA_FLAGS"] = xla_flags.strip()

import pytest  # noqa: E402

#: seconds a test may run before the watchdog ends its process; a test
#: that starts a server, a child or a collective sets its own with
#: ``@pytest.mark.time_limit(seconds)``
DEFAULT_TIME_LIMIT_S = 600

_watchdog_out = None


def pytest_configure(config):
    global _watchdog_out
    config.addinivalue_line(
        "markers", "time_limit(seconds): end the test process, with every "
        "thread's traceback, when the test runs longer than this")
    # output capture is suspended while plugins configure: this is the
    # real stderr, which a dying process can still write to
    _watchdog_out = os.fdopen(os.dup(2), "w")


@pytest.fixture(autouse=True)
def _time_limit(request):
    """A stuck test fails instead of eating the run: past its limit the
    watchdog thread dumps all tracebacks and exits the process, even
    when the main thread sits in native code.  Under xdist the worker
    is replaced and the test is reported as the one that crashed it."""
    marker = request.node.get_closest_marker("time_limit")
    seconds = marker.args[0] if marker else DEFAULT_TIME_LIMIT_S
    faulthandler.dump_traceback_later(seconds, exit=True,
                                      file=_watchdog_out)
    yield
    faulthandler.cancel_dump_traceback_later()


@pytest.fixture(scope="session")
def tpu_session():
    from spark_rapids_tpu.api.session import TpuSession
    return TpuSession.builder().get_or_create()


@pytest.fixture(autouse=True, scope="module")
def _bound_jit_code_residency():
    """Flush compiled-code caches between test modules.

    Beyond the engine's own LRU (exec/base.py process_jit), jax keeps
    GLOBAL caches for eager ops and dropped jits; across ~40 modules the
    accumulated LLVM JIT segments walk the process into the kernel's
    vm.max_map_count, after which any native allocation segfaults.
    In-module kernel reuse (what the tests exercise) is unaffected."""
    yield
    import jax

    from spark_rapids_tpu.exec.base import clear_jit_cache
    clear_jit_cache()
    jax.clear_caches()
