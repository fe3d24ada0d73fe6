"""`chip_smoke.py`'s phases at a few thousand rows on the virtual CPU mesh,
so that a refactor cannot break the script unnoticed, and the refusals
that took the place of the CPU stand-ins: `chip_smoke.py` and `bench.py`
run nothing without a TPU, the compile cache has one place, the
bandwidth table knows only devices it was given."""

import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import bench  # noqa: E402
import chip_smoke  # noqa: E402

# the phases run both engines and, on four devices, collectives
pytestmark = pytest.mark.time_limit(300)


@pytest.fixture
def host_memory_stats(monkeypatch):
    """XLA:CPU reports no memory_stats; the chip does, and `main()` reads
    it there unsteered."""
    monkeypatch.setattr(chip_smoke, "peak_device_bytes",
                        lambda device: 1 << 40)


def test_one_chip_phase_matches_cpu_engine(tmp_path, monkeypatch,
                                           host_memory_stats):
    # this process has four devices; the chip machine has one, where the
    # planner fuses the exchanges away (plan/overrides._fuse_single_chip)
    from spark_rapids_tpu.plan import overrides
    monkeypatch.setattr(overrides, "_fuse_single_chip", lambda conf: True)
    facts = chip_smoke.run_one_chip(jax.devices()[0], 6000, 42,
                                    str(tmp_path))
    assert [f["query"] for f in facts] == list(bench._SUITE_NAMES)
    for f in facts:
        # run_and_compare already failed on a mismatch or a fallback
        # outside chip_smoke.EXPECTED_CPU_OPS; these are its facts
        assert f["rows"] > 0 and f["cpu_ops"] == 1 and f["tpu_ops"] >= 2, f


def test_four_chip_phase_runs_ici_stages_on_four_devices(host_memory_stats):
    devices = jax.devices()
    assert len(devices) == 4
    facts = chip_smoke.run_four_chips(devices, 6000, 42)
    stages = {s for f in facts for s in f["ici_stages"]}
    assert stages == set(chip_smoke.ICI_STAGES)


def test_unexpected_cpu_fallback_fails_the_query(tmp_path):
    fact, dim = bench.make_tables(2000, 7)
    s = chip_smoke._session(True)
    q = dict(bench.queries(s, fact, dim, str(tmp_path), str(tmp_path)))
    with pytest.raises(AssertionError, match="fell back to the CPU engine"):
        chip_smoke.run_and_compare("agg", q["agg"], q["agg"], s,
                                   expected_cpu_ops=frozenset())


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_a_platform_that_is_not_tpu(argv, capsys):
    with pytest.raises(SystemExit) as exit_info:
        chip_smoke.main(argv)
    assert exit_info.value.code not in (0, None)
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("args", [["1000"], ["--serve"], ["--phase=cold",
                                                         "1000"]])
def test_bench_exits_nonzero_and_prints_no_number_without_a_tpu(args):
    """What replaced `--cpu-fallback`: no CPU number under a device
    metric's name, from the parent or from a phase."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), *args],
        capture_output=True, text=True, timeout=240,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "needs a TPU" in proc.stderr


def test_bench_parent_stays_off_jax():
    """One process per chip: the process that starts the phases must
    not hold the device, so it may import neither JAX nor the engine."""
    code = ("import sys; sys.argv = ['bench.py']; import bench; "
            "assert 'jax' not in sys.modules and "
            "'spark_rapids_tpu' not in sys.modules; "
            "bench._run_phase = lambda *a, **k: sys.exit(7); bench.main()")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 7, proc.stderr


def test_hbm_bandwidth_table_rejects_an_unknown_device():
    assert bench.hbm_bytes_per_s("TPU v5 lite") == 819e9
    with pytest.raises(SystemExit, match="no published HBM bandwidth"):
        bench.hbm_bytes_per_s("cpu")


# -- one compile cache, placeable ------------------------------------------

@pytest.fixture
def recorded_jax_config(monkeypatch):
    """jax.config.update recorded, not applied: this process's own
    cache setting stays what conftest made it."""
    from jax.experimental.compilation_cache import compilation_cache
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.__setitem__(k, v))
    monkeypatch.setattr(compilation_cache, "reset_cache", lambda: None)
    return calls


def test_cache_dir_from_the_environment_is_not_set_in_code(
        monkeypatch, tmp_path, recorded_jax_config):
    from spark_rapids_tpu import plugin
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert plugin.init_compilation_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in recorded_jax_config


def test_default_cache_dir_is_fixed_under_the_checkout(
        monkeypatch, tmp_path, recorded_jax_config):
    from spark_rapids_tpu import plugin
    assert plugin.default_cache_dir() == os.path.join(REPO, ".jax_cache")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(plugin, "default_cache_dir",
                        lambda: str(tmp_path / ".jax_cache"))
    d = plugin.init_compilation_cache()
    assert d == str(tmp_path / ".jax_cache") and os.path.isdir(d)
    assert recorded_jax_config["jax_compilation_cache_dir"] == d
    assert os.listdir(d) == []      # no hashed or per-process sub-directory


@pytest.mark.parametrize("conf,env", [
    ({"spark.rapids.tpu.compilationCache.enabled": "false"}, {}),
    ({}, {"SPARK_RAPIDS_TPU_DISABLE_COMPILE_CACHE": "1"}),
])
def test_cache_switches_off_by_its_key_or_the_child_variable(
        monkeypatch, conf, env, recorded_jax_config):
    from spark_rapids_tpu import plugin
    monkeypatch.delenv("SPARK_RAPIDS_TPU_DISABLE_COMPILE_CACHE",
                       raising=False)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    monkeypatch.setattr(plugin, "init_compilation_cache",
                        lambda: pytest.fail("cache initialised"))
    plugin.TpuExecutorPlugin(conf)._init_compilation_cache()


@pytest.mark.parametrize("key", ["spark.rapids.tpu.compilationCache.dir",
                                 "spark.rapids.tpu.jit.persistentCacheDir"])
def test_cache_directory_keys_are_gone(key):
    from spark_rapids_tpu import config as cfg
    assert key not in cfg.generate_docs()
    assert "spark.rapids.tpu.compilationCache.enabled" in cfg.generate_docs()
