"""The entry points that drive the chip: compile cache placement, the
device-kind machine profile, and failure exit codes."""
import os
import sys
import types

import pytest
import jax

from repro.analysis import machine
from repro.runtime import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_env_var_wins(monkeypatch, tmp_path,
                                    cache_dir_config):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "env"))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache(str(tmp_path)) == str(
        tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch, tmp_path,
                                            cache_dir_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable_compile_cache(str(tmp_path))
    assert path == str(tmp_path / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_machine_profile_follows_device_kind():
    v5e = types.SimpleNamespace(device_kind="TPU v5 lite", platform="tpu")
    assert machine.device_machine(v5e) == "tpu-v5e"
    assert machine.get_machine(machine.device_machine(v5e)).peak_flops == \
        197e12
    assert machine.device_machine() == "host-sim"  # the tests' CPU
    with pytest.raises(KeyError, match="TPU v9"):
        machine.device_machine(types.SimpleNamespace(device_kind="TPU v9",
                                                     platform="tpu"))


def test_benchmarks_run_exits_nonzero_on_module_error(monkeypatch, capsys,
                                                      tmp_path):
    from benchmarks import run

    def boom(report):
        raise RuntimeError("module broke")

    monkeypatch.setitem(run.MODULES, "boom",
                        types.SimpleNamespace(main=boom))
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    monkeypatch.setattr(sys, "argv", ["run", "--only", "boom"])
    with pytest.raises(SystemExit) as e:
        run.main()
    assert e.value.code == 1
    assert "boom,ERROR,module broke" in capsys.readouterr().out


def test_chip_smoke_refuses_without_a_tpu(capsys):
    sys.path.insert(0, ROOT)
    import chip_smoke
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr().out
    assert "platform=cpu" in out and '"ok"' not in out
