"""chip_smoke.py off the chip, and the compile-cache helper it shares with
the trainers, the server and bench.py."""

import inspect
import os
import shutil
import subprocess
import sys

import jax
import pytest

from simclr_pytorch_distributed_tpu import config as config_lib
from simclr_pytorch_distributed_tpu.train import supcon

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("alone,argv", [
    (False, []),              # the driver's sandbox check: CPU only
    (True, []),               # a dir holding chip_smoke.py and nothing else
    (True, ["--rehearse"]),   # ... where even a rehearsal cannot import
])
def test_chip_smoke_fails_without_a_tpu(tmp_path, alone, argv):
    """No accelerator -> non-zero exit, no result line, within seconds: the
    platform check comes before any trainer import."""
    cwd = REPO
    if alone:
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        cwd = str(tmp_path)
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "XLA_FLAGS")}  # one plain CPU device
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py", *argv], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    if argv:
        assert "No module named 'simclr_pytorch_distributed_tpu'" in proc.stderr
    else:
        assert "needs a TPU" in proc.stderr
        assert proc.stdout == ""  # refused before it said anything


@pytest.fixture
def config_updates(monkeypatch):
    """Record ``jax.config.update`` calls instead of applying them."""
    calls = []
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: calls.append((name, value))
    )
    return calls


def test_compile_cache_env_var_set_means_code_sets_no_place(
    monkeypatch, config_updates
):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert supcon.enable_compile_cache() == "/somewhere/else"
    # nothing about the PLACE; what the key holds is set in either case
    # (PR 25: op_names and nothing else of the metadata, so that a hit never
    # hands back another build's scopes and a line shift still hits)
    assert config_updates == [
        ("jax_compilation_cache_include_metadata_in_key", True),
        ("jax_traceback_in_locations_limit", 0),
    ]


def test_compile_cache_defaults_to_checkout(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert supcon.enable_compile_cache() == want
    assert ("jax_compilation_cache_dir", want) in config_updates
    assert ("jax_compilation_cache_include_metadata_in_key", True) in config_updates


def test_compile_cache_dir_does_not_move_with_the_workdir(
    monkeypatch, config_updates, tmp_path
):
    """Two runs with different workdirs (and cwds) share one cache dir: the
    helper takes no workdir and the ``--compile_cache`` flag is gone."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert not inspect.signature(supcon.enable_compile_cache).parameters
    dirs = []
    for name in ("run_a", "run_b"):
        workdir = tmp_path / name
        workdir.mkdir()
        monkeypatch.chdir(workdir)
        cfg = config_lib.parse_supcon(
            ["--dataset", "synthetic", "--workdir", str(workdir)]
        )
        assert not hasattr(cfg, "compile_cache")
        dirs.append(supcon.enable_compile_cache())
    assert dirs[0] == dirs[1] == os.path.join(REPO, ".jax_cache")
    with pytest.raises(SystemExit):
        config_lib.supcon_parser().parse_args(["--compile_cache", "x"])
