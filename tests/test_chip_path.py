"""Guards on the path that runs on the chip: the smoke run refuses any other
platform, peaks exist only for known device kinds, the compile cache sits
where the environment or the repo says, and importing the dry-run tools
leaves XLA_FLAGS alone."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import roofline
from repro.launch.compile_cache import DEFAULT_DIR, enable_compile_cache
from repro.launch.mesh import peaks

REPO = Path(__file__).resolve().parents[1]


def _run(code_or_args, env_extra=None, cwd=REPO):
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"), "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(REPO / "src"), **(env_extra or {})}
    args = code_or_args if isinstance(code_or_args, list) else ["-c", code_or_args]
    return subprocess.run([sys.executable, *args], capture_output=True, text=True,
                          cwd=cwd, env=env, timeout=300)


def test_chip_smoke_refuses_cpu_before_any_phase():
    proc = _run([str(REPO / "chip_smoke.py")])
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "needs a TPU" in proc.stderr


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_peaks_unknown_device_kind_is_an_error(kind):
    with pytest.raises(ValueError, match="no published peaks"):
        peaks(kind)


def test_peaks_v5e_and_roofline_use_the_cell_device_kind():
    v5e = peaks("TPU v5 lite")
    assert v5e["flops_bf16"] == 197e12 and v5e["hbm_bw"] == 819e9
    rec = {"arch": "qwen2-0.5b", "shape": "train_4k", "mesh": "single", "n_devices": 256,
           "cost": {"flops": 1e15, "bytes accessed": 1e12}, "collectives": {}}
    row = roofline.analyze_cell({**rec, "device_kind": "TPU v5 lite"})
    assert row["t_compute_s"] == pytest.approx(1e15 / 197e12)
    assert row["dominant"] == "compute"
    with pytest.raises(ValueError, match="'cpu'"):
        roofline.analyze_cell({**rec, "device_kind": "cpu"})


def test_compile_cache_defaults_to_the_repo(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == str(REPO / ".jax_cache") == str(DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_lands_in_the_environment_dir(tmp_path):
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()\n"
    )
    proc = _run(code, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == str(tmp_path)
    assert any(tmp_path.iterdir())
    assert not (tmp_path / ".jax_cache").exists()


def test_importing_dry_run_tools_sets_no_xla_flags():
    code = (
        "import os\n"
        "import repro.launch.dryrun, repro.launch.hlo_profile, repro.obs.profile\n"
        "print(os.environ.get('XLA_FLAGS'))\n"
    )
    proc = _run(code)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "None"
