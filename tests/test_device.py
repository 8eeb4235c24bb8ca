"""Strict device checks: the chip spec is looked up by device kind, the
compile cache sits where it is told, and the chip smoke refuses a host
without a TPU."""
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from repro.core.gemm import cmr
from repro.runtime import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v5e"])
def test_v5e_kinds_map_to_v5e_spec(kind):
    assert cmr.spec_for_kind(kind) is cmr.TPU_V5E


def test_unknown_tpu_kind_raises():
    with pytest.raises(ValueError, match="no TpuSpec for TPU device kind"):
        cmr.spec_for_kind("TPU v99 imaginary")


def test_cpu_plans_against_v5e():
    assert cmr.device_spec() is cmr.TPU_V5E
    # The planner admits against half the limit the kernels ask Mosaic for.
    assert cmr.TPU_V5E.vmem_budget * 2 == cmr.TPU_V5E.vmem_limit


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)


def test_compile_cache_fixed_in_repo_path(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.compile_cache_dir()
    assert first == compile_cache.compile_cache_dir()
    assert pathlib.Path(first) == REPO / ".jax_cache"


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_chip_smoke_refuses_a_host_without_tpu(tmp_path, where):
    """No TPU (or no repo beside the script): non-zero exit, no result."""
    script = REPO / "chip_smoke.py"
    if where == "alone":
        script = pathlib.Path(shutil.copy(script, tmp_path / script.name))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
