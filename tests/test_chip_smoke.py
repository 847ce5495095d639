"""chip_smoke.py on the CPU: its phases at smoke size, Pallas in interpret
mode, for control flow and arguments — and its refusal to run anywhere
but on a TPU.  The launcher's --mesh must fail, not shrink, when the host
cannot build the mesh; the compile cache must land where it is told."""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import CHECKOUT_CACHE, ENV_VAR  # noqa: E402

SMOKE = chip_smoke.Run(smoke=True, requests=4, max_new=4)


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch, tmp_path):
    """Name a cache directory the way a caller would: the launcher then
    leaves JAX's configuration alone, so these in-process runs write no
    cache entries (JAX reads the variable only at import)."""
    monkeypatch.setenv(ENV_VAR, str(tmp_path / "unused"))


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_to_run_without_a_tpu(argv, capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main(argv)
    assert isinstance(exc.value.code, str) and "no TPU" in exc.value.code
    out = capsys.readouterr().out
    assert "platform=cpu" in out
    assert '"ok"' not in out


def test_serve_phases_at_smoke_size(capsys):
    chip_smoke.phase_resident(SMOKE)
    cfg, packed = chip_smoke.build_packed(SMOKE)
    chip_smoke.phase_forward(SMOKE, cfg, packed)
    chip_smoke.phase_paged(SMOKE, 0.4)
    out = capsys.readouterr().out
    assert out.count("forward check") == 2
    assert "paged tokens BIT-EXACT" in out
    assert "async tokens BIT-EXACT" in out


def test_kernel_phase_in_interpret_mode(capsys):
    cases = chip_smoke.kernel_cases("interpret", full=False)
    names = " ".join(c.name for c in cases)
    for kernel in ("qmatmul_f32 ", "qmatmul_f32_blockscale", "qmatmul_int8",
                   "flash_attention", "ssm_scan", "dense3x3", "dw3x3",
                   "pw1x1"):
        assert kernel in names
    chip_smoke.phase_kernels(cases)
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("kernel ")]
    assert len(lines) == len(cases)
    assert all(ln.endswith(" ok") for ln in lines)


def test_kernel_phase_fails_a_kernel_off_its_oracle():
    import jax.numpy as jnp
    bad = chip_smoke.KernelCase("off by one", lambda: jnp.ones(4, jnp.int32),
                                lambda: jnp.zeros(4, jnp.int32), True)
    with pytest.raises(chip_smoke.SmokeFailure, match="off by one"):
        chip_smoke.phase_kernels([bad])


@pytest.mark.parametrize("mesh,match", [("4", "needs 4 devices"),
                                        ("1", "no parameter shards")])
def test_serve_mesh_the_host_cannot_meet_raises(mesh, match):
    with pytest.raises(SystemExit, match=match):
        serve.main(["--smoke", "--budget-mb", "0.05", "--requests", "1",
                    "--max-new", "1", "--mesh", mesh])


_COMPILE_ONE = """
    import jax, jax.numpy as jnp
    from repro.launch.compile_cache import use_compile_cache
    print(use_compile_cache(), jax.config.jax_compilation_cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
"""


def _compile_in_child(env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    env.pop(ENV_VAR, None)
    if env_dir is not None:
        env[ENV_VAR] = env_dir
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(_COMPILE_ONE)],
                         capture_output=True, text=True, timeout=120,
                         env=env)
    assert out.returncode == 0, out.stderr
    return out.stdout.strip().splitlines()[-1].split()


def _entries(path):
    return {f for f in os.listdir(path) if f.endswith("-cache")} \
        if os.path.isdir(path) else set()


def test_compile_cache_goes_where_the_variable_says(tmp_path):
    """JAX itself reads the variable; the helper sets no other directory."""
    where = str(tmp_path / "cache")
    assert _compile_in_child(where) == [where, where]
    assert _entries(where)


def test_compile_cache_defaults_to_the_checkout():
    assert CHECKOUT_CACHE == type(CHECKOUT_CACHE)(REPO) / ".jax_cache"
    assert _compile_in_child(None) == [str(CHECKOUT_CACHE)] * 2
    assert _entries(CHECKOUT_CACHE)
