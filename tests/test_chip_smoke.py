"""``chip_smoke.py`` rehearsed on the CPU, and the launcher repairs it
relies on.

The script itself refuses every platform but the TPU; its phases take the
platform check as an argument, so the tests run them here at the reduced
configuration with the check replaced.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402
from repro.configs import reduced_config  # noqa: E402

pytestmark = pytest.mark.timeout(300)


def _cpu_env(**extra) -> dict:
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("REPRO_FORCE_PALLAS_INTERPRET", None)
    return {**env, **extra}


def test_serve_phase_runs_at_reduced_size():
    """The one-chip phase end to end: logits check, two waves through
    ServeEngine with greedy and sampled requests joining mid-flight."""
    res = chip_smoke.serve_phase(
        reduced_config(chip_smoke.ARCH), n_slots=4, max_seq=128, block_size=16,
        n_requests=8, prompt_lens=(12, 20), gen=(32, 40, 48),
        check=chip_smoke.device_info,
    )
    assert res["logits_rel_diff"] <= chip_smoke.LOGITS_TOL
    assert res["tokens"] == sum((32, 40, 48)[i % 3] for i in range(8))
    assert res["device"]["platform"] == jax.default_backend()


def test_smoke_refuses_the_cpu_and_prints_no_result():
    r = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")], env=_cpu_env(),
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout


def test_smoke_refuses_interpret_mode_pallas():
    r = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), "--chips", "4"],
        env=_cpu_env(REPRO_FORCE_PALLAS_INTERPRET="1"),
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert r.returncode != 0
    assert "REPRO_FORCE_PALLAS_INTERPRET" in r.stderr
    assert '"ok"' not in r.stdout


TRAIN_SCRIPT = textwrap.dedent(
    """
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, os.getcwd())
    import chip_smoke
    from repro.configs import reduced_config

    res = chip_smoke.train_phase(
        reduced_config("deepseek-7b"), steps=5, batch=8, seq=32, microbatches=2,
        fail_at=2, check=chip_smoke.device_info,
        launcher_args=("--arch", "deepseek-7b", "--reduced"),
    )
    assert len(res["losses_22"]) == 2 and len(res["losses_12"]) == 3, res
    print("TRAIN_PHASE_OK")
    """
)


def test_train_phase_on_four_virtual_devices():
    """The four-chip phase on four virtual host devices: launcher run on
    (2, 2) with a live re-mesh to (1, 2), losses against (1, 4)."""
    r = subprocess.run(
        [sys.executable, "-c", TRAIN_SCRIPT], env=_cpu_env(),
        capture_output=True, text=True, timeout=280, cwd=REPO,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout[-3000:]}\nstderr:\n{r.stderr[-3000:]}"
    assert "TRAIN_PHASE_OK" in r.stdout


@pytest.fixture()
def restore_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def test_launcher_fail_at_needs_several_devices(capsys, monkeypatch):
    """A simulated rank loss on one device is an argument error, not a
    silent carry-on."""
    from repro.launch import train

    # the test process may hold several virtual host devices: show it one
    monkeypatch.setattr(train.jax, "devices", lambda *a: jax.local_devices()[:1])
    with pytest.raises(SystemExit) as e:
        train.main(["--reduced", "--steps", "2", "--fail-at", "1:1"])
    assert e.value.code == 2
    assert "--fail-at" in capsys.readouterr().err


def test_launcher_layers_cuts_depth_only(restore_cache_dir):
    from repro.launch import train

    out = train.main([
        "--reduced", "--layers", "1", "--steps", "2", "--batch", "2", "--seq", "16",
        "--microbatches", "1", "--log-every", "0",
    ])
    assert out["final_step"] == 2 and len(out["losses"]) == 2


def test_compile_cache_honours_env_then_checkout(monkeypatch, tmp_path, restore_cache_dir):
    from repro.launch.compile_cache import enable_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert enable_compile_cache() == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")
