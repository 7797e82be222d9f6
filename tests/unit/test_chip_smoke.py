"""chip_smoke.py's contract off the chip, and the compile-cache helper.

The phases themselves only mean something on a TPU (``chiprun``); what the
CPU suite pins is that the script never reports success anywhere else: a
``cpu`` platform is refused before anything is built, and a phase that
fails ends the run with ``ok`` false and a non-zero exit.
"""

import json
import os
import subprocess
import sys

import pytest

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO_ROOT)
import chip_smoke  # noqa: E402

from deepspeed_tpu.utils import compile_cache  # noqa: E402


def test_refuses_to_run_on_cpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""                    # no result of any kind
    assert "needs 1 TPU chip(s), JAX found" in proc.stderr
    assert "deepspeed_tpu" not in proc.stderr   # refused before building


def test_train_kernels_are_names_the_kernel_files_give():
    """The train phase asserts kernels by ``pallas_call`` name: each name
    it waits for is one a kernel file gives (a renamed or merged kernel,
    like the cross-entropy's one backward, must be followed here)."""
    import re
    from deepspeed_tpu.ops.pallas import cross_entropy, flash_attention
    given = set()
    for module in (cross_entropy, flash_attention):
        with open(module.__file__) as f:
            given |= set(re.findall(r'name="(\w+)"', f.read()))
    assert set(chip_smoke.TRAIN_KERNELS) <= given
    assert {n for n in given if n.startswith("ce_")} == {"ce_fwd", "ce_bwd"}
    # Adam is the optax chain in every compiled step: the five kernels of
    # flash and cross-entropy, and no ``fused_adam`` (the NVMe walk's)
    assert sorted(chip_smoke.TRAIN_KERNELS) == [
        "ce_bwd", "ce_fwd", "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]


@pytest.fixture
def off_chip_main(monkeypatch):
    """``main()`` past the platform check, with nothing that outlives the
    test: no compile cache on the CPU backend, the library's log stream
    left where it is."""
    device = {"platform": "tpu", "kind": "rehearsal", "count": 1}
    monkeypatch.setattr(chip_smoke, "device_or_exit", lambda chips: device)
    monkeypatch.setattr(chip_smoke, "library_log_to_stderr", lambda: None)
    monkeypatch.setattr(compile_cache, "use_compile_cache", lambda: "unused")


@pytest.mark.parametrize("failing", ["train", "serve"])
def test_failed_phase_is_never_ok(off_chip_main, monkeypatch, capsys, failing):
    ran = []

    def phase(name):
        def run(seed):
            ran.append(name)
            chip_smoke.check(name != failing, f"injected into {name}")
        return run

    monkeypatch.setattr(chip_smoke, "train_phase", phase("train"))
    monkeypatch.setattr(chip_smoke, "serve_phase", phase("serve"))
    with pytest.raises(chip_smoke.SmokeFailure, match="injected"):
        chip_smoke.main([])
    assert ran[-1] == failing                   # nothing is carried past it
    lines = capsys.readouterr().out.strip().splitlines()
    verdict = json.loads(lines[-1])
    assert verdict["ok"] is False and verdict["failed"] == failing
    assert not any(json.loads(l).get("ok") for l in lines)


def test_four_chips_runs_only_the_sharded_phase(off_chip_main, monkeypatch,
                                                capsys):
    ran = []
    for name in ("train_phase", "serve_phase", "sharded_phase"):
        monkeypatch.setattr(chip_smoke, name,
                            lambda seed, name=name: ran.append(name))
    assert chip_smoke.main(["--chips", "4"]) == 0
    assert ran == ["sharded_phase"]
    assert json.loads(capsys.readouterr().out.strip().splitlines()[-1])["ok"]


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_cache_helper_leaves_config_alone_under_env(cache_config, monkeypatch,
                                                    tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_helper_fixed_path_without_env(cache_config, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fixed = os.path.join(REPO_ROOT, ".jax_cache")
    assert compile_cache.use_compile_cache() == fixed
    assert jax.config.jax_compilation_cache_dir == fixed
    assert compile_cache.use_compile_cache() == fixed   # never pid/time/temp
