"""chip_smoke.py and the compile-cache placement every entry point uses.

The smoke script's phases run here at a tiny size on the CPU (Pallas in
interpret mode): the serving checks, the pallas-vs-numpy window identity
over the spool and the Algorithm 2 comparison are the same code the chip
runs at full width.  The script itself refuses to run without a TPU.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _run(argv, env=None, cwd=REPO):
    env = {k: v for k, v in (env or os.environ).items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    return subprocess.run([sys.executable, *argv], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


class TestNoChip:
    def test_refuses_without_tpu(self):
        p = _run(["chip_smoke.py"], env={**os.environ,
                                         "JAX_PLATFORMS": "cpu"})
        assert p.returncode != 0
        assert "needs a TPU" in p.stderr
        assert '"ok"' not in p.stdout


class TestPhasesAtSmokeSize:
    def test_serve_then_online_analysis(self, tmp_path):
        spool = str(tmp_path / "spool")
        tp = chip_smoke.serve_phase(spool, "h2o-danube-3-4b", smoke=True,
                                    seed=0)
        assert tp["requests_completed"] == 4
        assert tp["tokens_decode"] == 4 * 16
        assert tp["prefill_tok_per_s"] > 0 and tp["decode_tok_per_s"] > 0
        assert chip_smoke.online_phase(spool) >= 4

    def test_algo2_pallas_equals_numpy(self):
        fast, ref, _, _ = chip_smoke.analyzer_phase(2048, 128)
        assert ref.exists and ref.ccrs == fast.ccrs
        assert fast.fetch_stats["device_calls"] > 0
        assert ref.fetch_stats["device_calls"] == 0

    def test_failed_check_raises(self):
        with pytest.raises(RuntimeError, match="check failed: x"):
            chip_smoke.require(False, "x")


_CACHE_PROBE = (
    "import jax, jax.numpy as jnp\n"
    "from repro.compile_cache import use_compile_cache\n"
    "print(use_compile_cache())\n"
    "jax.jit(lambda x: x * 3 + 1)(jnp.arange(5.0)).block_until_ready()\n")


class TestCompileCache:
    def _probe(self, **env):
        base = {k: v for k, v in os.environ.items()
                if k != "JAX_COMPILATION_CACHE_DIR"}
        return subprocess.run(
            [sys.executable, "-c", _CACHE_PROBE], capture_output=True,
            text=True, timeout=120,
            env={**base, "PYTHONPATH": os.path.join(REPO, "src"),
                 "JAX_PLATFORMS": "cpu",
                 "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0", **env})

    def test_env_dir_is_used(self, tmp_path):
        d = str(tmp_path / "cache")
        p = self._probe(JAX_COMPILATION_CACHE_DIR=d)
        assert p.returncode == 0, p.stderr
        assert p.stdout.strip() == d
        assert any(f.startswith("jit_") for f in os.listdir(d))

    def test_default_is_fixed_path_in_checkout(self):
        from repro.compile_cache import DEFAULT_CACHE_DIR
        assert DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
        p = self._probe()
        assert p.returncode == 0, p.stderr
        assert p.stdout.strip() == DEFAULT_CACHE_DIR
        assert any(f.startswith("jit_")
                   for f in os.listdir(DEFAULT_CACHE_DIR))
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
