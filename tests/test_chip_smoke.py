"""Tier-1 for the bring-up plumbing: ``chip_smoke.py`` itself (run the
way an operator runs it, as a subprocess), the compile-cache helper,
the apps' device header / ``--backend`` refusal / kernel-mode record,
and the native loader's source stamp.

The chip is not here: what these pin is that the script's legs and
checks work at toy sizes when the operator TYPES ``--platform cpu
--tiny``, and that without that opt-in a machine with no accelerator
gets a non-zero exit naming the platform and no result line.
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from hpc_patterns_tpu import compile_cache

REPO = Path(__file__).resolve().parent.parent
SMOKE = REPO / "chip_smoke.py"


def _run_smoke(args, cwd=REPO, script=SMOKE, timeout=300):
    env = dict(os.environ)
    # the suite's own warm cache, placed from outside the way a machine
    # that keeps one would
    env[compile_cache.ENV_CACHE_DIR] = jax.config.jax_compilation_cache_dir
    return subprocess.run([sys.executable, str(script), *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


class TestChipSmokeScript:
    def test_tiny_cpu_run_passes_every_one_chip_leg(self, tmp_path):
        default = compile_cache.DEFAULT_CACHE_DIR
        before = set(os.listdir(default)) if default.is_dir() else set()
        r = _run_smoke(["--platform", "cpu", "--tiny", "--out",
                        str(tmp_path)])
        assert r.returncode == 0, r.stdout + r.stderr
        # the cache was placed from outside (_run_smoke): no leg may
        # have written to the in-checkout default as well
        after = set(os.listdir(default)) if default.is_dir() else set()
        assert after == before
        lines = r.stdout.strip().splitlines()
        # versions and device first, one PASS per leg, the result last
        assert lines[0].startswith("chip_smoke: jax=")
        assert "platform=cpu" in lines[0]
        passed = [l.split()[1] for l in lines if l.startswith("PASS ")]
        assert passed == ["train", "serve", "concurrency", "sweep",
                          "allreduce_ring", "allreduce_collective",
                          "interop"]
        assert not [l for l in lines if l.startswith("FAIL")]
        assert json.loads(lines[-1]) == {
            "ok": True,
            "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
        # every leg's log opens with the device it ran on and closes
        # with the mode each Pallas kernel was traced in
        recs = [json.loads(l) for l in
                (tmp_path / "train.jsonl").read_text().splitlines()]
        assert recs[0]["kind"] == "device"
        assert recs[0]["platform"] == "cpu"
        modes = [r for r in recs if r["kind"] == "kernels"][-1]["modes"]
        fwd = modes["flash_attention.fwd"]
        assert fwd["interpret"] >= 1 and fwd["compiled"] == 0

    def test_without_the_opt_in_a_cpu_machine_fails_naming_it(
            self, tmp_path):
        # conftest pins JAX_PLATFORMS=cpu for this process tree: exactly
        # the sandbox the driver's must-fail run happens in
        r = _run_smoke(["--out", str(tmp_path)])
        assert r.returncode != 0
        assert "platform is 'cpu', not 'tpu'" in r.stdout
        assert '"ok"' not in r.stdout

    def test_fewer_chips_than_asked_is_a_problem(self):
        sys.path.insert(0, str(REPO))
        import chip_smoke

        one = {"platform": "tpu", "device_kind": "TPU v5 lite",
               "device_count": 1}
        assert chip_smoke.header_problem(one, "tpu", 1) is None
        assert "--chips 4" in chip_smoke.header_problem(one, "tpu", 4)
        assert "'tpu'" in chip_smoke.header_problem(one, "cpu", 1)

    def test_alone_in_a_directory_it_fails_without_a_result(self,
                                                            tmp_path):
        lone = tmp_path / "chip_smoke.py"
        shutil.copy(SMOKE, lone)
        r = _run_smoke(["--platform", "cpu", "--tiny"], cwd=tmp_path,
                       script=lone)
        assert r.returncode != 0
        assert '"ok"' not in r.stdout
        assert "no hpc_patterns_tpu package" in r.stdout


class TestCompileCacheHelper:
    def test_environment_wins_and_subdir_is_ignored(self, monkeypatch,
                                                    tmp_path):
        monkeypatch.setenv(compile_cache.ENV_CACHE_DIR, str(tmp_path))
        assert compile_cache.cache_dir() == tmp_path
        assert compile_cache.cache_dir("cpu-abc") == tmp_path

    def test_default_is_the_fixed_in_checkout_path(self, monkeypatch):
        monkeypatch.delenv(compile_cache.ENV_CACHE_DIR, raising=False)
        assert compile_cache.cache_dir() == REPO / ".cache" / "jax"
        assert (compile_cache.cache_dir("cpu-abc")
                == REPO / ".cache" / "jax" / "cpu-abc")

    def test_enable_is_once_per_process(self):
        # conftest enabled it; a later call must not switch a cache
        # that was turned off on purpose back on (test_graft_entry)
        before = jax.config.jax_compilation_cache_dir
        try:
            jax.config.update("jax_compilation_cache_dir", None)
            compile_cache.enable()
            assert jax.config.jax_compilation_cache_dir is None
        finally:
            jax.config.update("jax_compilation_cache_dir", before)



class TestBackendRefusal:
    def test_backend_tpu_on_a_cpu_machine_is_a_failure(self, capsys):
        from hpc_patterns_tpu.apps import serve_app

        assert serve_app.main(["--backend", "tpu"]) == 1
        out = capsys.readouterr().out
        assert "ERROR: --backend tpu" in out
        assert "FAILURE" in out

    def test_device_discovery_of_a_missing_platform_is_a_failure(
            self, capsys):
        from hpc_patterns_tpu.apps import allreduce_app

        assert allreduce_app.main(["--backend", "tpu", "-p", "3"]) == 1
        out = capsys.readouterr().out
        assert "ERROR: no devices for platform prefix 'tpu'" in out
        assert "FAILURE" in out


class TestNativeSourceStamp:
    def test_a_library_from_other_sources_is_never_loaded(
            self, monkeypatch, tmp_path):
        from hpc_patterns_tpu.interop import native

        if not (native.available() or native.build()):
            pytest.skip("native library cannot be built here")
        good = native._SO.read_bytes()
        stamp = native.hashlib.sha256(
            (native._NATIVE_DIR / "hpcpat.cpp").read_bytes()
        ).hexdigest()[:16].encode()
        assert stamp in good
        stale = tmp_path / "libhpcpat.so"
        stale.write_bytes(good.replace(stamp, b"0" * 16))
        monkeypatch.setattr(native, "_SO", stale)
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_load_failed", False)
        assert not native.available()
