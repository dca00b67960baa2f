"""Concurrency suite tests (C1-C4, C12).

The reference's own test is performance-property-based (overlap speedup,
SURVEY.md §4.3) — inherently timing-dependent, so on the CPU test mesh we
assert *mechanics and correctness* (kernel math, command lifecycle, mode
dispatch, autotuner behavior, verdict wiring) and leave the overlap PASS
claim to real-TPU runs (``chip_smoke.py``'s concurrency leg; no benchmark
cell holds the number yet: PERF.md section 7, ``overlap-1chip``).
"""

import json
import numpy as np
import pytest

import jax.numpy as jnp

from hpc_patterns_tpu.concurrency import autotune, commands, engine, kernels


class TestBusyWaitKernel:
    def test_matches_reference_recurrence(self):
        x = jnp.full((8, 128), 2.0, jnp.float32)
        got = kernels.busy_wait(x, 3)
        want = kernels.busy_wait_reference(x, 3)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)

    def test_tripcount_is_runtime_scalar_no_recompile(self):
        x = jnp.full((8, 128), 2.0, jnp.float32)
        a = kernels.busy_wait(x, 1)
        b = kernels.busy_wait(x, 5)
        # different trips must give different results (the autotuner's
        # core assumption: duration/result depend on the runtime scalar)
        assert not np.array_equal(np.asarray(a), np.asarray(b))
        assert kernels._busy_wait_call._cache_size() <= 2

    def test_compute_buffer_tileable(self):
        for n in (1, 100, 8 * 128, 10_000):
            buf = kernels.compute_buffer(n)
            assert buf.shape[1] == 128 and buf.shape[0] % 8 == 0
            assert buf.size >= n


class TestCommands:
    @pytest.mark.parametrize("kind", ["C", "M2D", "D2M"])
    def test_lifecycle(self, kind):
        cmd = commands.make_command(kind, copy_elements=1 << 10, tripcount=2)
        assert cmd.name == kind
        for _ in range(3):  # repeat submissions must do fresh work
            cmd.submit()
            cmd.block()
        assert cmd.nbytes > 0

    def test_block_before_submit_is_noop(self):
        cmd = commands.make_command("M2D", copy_elements=1 << 8)
        cmd.block()

    def test_unknown_kind(self):
        with pytest.raises(ValueError, match="unknown command"):
            commands.make_command("H2H")


class TestEngine:
    def _cmds(self):
        return [
            commands.make_command("C", tripcount=2),
            commands.make_command("M2D", copy_elements=1 << 10),
            commands.make_command("D2M", copy_elements=1 << 10),
        ]

    def test_serial_records_per_command(self):
        res = engine.bench("serial", self._cmds(), repetitions=2, warmup=1)
        assert res.mode == "serial"
        assert len(res.per_command) == 3
        assert res.best_serial_total_s > 0
        assert len(res.total.times_s) == 2

    @pytest.mark.parametrize("mode", ["async", "threads"])
    def test_concurrent_modes(self, mode):
        res = engine.bench(mode, self._cmds(), repetitions=2, warmup=1)
        assert res.per_command is None
        assert res.total.min_s > 0
        with pytest.raises(ValueError):
            res.best_serial_total_s

    @pytest.mark.parametrize(
        "alias,canonical",
        [("out_of_order", "async"), ("in_order", "async"),
         ("nowait", "async"), ("host_threads", "threads")],
    )
    def test_reference_mode_aliases(self, alias, canonical):
        assert engine.canonical_mode(alias) == canonical

    def test_unknown_mode(self):
        with pytest.raises(ValueError, match="unknown mode"):
            engine.canonical_mode("warp_speed")

    def test_empty_commands(self):
        with pytest.raises(ValueError):
            engine.bench("async", [])


class TestAutotune:
    def test_balance_shrinks_slower_direction(self):
        m2d, d2m, info = autotune.balance_copy_sizes(1 << 12, 1 << 12)
        assert m2d <= 1 << 12 and d2m <= 1 << 12
        assert min(m2d, d2m) >= 1 << 10  # floor respected
        assert info["t_m2d_s"] > 0 and info["t_d2m_s"] > 0

    def test_tune_tripcount_scales_toward_target(self):
        trip, info = autotune.tune_tripcount(
            5e-3, probe_tripcount=8, compute_elements=8 * 128
        )
        assert trip >= 1
        assert info["tripcount"] == trip
        # longer targets must not yield smaller tripcounts
        trip_big, _ = autotune.tune_tripcount(
            5e-2, probe_tripcount=8, compute_elements=8 * 128
        )
        assert trip_big >= trip / 4  # generous: timing noise on shared CI

    def test_rejects_nonpositive_target(self):
        with pytest.raises(ValueError):
            autotune.tune_tripcount(0.0)


class TestApps:
    def test_concurrency_app_serial(self, capsys):
        from hpc_patterns_tpu.apps import concurrency_app

        code = concurrency_app.main(
            ["serial", "C", "M2D", "--tripcount", "2",
             "--copy-elements", "1024", "--repetitions", "2", "--warmup", "1"]
        )
        out = capsys.readouterr().out
        assert code == 0
        assert "SUCCESS" in out

    def test_concurrency_app_async_runs_to_verdict(self, capsys):
        from hpc_patterns_tpu.apps import concurrency_app

        code = concurrency_app.main(
            ["async", "C", "M2D", "--tripcount", "2",
             "--copy-elements", "1024", "--repetitions", "2", "--warmup", "1"]
        )
        out = capsys.readouterr().out
        assert code in (0, 1)  # overlap not guaranteed on CPU interpret path
        assert ("SUCCESS" in out) or ("FAILURE" in out)
        assert "speedup=" in out

    def test_sweep_emits_summary(self, capsys, tmp_path):
        from hpc_patterns_tpu.apps import sweep

        log = tmp_path / "run.jsonl"
        sweep.main(
            ["--modes", "async", "--tripcount", "2", "--copy-elements", "1024",
             "--repetitions", "1", "--warmup", "1", "--log", str(log)]
        )
        out = capsys.readouterr().out
        assert "SUCCESS count:" in out and "FAILURE count:" in out
        assert log.exists() and log.read_text().strip()

    def test_profiling_flag_produces_trace(self, tmp_path, capsys):
        from hpc_patterns_tpu.apps import concurrency_app

        tdir = tmp_path / "trace"
        code = concurrency_app.main(
            ["async", "C", "--tripcount", "2", "--repetitions", "1",
             "--warmup", "1", "--enable_profiling", "--trace-dir", str(tdir)]
        )
        assert code in (0, 1)
        out = capsys.readouterr().out
        assert "profiler trace:" in out
        assert any(tdir.rglob("*")), "trace dir should contain artifacts"


class TestOnchipEngine:
    """run_onchip's flow, CPU-testable via stubbed measurements (the real
    kernels only time meaningfully on hardware — bench/app runs cover
    that); attribution, verdicts, and autotune wiring are logic."""

    def _drive(self, monkeypatch, tmp_path, argv, times):
        import jax.numpy as jnp

        from hpc_patterns_tpu.apps import concurrency_app
        from hpc_patterns_tpu.concurrency import pipeline
        from hpc_patterns_tpu.harness import RunLog

        monkeypatch.setattr(
            pipeline, "per_pass_seconds",
            lambda x, m, t, **kw: times[m],
        )
        monkeypatch.setattr(
            pipeline, "make_hbm_array",
            lambda *a, **kw: jnp.zeros((2, 8, 128), jnp.float32),
        )
        log_path = tmp_path / "run.jsonl"
        args = concurrency_app.build_parser().parse_args(
            [*argv, "--log", str(log_path)]
        )
        log = RunLog(str(log_path))
        mode = "serial" if argv[0] == "serial" else "async"
        code = concurrency_app.run_onchip(args, log, mode)
        records = [json.loads(line) for line in
                   log_path.read_text().splitlines()]
        return code, records

    def test_attribution_not_swapped(self, monkeypatch, tmp_path):
        # distinct baseline times: the copy must land on M2D, not C
        code, records = self._drive(
            monkeypatch, tmp_path, ["async", "C", "M2D"],
            {"dma": 10e-6, "compute": 14e-6, "serial": 24e-6,
             "overlap": 15e-6},
        )
        assert code == 0
        result = [r for r in records if r.get("kind") == "result"][-1]
        assert result["commands"] == ["M2D", "C"]
        assert result["per_command_us"] == [10.0, 14.0]
        assert result["resources"] == ["hbm", "core"]

    def test_shared_resource_pair_passes_at_unity(self, monkeypatch, tmp_path):
        # two DMA streams share HBM bandwidth: ~sum-of-times concurrent
        # time passes (floor = sum), the naive 2x bar is never applied
        code, records = self._drive(
            monkeypatch, tmp_path, ["async", "M2D", "D2M"],
            {"dma": 10e-6, "dma_out": 10e-6, "pair_serial": 21e-6,
             "pair_overlap": 19e-6},
        )
        assert code == 0

    def test_distinct_resources_demand_overlap(self, monkeypatch, tmp_path):
        # C vs copy on separate hardware: no overlap -> FAILURE
        code, _ = self._drive(
            monkeypatch, tmp_path, ["async", "C", "M2D"],
            {"dma": 10e-6, "compute": 10e-6, "serial": 20e-6,
             "overlap": 20e-6},
        )
        assert code == 1

    def test_serial_mode_skips_concurrent_measurement(self, monkeypatch,
                                                      tmp_path):
        # the overlap mode must never be measured in serial mode
        code, records = self._drive(
            monkeypatch, tmp_path, ["serial", "C", "M2D"],
            {"dma": 10e-6, "compute": 10e-6},  # no serial/overlap entries
        )
        assert code == 0

    def test_cc_pair_passes_without_overlap(self, monkeypatch, tmp_path):
        # two chains serialize on the one core: the two-chain kernel
        # takes ~2x a single chain, speedup ~1.0 vs the resource floor
        code, _ = self._drive(
            monkeypatch, tmp_path, ["async", "C", "C"],
            {"compute": 10e-6, "compute2": 21e-6},
        )
        assert code == 0


def test_balance_tripcount_clamps_runaway():
    from hpc_patterns_tpu.concurrency import pipeline

    # absurdly fast compute probe: trips must clamp, not explode
    trips, t = pipeline.balance_tripcount(
        lambda m, t: 1e-9, 1.0, "compute", 64, max_trips=4096
    )
    assert trips <= 4096
