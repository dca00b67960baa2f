"""Tests: halo exchange + stencil app, checkpoint/resume, trainer app."""

import numpy as np
import pytest

import jax

from jax import shard_map
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from hpc_patterns_tpu.comm import halo


class TestHaloExchange:
    def test_ghost_rows_match_neighbors(self, mesh8):
        n = 32  # 4 rows per rank
        x = jnp.arange(n, dtype=jnp.float32)
        padded = jax.jit(
            shard_map(
                lambda u: halo.halo_exchange(u, "x")[None],
                mesh=mesh8, in_specs=P("x"), out_specs=P("x", None),
            )
        )(x)
        padded = np.asarray(padded)  # (8, 6): halo+4+halo per rank
        for r in range(8):
            lo, hi = r * 4, (r + 1) * 4
            want = np.concatenate(
                [[(lo - 1) % n], np.arange(lo, hi), [hi % n]]
            ).astype(np.float32)
            np.testing.assert_array_equal(padded[r], want)

    def test_halo_validation(self, mesh8):
        with pytest.raises(ValueError, match="halo"):
            halo.halo_exchange(jnp.zeros((4, 2)), "x", halo=0)

    def test_stencil_app_passes(self, capsys):
        from hpc_patterns_tpu.apps import stencil_app

        code = stencil_app.main(
            ["-p", "10", "--steps", "8", "--repetitions", "1", "--warmup", "1"]
        )
        out = capsys.readouterr().out
        assert code == 0, out
        assert "SUCCESS" in out and "dense-match=True" in out


class TestCheckpoint:
    def test_roundtrip_sharded(self, tmp_path, mesh_dp_sp_tp):
        from hpc_patterns_tpu.models import TransformerConfig
        from hpc_patterns_tpu.models.train import init_train_state
        from hpc_patterns_tpu.utils.checkpoint import (
            latest_step,
            restore_checkpoint,
            save_checkpoint,
        )

        cfg = TransformerConfig(vocab=64, d_model=32, n_heads=8, n_layers=2,
                                d_ff=64, max_seq=32, attention="ring")
        params, opt = init_train_state(jax.random.PRNGKey(0), cfg, mesh_dp_sp_tp)
        save_checkpoint(tmp_path, params, opt, step=3)
        assert latest_step(tmp_path) == 3
        r_params, r_opt, step = restore_checkpoint(tmp_path, params, opt)
        assert step == 3
        a = np.asarray(jax.device_get(params["layers"]["wqkv"]))
        b = np.asarray(jax.device_get(r_params["layers"]["wqkv"]))
        np.testing.assert_array_equal(a, b)
        # restored arrays land sharded, same spec
        assert (
            r_params["layers"]["wqkv"].sharding.spec
            == params["layers"]["wqkv"].sharding.spec
        )

    def test_restore_missing(self, tmp_path):
        from hpc_patterns_tpu.utils.checkpoint import restore_checkpoint

        with pytest.raises(FileNotFoundError):
            restore_checkpoint(tmp_path / "nope", {}, {})


class TestTrainApp:
    def test_single_device_run(self, capsys):
        from hpc_patterns_tpu.apps import train_app

        code = train_app.main(
            ["--steps", "4", "--batch", "4", "--seq", "16", "--d-model", "32",
             "--n-layers", "1", "--n-heads", "4", "--vocab", "64"]
        )
        out = capsys.readouterr().out
        assert code == 0, out
        assert "SUCCESS" in out and "tok/s" in out

    @pytest.mark.slow  # unrolled-1F1B compile dominates (~1 min)
    def test_pp_run(self, capsys):
        from hpc_patterns_tpu.apps import train_app

        code = train_app.main(
            ["--steps", "3", "--batch", "4", "--seq", "8", "--d-model", "16",
             "--n-layers", "2", "--n-heads", "2", "--vocab", "32",
             "--pp", "2", "--microbatches", "2"]
        )
        out = capsys.readouterr().out
        assert code == 0, out
        assert "1f1b" in out and "SUCCESS" in out

    @pytest.mark.slow  # unrolled-1F1B compile dominates (~1 min)
    def test_pp_chunked_loss_run(self, capsys):
        # --pp x --loss-chunk trains: the pipeline loss head computes
        # the chunked (logits-free) NLL per microbatch
        from hpc_patterns_tpu.apps import train_app

        code = train_app.main(
            ["--steps", "3", "--batch", "4", "--seq", "8", "--d-model", "16",
             "--n-layers", "2", "--n-heads", "2", "--vocab", "32",
             "--pp", "2", "--microbatches", "2", "--loss-chunk", "8"]
        )
        out = capsys.readouterr().out
        assert code == 0, out
        assert "1f1b" in out and "SUCCESS" in out

    @pytest.mark.slow  # unrolled-1F1B compile dominates
    def test_pp_fsdp_run(self, capsys):
        # --pp x --fsdp: ZeRO-3 stage params through the 1F1B schedule
        from hpc_patterns_tpu.apps import train_app

        code = train_app.main(
            ["--steps", "3", "--batch", "4", "--seq", "8", "--d-model",
             "16", "--n-layers", "2", "--n-heads", "2", "--vocab", "32",
             "--pp", "2", "--fsdp", "2", "--microbatches", "2"]
        )
        out = capsys.readouterr().out
        assert code == 0, out
        assert "fsdp=2" in out and "SUCCESS" in out

    def test_pp_offload_opt_gated_on_cpu(self, capsys):
        # --pp x --offload-opt: composes (no rejection); on a CPU
        # backend the offload itself is gated with the same note as the
        # sharded-train path
        from hpc_patterns_tpu.apps import train_app

        code = train_app.main(
            ["--steps", "2", "--batch", "4", "--seq", "8", "--d-model",
             "16", "--n-layers", "2", "--n-heads", "2", "--vocab", "32",
             "--pp", "2", "--microbatches", "2", "--offload-opt"]
        )
        out = capsys.readouterr().out
        assert code == 0, out
        assert "ignoring" in out and "SUCCESS" in out

    def test_diverged_run_halts_early_and_fails(self, capsys, tmp_path):
        import os

        from hpc_patterns_tpu.apps import train_app

        code = train_app.main(
            ["--steps", "6", "--batch", "4", "--seq", "16", "--d-model",
             "32", "--n-layers", "1", "--n-heads", "4", "--vocab", "64",
             "--lr", "1e30", "--checkpoint-dir", str(tmp_path)]
        )
        out = capsys.readouterr().out
        assert code == 1
        assert "non-finite loss" in out and "halting early" in out
        assert "FAILURE" in out
        # a diverged run must never persist its NaN state
        assert not os.listdir(tmp_path)

    @pytest.mark.parametrize("dp,tp", [("2", "4"), ("-1", "2")])
    def test_dcn_dp_mesh(self, capsys, monkeypatch, dp, tp):
        # dp across synthetic slices, tp within one (make_hybrid_mesh);
        # the -1/tp=2 case uses only part of each slice, so the device
        # pick must be per-slice, never a flat prefix. Slices come from
        # the production env override (no monkeypatched grouping) — the
        # same protocol the cross-process launch test drives for real
        from hpc_patterns_tpu import topology
        from hpc_patterns_tpu.apps import train_app

        monkeypatch.setenv(topology.ENV_SLICE_GROUPING, "devices:4")
        code = train_app.main(
            ["--steps", "2", "--batch", "4", "--seq", "16", "--d-model",
             "32", "--n-layers", "1", "--n-heads", "4", "--vocab", "64",
             "--dp", dp, "--tp", tp, "--dcn-dp"]
        )
        out = capsys.readouterr().out
        assert code == 0, out
        assert "SUCCESS" in out

    def test_dcn_dp_guards(self, capsys):
        from hpc_patterns_tpu.apps import train_app

        # dp mismatched to the (single) slice count: clear error
        code = train_app.main(
            ["--steps", "1", "--batch", "2", "--seq", "16", "--d-model",
             "32", "--n-layers", "1", "--n-heads", "4", "--vocab", "64",
             "--dp", "2", "--tp", "4", "--dcn-dp"]
        )
        out = capsys.readouterr().out
        assert code == 1 and "slice count" in out
        # the same slice-count guard holds on the pp path (pp x dcn-dp
        # COMPOSES since round 4 — only the dp mismatch errors)
        assert train_app.main(["--pp", "2", "--dcn-dp", "--dp", "2",
                               "--n-layers", "2"]) == 1
        out = capsys.readouterr().out
        assert "slice count" in out

    def test_pp_rejects_sp_and_tp_moe(self, capsys):
        # --pp composes with --tp since round 5; sp/ep inside stages
        # and tp with MoE stages still reject
        from hpc_patterns_tpu.apps import train_app

        code = train_app.main(["--pp", "2", "--sp", "2"])
        out = capsys.readouterr().out
        assert code == 1
        assert "no sp/ep axes inside pipeline stages" in out
        code = train_app.main(["--pp", "2", "--tp", "2", "--n-experts",
                               "2"])
        out = capsys.readouterr().out
        assert code == 1
        assert "MoE" in out

    def test_pp_tp_trains(self, capsys):
        # Megatron tp inside pipeline stages through the CLI: loss
        # falls, SUCCESS verdict, tp in the run label
        from hpc_patterns_tpu.apps import train_app

        code = train_app.main(
            ["--backend", "cpu", "--pp", "2", "--tp", "2", "--steps", "3",
             "--batch", "4", "--seq", "16", "--d-model", "32",
             "--n-heads", "4", "--n-layers", "4", "--vocab", "64",
             "--microbatches", "2"]
        )
        out = capsys.readouterr().out
        assert code == 0, out
        assert "tp=2" in out and "SUCCESS" in out

    def test_mesh_run_with_resume(self, capsys, tmp_path):
        from hpc_patterns_tpu.apps import train_app

        code = train_app.main(
            ["--steps", "3", "--batch", "4", "--seq", "16", "--d-model", "32",
             "--n-layers", "1", "--n-heads", "8", "--vocab", "64",
             "--dp", "2", "--sp", "2", "--tp", "2", "--attention", "ring",
             "--resume-check", "--checkpoint-dir", str(tmp_path / "ck")]
        )
        out = capsys.readouterr().out
        assert code == 0, out
        assert "resume-check" in out and "SUCCESS" in out
