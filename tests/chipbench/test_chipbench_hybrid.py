"""The ``serve_hybrid`` driver end to end at a tiny fixture on the CPU, in
``test_chipbench_rehearsal``'s manner (sound, the timed path broken, the
control in the program's place), the counts of ``counts/hybrid.py`` against
hand counts, the weights' rules and the reader this PR brings."""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import run as runlib  # noqa: E402
from chipbench import spans  # noqa: E402
from chipbench import weights_nemotron_h as W  # noqa: E402
from chipbench.counts import hybrid  # noqa: E402
from chipbench.readers import scope_share_of_peak  # noqa: E402
import manifest_rules as rules  # noqa: E402

FIX = "tests/chipbench/fixtures"
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
BENCH = {
    "workloads": [{"name": "tiny-hybrid", "config": "tiny-nemotron",
                   "traffic": "x", "chips": 1,
                   "file": f"{FIX}/tiny-hybrid.json"}],
    "configs": [{"name": "tiny-nemotron",
                 "file": f"{FIX}/tiny-nemotron.json"}],
    "end_to_end": [{"name": n, "unit": u} for n, u in (
        ("ttft_p95_ms", "ms"), ("tpot_p95_ms", "ms"), ("setup_s", "s"))],
    "per_layer": [],
}
REAL = json.loads(
    (ROOT / "chipbench/configs/nemotron3-super-ep4.json").read_text())
TINY = json.loads((ROOT / f"{FIX}/tiny-nemotron.json").read_text())


def drive(control=None):
    return runlib.run_cell(BENCH, "tiny-hybrid", 2**31 + 7, 0.5, False,
                           jax.devices()[:1], PEAKS, control=control,
                           readings=True)


def test_driver_runs_end_to_end_and_proves_correct():
    r = drive()
    assert r["correct"] is True, r["checks"]
    assert set(r["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    assert r["attempted"] > 0 and r["failed"] == 0
    # the route's counters came through as facts: a token's 3 picks fall
    # on 16 experts, 8 of them here
    assert 0 < r["readings"]["moe_local_picks_per_token"] < 3
    assert 0 < r["readings"]["moe_experts_touched_decode"] <= 8
    json.dumps(r)


def test_control_in_the_programs_place_is_not_correct():
    """By the mean and by the tail's share; the program's own numbers ride
    along in the control's readings."""
    r = drive(control="ref-fp8")
    assert r["correct"] is False
    for name in ("served_logit_gap", "served_gap_tail_share"):
        assert r["checks"][name]["value"] > r["checks"][name]["limit"]
    own = r["readings"]["gaps"]["program"]
    assert own["mean"] <= r["checks"]["served_logit_gap"]["limit"]


@pytest.mark.parametrize("slots", ["every", "first"])
def test_token_altered_where_it_is_produced_is_not_correct(monkeypatch,
                                                           slots):
    """Every slot's tokens, and one slot's alone (column 0 of the chunk's
    (step, slot) output)."""
    from hpc_patterns_tpu.models import serving
    real = serving._chunk_step

    def altered(*a, **kw):
        *state, out = real(*a, **kw)
        wrong = (out + 1) % kw["cfg"].vocab
        if slots == "first":
            wrong = out.at[:, 0].set(wrong[:, 0])
        return (*state, wrong)

    monkeypatch.setattr(serving, "_chunk_step", altered)
    r = drive()
    assert r["correct"] is False
    assert r["checks"]["served_gap_widest"]["value"] > \
        r["checks"]["served_gap_widest"]["limit"]


def test_state_taken_at_the_buckets_end_is_not_correct(monkeypatch):
    """The fault the prefill's rule exists for: the state and the
    convolution's tail installed are those after the bucket's padding,
    not those at the prompt's true last position."""
    from hpc_patterns_tpu.models import serving, ssm
    real = ssm.mamba_prefill
    monkeypatch.setattr(
        ssm, "mamba_prefill",
        lambda h, lp, cfg, last_pos=None: real(h, lp, cfg, None))
    serving._prefill_one.clear_cache()
    try:
        assert drive()["correct"] is False
    finally:
        serving._prefill_one.clear_cache()


def test_the_cells_limits_lie_between_their_two_readings():
    """``serve-chat``'s check block as the driver reads it, against the
    chip's readings that ``check.set_from`` records: the program's
    largest pass each limit, the fp8 control's smallest fail the mean and
    the tail's share, a request of wrong tokens fails the widest."""
    from chipbench.drivers import serve_hybrid
    check = json.loads((ROOT / "chipbench/workloads/serve-chat.json")
                       .read_text())["check"]
    passes = lambda judged: [v <= lim for _, v, lim
                             in serve_hybrid.gap_checks(judged, check)]
    program = {"mean": 0.0331, "tail_share": 0.0088, "widest": 1.2312}
    control = {"mean": 0.1635, "tail_share": 0.1041, "widest": 1.1911}
    assert passes(program) == [True, True, True]
    assert passes(control) == [False, False, True]
    assert passes(dict(program, widest=5.8452)) == [True, True, False]
    assert check["tail_above"] == 0.5   # what the tail's readings are of


# -- counts against hand counts -------------------------------------------------

def test_matmul_parameters_are_the_published_layers():
    d = hybrid.dims(REAL)
    assert d["pM"] == 4096 * (8192 + 10240 + 128) + 8192 * 4096 == 109_576_192
    assert d["pA"] == 4096 * (32 + 4) * 128 + 4096 * 4096 == 35_651_584
    assert d["pE"] == 4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376 \
        == 54_525_952
    assert d["pX"] == 2 * 1024 * 2688 == 5_505_024
    assert (d["nM"], d["nA"], d["nE"], d["held"]) == (5, 1, 5, 128)
    assert d["state"] == 128 * 64 * 128


def test_a_prefilled_tokens_operations_follow_the_picks():
    d = hybrid.dims(REAL)
    base = 2 * (5 * d["pM"] + d["pA"] + 5 * d["pE"])
    assert hybrid.token_flops(d, 0.0, 0) == base
    # 5.5 picks a token a layer: 5 layers x 5.5 x 2 x 5,505,024
    assert hybrid.token_flops(d, 5.5, 0) - base == 5 * 5.5 * 2 * d["pX"]
    # one attention layer: 4 c H Dh
    assert hybrid.token_flops(d, 0.0, 100) - base == 4 * 100 * 32 * 128
    flops = hybrid.prefill_flops(REAL, 512, 5.5)
    want = 512 * (hybrid.token_flops(d, 5.5, 256)
                  + 5 * (128 * (1024 + 8192) + 4 * d["state"])) \
        + 2 * 4096 * 32768
    assert flops == want


def _facts(**kw):
    # two admissions and one chunk of two steps (rows 2 and 1) in the trace
    return dict({
        "trace_host_window": (10.0, 14.0),
        "admissions": [(9.0, 512, 300), (11.0, 512, 400), (12.0, 1024, 700)],
        "token_instants": [(400, [11.5, 12.5, 12.5]), (700, [12.2, 12.5])],
    }, **kw)


def test_work_of_the_traced_window_counts_true_tokens_and_reported_picks():
    f = _facts(moe_picks_per_token_prefill=5.0,
               moe_picks_per_token_decode=6.0,
               moe_experts_touched_prefill=120.0,
               moe_experts_touched_decode=40.0)
    assert hybrid.prefill_work(f, REAL, 2) == (
        hybrid.prefill_flops(REAL, 400, 5.0)
        + hybrid.prefill_flops(REAL, 700, 5.0), 0)
    steps = [401, 701, 402]     # contexts of the three decoded tokens
    assert hybrid.decode_work(f, REAL, 1) == (
        sum(hybrid.decode_flops(REAL, c, 6.0) for c in steps), 0)
    d = hybrid.dims(REAL)
    fl, by = hybrid.ssm_step_decode_work(f, REAL, 1)
    assert fl == 5 * 3 * 5 * d["state"]
    assert by == 5 * 3 * (2 * d["state"] * 4 + (10240 + 8192) * 4)
    # grouped products: 20 ragged-dot events = 2 prefills x 5 layers x 2
    fl, by = hybrid.moe_experts_prefill_work(f, REAL, 20)
    assert fl == 2 * (1100 * 5.0 * 5) * d["pX"]
    assert by == 10 * 120 * d["pX"] * 2 + (1100 * 5.0 * 5) * 2 * 1024 * 2
    fl, by = hybrid.moe_experts_decode_work(f, REAL, 20)
    assert fl == 2 * (3 * 6.0 * 5) * d["pX"]
    assert by == (5 * 2) * 40 * d["pX"] * 2 + (3 * 6.0 * 5) * 2 * 1024 * 2
    # the one attention layer's kernels: 2 prefills at their rungs (512,
    # 1024), and the three decoded tokens' contexts; 32 / 2 heads of 128
    fl, by = hybrid.flash_fwd_prefill_work(f, REAL, 2)
    assert fl == 2 * 128 * 32 * (512 ** 2 + 1024 ** 2)
    assert by == sum((2 * T * 32 * 128 + 2 * T * 2 * 128) * 2 + 4 * T * 32
                     for T in (512, 1024))
    fl, by = hybrid.flash_decode_paged_work(f, REAL, 2)
    assert fl == 4 * sum(steps) * 128 * 32
    assert by == (2 * sum(steps) * 2 * 128 + 2 * 3 * 32 * 128) * 2


def test_a_program_without_the_routes_counter_gives_nothing_to_read():
    f = _facts()
    for fn in (hybrid.prefill_work, hybrid.decode_work,
               hybrid.moe_experts_prefill_work,
               hybrid.moe_experts_decode_work):
        assert fn(f, REAL, 2) == (0, 0)


# -- the reader: a scope's work over its own device time ------------------------

def _trace():
    P, L = "/device:TPU:0", "XLA Ops"
    op = lambda name, start, dur, path, line=L: spans.Op(
        P, line, name, start, dur, path)
    return spans.SpanTrace([], [
        op("jit__chunk_step(1)", 0.0, 1.0, "", "XLA Modules"),
        op("%while = ", 0.0, 1.0, "jit(_chunk_step)/while"),
        op("%a = ", 0.1, 0.2, "jit(_chunk_step)/while/body/ssm/step/mul"),
        op("%b = ", 0.4, 0.1,      # the fused pass over S, named by its root
           "jit(_chunk_step)/while/body/ssm/step/state_write/select_n"),
        op("%c = ", 0.6, 0.3, "jit(_chunk_step)/while/body/ssm/conv/add"),
        op("%d = ", 2.0, 0.5, "jit(_other)/ssm/step/mul"),   # outside
    ])


def test_scope_share_reads_a_nested_scopes_self_time(monkeypatch):
    monkeypatch.setattr(hybrid, "ssm_step_decode_work",
                        lambda facts, config, n: (n * 3e10, n * 6e9))
    args = {"scope": "ssm/step", "program": "^jit__chunk_step\\(",
            "counts": "hybrid.ssm_step_decode_work", "bound": "roofline"}
    # 0.3 s of self time under ssm/step in one program; the larger of
    # 3e10 / 1e12 = 0.03 s and 6e9 / 1e11 = 0.06 s
    got = scope_share_of_peak.compute(args, _trace(), {}, REAL, PEAKS)
    assert got == pytest.approx(100 * 0.06 / 0.3)
    assert scope_share_of_peak.compute(
        dict(args, bound="flops"), _trace(), {}, REAL, PEAKS) \
        == pytest.approx(100 * 0.03 / 0.3)
    # a scope that is not there, and a count with nothing to count
    assert scope_share_of_peak.compute(
        dict(args, scope="ssm/scan"), _trace(), {}, REAL, PEAKS) is None
    monkeypatch.setattr(hybrid, "ssm_step_decode_work",
                        lambda facts, config, n: (0, 0))
    assert scope_share_of_peak.compute(args, _trace(), {}, REAL,
                                       PEAKS) is None


# -- the weights' rules ---------------------------------------------------------

def test_an_experts_numbers_do_not_depend_on_who_asks():
    m = W.model_dims(TINY)
    key = W.seed_key(2**31 + 5)
    built = jax.jit(lambda k: W.build(k, m, jnp.float32))(key)
    i = m["pattern"].index("E")
    # the same draws; the scale's multiply may fuse differently from one
    # jit to another (one unit in the last place seen)
    same = lambda a, b: np.testing.assert_allclose(a, b, rtol=3e-7, atol=0)
    for j in range(m["held"]):
        w1, w2 = W.expert(key, m, i, m["held0"] + j)
        same(built["layers"][i]["w1"][j], w1)
        same(built["layers"][i]["w2"][j], w2)
    for name, a in W.layer(key, m, i).items():
        same(built["layers"][i][name], a)
    low = jax.jit(lambda k: W.build(k, m, jnp.bfloat16))(key)
    for name, a in low["layers"][i].items():
        want = jnp.float32 if name in W.FLOAT32_LEAVES else jnp.bfloat16
        assert a.dtype == want, name


def test_configuration_holds_the_catalog_rows_numbers():
    """Every number of the catalog row's ``config`` under the same key,
    but the three that ``reduced`` names; no width among them."""
    pub = REAL["published"]
    assert REAL["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    assert (pub["num_hidden_layers"], pub["n_routed_experts"],
            pub["vocab_size"]) == (88, 512, 131072)
    assert REAL["hybrid_override_pattern"] == \
        pub["hybrid_override_pattern"][:11] == "MEMEMEM*EME"
    assert (REAL["hidden_size"], REAL["moe_latent_size"],
            REAL["moe_intermediate_size"], REAL["num_experts_per_tok"],
            REAL["mamba_num_heads"], REAL["ssm_state_size"]) == (
        4096, 1024, 2688, 22, 128, 128)
    assert any("multi-token-prediction" in d for d in REAL["departures"])
    assert "four chips share each layer" in REAL["deployment"]


def test_reference_imports_nothing_of_the_program():
    for f in ("chipbench/reference/nemotron_h.py",
              "chipbench/weights_nemotron_h.py", "chipbench/counts/hybrid.py"):
        assert "hpc_patterns_tpu" not in (ROOT / f).read_text()


# -- the manifest: this configuration's entries, found by name ------------------

ACCEPTED = {   # the manifest as this configuration found it, by name
    "configs": ["starcoder2-3b", "starcoder2-3b-train",
                "hpcpat-allreduce-np4"],
    "workloads": ["serve-code", "train-4k", "allreduce-sweep"],
    "per_layer": [
        "loadgen_late_p95_ms", "admit_bubble_pct", "queue_p95_ms",
        "prefill_dev_ms", "decode_dev_ms_tok", "prefill_mfu_pct",
        "decode_mfu_pct", "serve_idle_pct", "train_step_p50_ms",
        "train_mfu_pct", "train_peak_hbm_GB", "train_idle_pct",
        "allreduce_small_us", "allreduce_large_ici_pct",
        "allreduce_idle_pct", "round_p50_ms", "admit_host_ms",
        "first_token_wait_ms", "serve_idle_host_pct",
        "serve_idle_readback_pct", "serve_idle_nowork_pct",
        "serve_window_compiles", "flash_train_fwd_roofline",
        "flash_train_bwd_roofline", "train_attn_ms", "train_mlp_ms",
        "train_loss_ms", "train_update_ms", "flash_fwd_prefill_roofline",
        "flash_decode_paged_roofline"],
}
ADDED = {
    "configs": ["nemotron3-super-ep4"],
    "workloads": ["serve-chat", "serve-gen"],
    "per_layer": [
        "hybrid_prefill_mfu_pct", "hybrid_decode_mfu_pct",
        "ssm_scan_prefill_roofline", "ssm_step_decode_roofline",
        "ssm_prefill_ms", "moe_prefill_ms", "ssm_decode_ms_chunk",
        "moe_decode_ms_chunk", "moe_local_picks_per_token",
        "moe_load_max_over_mean", "hybrid_flash_fwd_roofline",
        "hybrid_flash_decode_paged_roofline"],
}


@pytest.mark.parametrize("section", list(ACCEPTED))
def test_this_prs_entries_come_after_the_accepted_ones(section):
    """The accepted names stand first; this configuration's stand
    anywhere after them, and later entries may follow."""
    bench = rules.manifest()
    rules.check_own_after(bench, section, ACCEPTED[section], ADDED[section])
    if section == "workloads":
        rules.check_cell(bench, "serve-chat", "nemotron3-super-ep4", 1)
        rules.check_cell(bench, "serve-gen", "starcoder2-3b", 1)
    elif section == "per_layer":
        for name in ADDED["per_layer"]:
            rules.check_entry(bench, name, cells=["serve-chat"])
        # the dense block's counts are wrong for the patterned model
        rules.check_joins(bench, ACCEPTED["per_layer"], "serve-chat")
