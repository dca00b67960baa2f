"""The ``serve_falcon_h1`` driver end to end at a tiny fixture on the CPU,
in ``test_chipbench_hybrid``'s manner (sound, the timed path broken, the
control in the program's place), the configuration against the catalog's
row, the counts of ``counts/falcon_h1.py`` against hand counts, the
weights' rules, and the reader this PR brings."""

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
from chipbench import weights_falcon_h1 as W  # noqa: E402
from chipbench.counts import falcon_h1 as counts  # noqa: E402
from chipbench.readers import span_attrs  # noqa: E402
import manifest_rules as rules  # noqa: E402

FIX = "tests/chipbench/fixtures"
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
BENCH = {
    "workloads": [{"name": "tiny-assist", "config": "tiny-falcon-h1",
                   "traffic": "x", "chips": 1,
                   "file": f"{FIX}/tiny-assist.json"}],
    "configs": [{"name": "tiny-falcon-h1",
                 "file": f"{FIX}/tiny-falcon-h1.json"}],
    "end_to_end": [{"name": n, "unit": u} for n, u in (
        ("ttft_p95_ms", "ms"), ("tpot_p95_ms", "ms"), ("setup_s", "s"))],
    "per_layer": [],
}
REAL = json.loads(
    (ROOT / "chipbench/configs/falcon-h1-34b-stage.json").read_text())
TINY = json.loads((ROOT / f"{FIX}/tiny-falcon-h1.json").read_text())

# the catalog row's ``config`` (model-configs guide, architectures.jsonl,
# Falcon-H1-34B-Instruct), key for key
PUBLISHED = {
    "attention_bias": False, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804,
    "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 128,
    "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2,
    "mamba_n_groups": 2, "mamba_n_heads": 32,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "model_type": "falcon_h1", "num_attention_heads": 20,
    "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False,
    "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845,
    "tie_word_embeddings": False, "vocab_size": 261120,
}


def drive(control=None):
    return runlib.run_cell(BENCH, "tiny-assist", 2**31 + 7, 0.5, False,
                           jax.devices()[:1], PEAKS, control=control,
                           readings=True)


def test_driver_runs_end_to_end_and_proves_correct():
    r = drive()
    assert r["correct"] is True, r["checks"]
    assert set(r["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["checks"]) == {"served_gap_widest", "served_logit_gap",
                                "requests_failed"}
    json.dumps(r)


def test_control_in_the_programs_place_is_not_correct():
    """By the widest gap and by the mean; the program's own numbers ride
    along in the control's readings."""
    r = drive(control="ref-fp8")
    assert r["correct"] is False
    for name in ("served_gap_widest", "served_logit_gap"):
        assert r["checks"][name]["value"] > r["checks"][name]["limit"]
    own = r["readings"]["gaps"]["program"]
    assert own["widest"] <= r["checks"]["served_gap_widest"]["limit"]


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
    """The state and the convolution's tail installed are those after the
    bucket's padding, not those at the prompt's true last position: in an
    ``H`` layer the attention half is right and the Mamba half is not."""
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
    """``serve-assist``'s check block against the chip's readings that
    ``check.set_from`` records: the program's largest passes each limit
    with room, the fp8 control's smallest fails each; the rate is four
    fifths of the knee that the sweep found."""
    cell = json.loads((ROOT / "chipbench/workloads/serve-assist.json")
                      .read_text())
    check, traffic = cell["check"], cell["traffic"]
    assert 2 * 0.0397 < check["widest_limit"] < 0.2208 / 2
    assert 2 * 0.00029 < check["gap_limit"] < 0.00918 / 2
    assert traffic["rate_rps"] == pytest.approx(0.8 * traffic["knee_rps"])
    assert cell["engine"]["pool_pages"] == (
        cell["engine"]["slots"] * cell["engine"]["pages_per_seq"])


# -- the configuration -----------------------------------------------------------

def test_configuration_holds_the_catalog_rows_numbers_key_for_key():
    assert REAL["reduced"] == ["num_hidden_layers"]
    assert REAL["num_hidden_layers"] == 6
    assert REAL["published"] == {"num_hidden_layers": 72}
    for key, value in PUBLISHED.items():
        if key != "num_hidden_layers":
            assert REAL[key] == value, key
    for key in ("source", "deployment", "precision", "assumed",
                "departures"):
        assert REAL[key], key
    assert "twelve pipeline stages" in REAL["deployment"]
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"]
                 if c["name"] == "falcon-h1-34b-stage")
    assert entry["reduced"] == REAL["reduced"]
    assert REAL["source"].startswith(entry["source"])


def test_the_driver_hands_the_program_the_published_numbers():
    from chipbench.drivers import serve_falcon_h1
    cfg = serve_falcon_h1.model_config(REAL, {"decode_attn": "flash"})
    assert cfg.layer_pattern == "HHHHHH" and cfg.vocab == 261120
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim,
            cfg.d_ff) == (5120, 20, 4, 128, 21504)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups,
            cfg.ssm_conv, cfg.ssm_chunk) == (32, 128, 256, 2, 4, 128)
    assert cfg.rope_theta == 1e11 and cfg.norm_eps == 1e-5
    flat = [PUBLISHED[k] for k in (
        "embedding_multiplier", "attention_in_multiplier", "key_multiplier",
        "attention_out_multiplier", "ssm_in_multiplier")]
    flat += PUBLISHED["ssm_multipliers"] + [PUBLISHED["ssm_out_multiplier"]]
    flat += PUBLISHED["mlp_multipliers"] + [PUBLISHED["lm_head_multiplier"]]
    assert list(cfg.multipliers) == flat and len(flat) == 14


def test_reference_imports_nothing_of_the_program():
    for f in ("chipbench/reference/falcon_h1.py",
              "chipbench/weights_falcon_h1.py",
              "chipbench/counts/falcon_h1.py",
              "chipbench/readers/span_attrs.py"):
        assert "hpc_patterns_tpu" not in (ROOT / f).read_text()


# -- counts against hand counts -------------------------------------------------

def test_parameters_are_the_published_layers():
    d = counts.dims(REAL)
    assert d["pA"] == 5120 * (2560 + 2 * 512) + 2560 * 5120 == 31_457_280
    assert d["pM"] == 5120 * 9248 + 4096 * 5120 == 68_321_280
    assert d["pF"] == 3 * 5120 * 21504 == 330_301_440
    # 430.1 M a layer, its convolution, per-head leaves and norms included
    assert counts.layer_params(REAL) == 430_080_000 + 5 * 5120 + 3 * 32 \
        + 4096 + 2 * 5120 == 430_120_032
    # six layers, the embedding and the head: 10.51 GB in bfloat16
    whole = 6 * counts.layer_params(REAL) + 2 * 261120 * 5120 + 5120
    assert round(whole * 2 / 1e9, 2) == 10.51
    assert counts.weight_bytes_step(REAL) == (
        6 * 430_080_000 + 5120 * 261120) * 2
    assert d["state"] * 4 == 4_194_304 and d["kv_token"] * 6 == 12_288


def _facts():
    # two admissions and one chunk of two steps (rows 2 and 1) in the trace
    return {
        "trace_host_window": (10.0, 14.0),
        "admissions": [(9.0, 512, 300), (11.0, 512, 400), (12.0, 1024, 700)],
        "token_instants": [(400, [11.5, 12.5, 12.5]), (700, [12.2, 12.5])],
    }


def test_work_of_the_traced_window_counts_true_tokens():
    f, d = _facts(), counts.dims(REAL)
    per_token = 2 * (d["pA"] + d["pM"] + d["pF"])
    want = lambda T: (
        T * 6 * (per_token + 4 * (T / 2) * 20 * 128
                 + 128 * (2 * 256 + 4096) + 4 * d["state"])
        + 2 * 5120 * 261120)
    assert counts.prefill_work(f, REAL, 2) == (want(400) + want(700), 0)
    steps = [401, 701, 402]     # contexts of the three decoded tokens
    assert counts.decode_work(f, REAL, 1) == (sum(
        6 * (per_token + 4 * c * 20 * 128 + 5 * d["state"])
        + 2 * 5120 * 261120 for c in steps), 0)
    fl, by = counts.ssm_step_decode_work(f, REAL, 1)
    assert fl == 6 * 3 * 5 * d["state"]
    assert by == 6 * 3 * (2 * d["state"] * 4 + (5120 + 4096) * 4)
    fl, by = counts.ssm_scan_prefill_work(f, REAL, 2)
    assert fl == 6 * 1100 * (128 * (512 + 4096) + 4 * d["state"])
    # the six layers' kernels over the TRUE tokens (not the rungs 512 and
    # 1024), and the three decoded tokens' contexts; 20 / 4 heads of 128
    fl, by = counts.flash_fwd_prefill_work(f, REAL, 12)
    assert fl == 6 * 2 * 128 * 20 * (400 ** 2 + 700 ** 2)
    assert by == 6 * sum((2 * T * 20 * 128 + 2 * T * 4 * 128) * 2
                         + 4 * T * 20 for T in (400, 700))
    fl, by = counts.flash_decode_paged_work(f, REAL, 12)
    assert fl == 6 * 4 * sum(steps) * 128 * 20
    assert by == 6 * (2 * sum(steps) * 4 * 128 + 2 * 3 * 20 * 128) * 2


def test_kv_and_state_weigh_the_same_near_two_thousand_tokens():
    """One row over a chunk of 8 from position 2048: 12,288 B a cached
    token against a row's state read and written, 2 x 6 x (4.19 MB of S +
    30 KB of tail)."""
    got = counts.kv_over_state(REAL, 2048.0, 1.0, 8)
    state_row = 6 * (4_194_304 + 3 * 5120 * 2)
    assert got == pytest.approx(12_288 * (2048 + 3.5) / (2 * state_row))
    assert 0.45 < got < 0.55
    assert counts.kv_over_state(REAL, 0.0, 0.0, 8) is None


# -- the reader: attributes of the program's spans ------------------------------

def _span_trace(attrs_of_each):
    return spans.SpanTrace(
        [spans.Span("main", "serve.round/serve.decode_dispatch", float(i),
                    0.01, a) for i, a in enumerate(attrs_of_each)], [])


def test_span_attrs_sums_the_dispatches_and_hands_them_to_the_count():
    args = json.loads((ROOT / "chipbench/metrics/h1_kv_over_state_bytes.json")
                      .read_text())["args"]
    st = _span_trace([{"chunk": "8", "rows": "2", "ctx_tokens": "3000"},
                      {"chunk": "8", "rows": "1", "ctx_tokens": "1096"}])
    got = span_attrs.compute(args, st, {"chunk": 8}, REAL)
    assert got == counts.kv_over_state(REAL, 4096.0, 3.0, 8)
    # a program that does not stamp the attribute, a trace without spans
    old = _span_trace([{"chunk": "8", "rows": "2"}])
    assert span_attrs.compute(args, old, {"chunk": 8}, REAL) is None
    assert span_attrs.compute(args, _span_trace([]), {"chunk": 8},
                              REAL) is None


# -- the weights' rules ---------------------------------------------------------

def test_a_leafs_numbers_do_not_depend_on_who_asks():
    m = W.model_dims(TINY)
    key = W.seed_key(2**31 + 5)
    built = jax.jit(lambda k: W.build(k, m, jnp.float32))(key)
    # the same draws; the scale's multiply may fuse differently from one
    # jit to another (one unit in the last place seen)
    same = lambda a, b: np.testing.assert_allclose(a, b, rtol=3e-7, atol=0)
    for i in range(m["L"]):
        for name, a in W.layer(key, m, i).items():
            same(built["layers"][i][name], a)
    same(built["embed"], W.embed_block(key, m, 0))
    same(built["lm_head"], W.head_block(key, m, 0))
    low = jax.jit(lambda k: W.build(k, m, jnp.bfloat16))(key)
    for name, a in low["layers"][0].items():
        want = jnp.float32 if name in W.FLOAT32_LEAVES else jnp.bfloat16
        assert a.dtype == want, name


def test_the_vocabulary_comes_in_blocks_that_tile_it():
    """At the published vocabulary the embedding and the head are made 17
    blocks of 15,360 rows at a time (the reference never holds 5.35 GB of
    float32); a block's numbers depend on its index alone."""
    m = W.model_dims(REAL)
    assert (m["Vb"], W.vocab_blocks(m)) == (15360, 17)
    small = dict(W.model_dims(TINY), V=96, Vb=32)
    key = W.seed_key(3)
    built = jax.jit(lambda k: W.build(k, small, jnp.float32))(key)
    for b in range(3):
        np.testing.assert_allclose(built["embed"][32 * b:32 * (b + 1)],
                                   W.embed_block(key, small, b), rtol=3e-7)
        np.testing.assert_allclose(built["lm_head"][:, 32 * b:32 * (b + 1)],
                                   W.head_block(key, small, b), rtol=3e-7)
    from chipbench.reference import falcon_h1 as ref
    tok = jnp.array([0, 31, 32, 95, 64])
    np.testing.assert_allclose(
        ref._embed(key, tok, m=ref._freeze(small)),
        built["embed"][tok] * small["m_embed"], rtol=1e-6)
    x = jax.random.normal(key, (5, small["D"]))
    want = (ref.rmsnorm(x, W.final_norm(key, small), small["eps"])
            @ built["lm_head"]) * small["m_head"]
    np.testing.assert_allclose(
        ref._head(key, x, m=ref._freeze(small), lowp=None), want,
        rtol=1e-4, atol=1e-5)


# -- the manifest: this configuration's entries, found by name ------------------

NEW = ["h1_prefill_mfu_pct", "h1_decode_mfu_pct", "h1_attn_prefill_ms",
       "h1_mlp_prefill_ms", "h1_attn_decode_ms_chunk",
       "h1_mlp_decode_ms_chunk", "h1_ssm_scan_prefill_roofline",
       "h1_ssm_step_decode_roofline", "h1_flash_fwd_roofline",
       "h1_flash_decode_paged_roofline", "h1_kv_over_state_bytes"]
OWN = {"configs": ["falcon-h1-34b-stage"], "workloads": ["serve-assist"],
       "per_layer": NEW}
# the manifest as this configuration left it: less OWN, as it found it
LEFT = json.loads((ROOT / f"{FIX}/accepted-manifest-pr31.json").read_text())


def test_this_prs_entries_come_after_the_accepted_ones():
    bench = rules.manifest()
    found = {s: [n for n in rules.names(LEFT, s) if n not in own]
             for s, own in OWN.items()}
    for section, own in OWN.items():
        rules.check_own_after(bench, section, found[section], own)
    rules.check_cell(bench, "serve-assist", "falcon-h1-34b-stage", 1)
    for name in NEW:
        rules.check_entry(bench, name, cells=["serve-assist"])
    # a metric whose count is another configuration's is not asked of
    # this cell
    rules.check_joins(bench, found["per_layer"], "serve-assist")
