"""The ``serve_laguna`` driver end to end at a tiny fixture on the CPU, in
``test_chipbench_sdar``'s manner (sound, the control in the program's
place, the program with a fault), the configuration against the catalog's
row, the counts of ``counts/laguna.py`` against hand counts, the weights'
rules and scales, the cell file's parameters, and the manifest: the
accepted one pinned as a PREFIX, this PR's entries found by name."""

import dataclasses
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
from chipbench import weights_laguna as W  # noqa: E402
from chipbench.counts import laguna as counts  # noqa: E402
from chipbench.drivers import serve_laguna  # noqa: E402
from chipbench.reference import laguna as ref  # noqa: E402
import manifest_rules as rules  # noqa: E402

FIX = "tests/chipbench/fixtures"
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
BENCH = {
    "workloads": [{"name": "tiny-repo", "config": "tiny-laguna",
                   "traffic": "x", "chips": 1,
                   "file": f"{FIX}/tiny-repo.json"}],
    "configs": [{"name": "tiny-laguna", "file": f"{FIX}/tiny-laguna.json"}],
    "end_to_end": [{"name": n, "unit": u} for n, u in (
        ("ttft_p95_ms", "ms"), ("tpot_p95_ms", "ms"), ("setup_s", "s"))],
    "per_layer": [],
}
REAL = json.loads(
    (ROOT / "chipbench/configs/laguna-s-2.1-ep2.json").read_text())
TINY = json.loads((ROOT / f"{FIX}/tiny-laguna.json").read_text())
CELL = json.loads((ROOT / "chipbench/workloads/serve-repo.json").read_text())
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
GAPS = ("served_logit_gap", "served_gap_tail_share", "served_gap_widest")


def drive(control=None):
    return runlib.run_cell(BENCH, "tiny-repo", 2**31 + 7, 0.5, False,
                           jax.devices()[:1], PEAKS, control=control,
                           readings=True)


def over(r, names=GAPS):
    return [n for n in names
            if r["checks"][n]["value"] > r["checks"][n]["limit"]]


# -- the driver, sound and broken --------------------------------------------------

def test_driver_runs_end_to_end_and_proves_correct():
    r = drive()
    assert r["correct"] is True, r["checks"]
    assert set(r["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["checks"]) == {*GAPS, "requests_failed"}
    readings = r["readings"]
    assert readings["gaps"]["one_wrong"]["mean"] > 1.0
    # the route reads all 16 experts; 8 are held here
    assert 0 < readings["moe_picks_per_token_decode"] < 3
    json.dumps(r)


def test_control_in_the_programs_place_is_not_correct():
    r = drive(control="ref-fp8")
    assert r["correct"] is False and over(r)
    own = r["readings"]["gaps"]["program"]
    assert own["mean"] <= r["checks"][GAPS[0]]["limit"]


def _faulty(monkeypatch, target, name, fn):
    """``drive()`` with ``target.name`` replaced inside freshly traced
    programs, which are dropped again afterwards."""
    from hpc_patterns_tpu.models import serving
    monkeypatch.setattr(target, name, fn)
    programs = (serving._prefill_one, serving._chunk_step)
    for p in programs:
        p.clear_cache()
    try:
        return drive()
    finally:
        for p in programs:
            p.clear_cache()


def test_the_window_dropped_from_the_window_layers_is_not_correct(
        monkeypatch):
    """Window layers that see every position (a window past the longest
    request, their rings as long as the table)."""
    real = serve_laguna.model_config
    monkeypatch.setattr(serve_laguna, "model_config", lambda c, e: (
        dataclasses.replace(real(c, e), window=4096)))
    r = drive()
    assert r["correct"] is False and over(r)


def test_the_window_applied_to_the_global_layers_is_not_correct(
        monkeypatch):
    """The global layers' attention cut to the window as well: in the
    prefill, dense or through flash_fwd, and in the decode step's
    flash_decode_paged (the fixture decodes through the kernel, as the
    cell does)."""
    from hpc_patterns_tpu import ops
    from hpc_patterns_tpu.models import decode
    from hpc_patterns_tpu.ops import flash_decode

    def cut(real):
        return lambda *a, window=0, **kw: real(*a, window=window or 24, **kw)

    monkeypatch.setattr(decode, "full_attention", cut(decode.full_attention))
    monkeypatch.setattr(ops, "flash_attention", cut(ops.flash_attention))
    r = _faulty(monkeypatch, flash_decode, "flash_decode_paged",
                cut(flash_decode.flash_decode_paged))
    assert r["correct"] is False and over(r)


def test_the_shared_expert_left_out_is_not_correct(monkeypatch):
    from hpc_patterns_tpu.parallel import moe
    real = moe.gated_moe
    r = _faulty(monkeypatch, moe, "gated_moe",
                lambda *a, **kw: real(*a, **{**kw, "shared": None}))
    assert r["correct"] is False and over(r)


def test_the_gate_left_out_is_not_correct(monkeypatch):
    from hpc_patterns_tpu.models import decode
    real = decode.attn_proj

    def ungated(o, lp, cfg, dt, ak=None, hn=None):
        return real(o, lp, cfg, dt, None if ak is None
                    else ak._replace(gate=False), hn)

    r = _faulty(monkeypatch, decode, "attn_proj", ungated)
    assert r["correct"] is False and over(r)


def test_a_program_without_the_window_layers_is_refused_at_once(
        monkeypatch, capsys):
    from hpc_patterns_tpu.models import transformer
    older = {k: v for k, v in transformer.LAYER_KINDS.items()
             if k not in "DW"}
    monkeypatch.setattr(transformer, "LAYER_KINDS", older)
    with pytest.raises(SystemExit) as stop:
        serve_laguna.model_config(REAL, {"decode_attn": "flash"})
    assert stop.value.code == 2
    assert "refused" in capsys.readouterr().err


# -- the configuration -------------------------------------------------------------

# the catalog row's ``config`` (model-configs guide, architectures.jsonl,
# Laguna-S-2.1), its numbers and words key for key; its lists and groups
# below
PUBLISHED = {
    "model_type": "laguna",
    "vocab_size": 100352,
    "hidden_size": 3072,
    "intermediate_size": 12288,
    "num_hidden_layers": 48,
    "num_attention_heads": 48,
    "num_key_value_heads": 8,
    "head_dim": 128,
    "max_position_embeddings": 1048576,
    "attention_bias": False,
    "rms_norm_eps": 1e-06,
    "num_experts": 256,
    "num_experts_per_tok": 10,
    "moe_intermediate_size": 1024,
    "shared_expert_intermediate_size": 1024,
    "norm_topk_prob": True,
    "decoder_sparse_step": 1,
    "tie_word_embeddings": False,
    "gating": "per-head",
    "sliding_window": 512,
    "moe_apply_router_weight_on_input": False,
    "moe_routed_scaling_factor": 2.5,
    "moe_router_logit_softcapping": 0
}
PERIOD = ["full_attention", "sliding_attention", "sliding_attention",
          "sliding_attention"]


def test_configuration_holds_the_catalog_rows_numbers_key_for_key():
    assert REAL["source"].startswith(
        "https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json")
    for key, value in PUBLISHED.items():
        if key not in REAL["reduced"]:
            assert REAL[key] == value, key
    # the per-layer lists whole, all 48 layers: the program reads the
    # first num_hidden_layers
    assert REAL["layer_types"] == PERIOD * 12
    assert REAL["mlp_layer_types"] == ["dense"] + ["sparse"] * 47
    assert REAL["gating_types"] == ["per_head"] * 48
    assert REAL["num_attention_heads_per_layer"] == [48, 72, 72, 72] * 12
    assert REAL["mlp_only_layers"] == [0]
    assert REAL["rope_parameters"] == {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}}
    assert REAL["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]
    assert REAL["published"] == {k: PUBLISHED[k] for k in REAL["reduced"]}
    assert (REAL["num_hidden_layers"], REAL["num_experts"],
            REAL["vocab_size"]) == (5, 128, 50_176)
    assert REAL["experts_held_from"] == 0
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == "laguna-s-2.1-ep2")
    assert entry["reduced"] == REAL["reduced"]
    assert REAL["source"].startswith(entry["source"])
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    for key in ("deployment", "precision", "assumed", "departures"):
        assert REAL[key], key
    assert "two chips share each layer" in REAL["deployment"]


def test_no_width_is_cut():
    m = W.model_dims(REAL)
    assert (m["D"], m["H"], m["Hw"], m["Hkv"], m["Dh"]) == (3072, 48, 72,
                                                            8, 128)
    assert (m["window"], m["Fd"], m["F"], m["Fs"]) == (512, 12288, 1024,
                                                       1024)
    assert (m["E"], m["held"], m["k"], m["scale"]) == (256, 128, 10, 2.5)
    assert m["pattern"] == "DWWWR" and m["V"] == 50_176
    assert m["rope_g"] == (500000.0, 64, (128.0, 8192, 32.0, 1.0,
                                          1.4852030263919618))
    assert m["rope_w"] == (10000.0, 128, ())


def test_what_is_assumed_is_written_down():
    said = " ".join(REAL["assumed"])
    for word in ("softmax", "selection-bias", "ungated", "sigmoid",
                 "no q/k norms", "rotate-half", "weight scales"):
        assert word in said, word
    for word in ("random weights", "greedy", "4,096", "no end token"):
        assert word in " ".join(REAL["departures"]), word


def test_the_driver_hands_the_program_the_published_numbers():
    cfg = serve_laguna.model_config(REAL, {"decode_attn": "flash"})
    assert cfg.layer_pattern == "DWWWR" and cfg.vocab == 50_176
    assert (cfg.d_model, cfg.n_heads, cfg.window_heads, cfg.kv_heads,
            cfg.head_dim, cfg.d_ff) == (3072, 48, 72, 8, 128, 12288)
    assert (cfg.moe_experts, cfg.experts_held, cfg.moe_held_start,
            cfg.moe_top_k, cfg.moe_d_ff, cfg.moe_shared_d_ff,
            cfg.moe_scale, cfg.moe_renorm) == (256, 128, 0, 10, 1024, 1024,
                                               2.5, True)
    assert (cfg.window, cfg.window_rope_theta, cfg.rope_theta,
            cfg.rope_partial, cfg.attn_gate) == (512, 1e4, 5e5, 0.5, True)
    assert cfg.rope_yarn == (128.0, 8192, 32.0, 1.0, 1.4852030263919618)
    assert cfg.ring_pages(256) == 3


def test_reference_imports_nothing_of_the_program():
    for f in ("chipbench/reference/laguna.py", "chipbench/weights_laguna.py",
              "chipbench/counts/laguna.py"):
        assert "hpc_patterns_tpu" not in (ROOT / f).read_text()


# -- counts against hand counts ----------------------------------------------------

def test_parameters_and_bytes_match_the_hand_count():
    """Counted by hand: 5,572 M parameters held here, 11.14 GB."""
    d = counts.dims(REAL)
    D = 3072
    glob = D * (48 + 16) * 128 + D * 48 + 48 * 128 * D
    win = D * (72 + 16) * 128 + D * 72 + 72 * 128 * D
    assert d["pA"] == [glob, win, win, win, glob]
    assert round(glob / 1e6, 2) == 44.19 and round(win / 1e6, 2) == 63.14
    assert d["pX"] == 3 * D * 1024 and round(d["pX"] / 1e6, 2) == 9.44
    routed = D * 256 + 3 * D * 1024                  # router + shared
    assert d["pM"] == [3 * D * 12288] + [routed] * 4
    assert (glob, win) == (44_187_648, 63_135_744)
    held = 128 * d["pX"]
    assert counts.layer_params(REAL, 0) == glob + 3 * D * 12288 + 2 * D \
        == 157_440_000
    assert counts.layer_params(REAL, 1) == win + routed + held + 2 * D \
        == 1_281_325_056
    assert counts.layer_params(REAL, 4) == glob + routed + held + 2 * D \
        == 1_262_376_960
    total = counts.model_params(REAL)
    assert total == 157_440_000 + 3 * 1_281_325_056 + 1_262_376_960 \
        + 2 * 50_176 * D + D == 5_572_076_544
    # 11.14 GB in bfloat16 (the router's 0.79 M a layer is held in float32)
    assert round(total * 2 / 1e9, 2) == 11.14
    assert counts.kv_bytes_token(REAL) == 4096
    eng = CELL["engine"]
    pools = 2 * (eng["pool_pages"] + 1) * eng["page_size"] * 4096
    rings = 3 * eng["slots"] * 3 * eng["page_size"] * 4096
    assert round(pools / 1e9, 2) == 2.15 and round(rings / 1e9, 2) == 0.60


def _facts():
    # two prefills and two chunks in the trace, one chunk before it
    return {
        "trace_host_window": (10.0, 14.0), "chunk": 8,
        "admissions": [(9.0, 2048, 1500), (11.0, 1024, 600),
                       (12.0, 4096, 3000)],
        "chunks": [
            {"t": 9.5, "rows": 9, "steps": 72, "keys": 90000,
             "keys_window": 36000, "picks": 3000, "touched": 2000,
             "calls": 128},
            {"t": 10.5, "rows": 10, "steps": 80, "keys": 100000,
             "keys_window": 40000, "picks": 3200, "touched": 2100,
             "calls": 128},
            {"t": 11.5, "rows": 12, "steps": 90, "keys": 120000,
             "keys_window": 45000, "picks": 3600, "touched": 2300,
             "calls": 128}],
        "moe_picks_per_token_prefill": 5.0,
    }


def test_work_of_the_traced_window_counts_what_the_traced_chunks_did():
    f, d = _facts(), counts.dims(REAL)
    dense = 2 * (sum(d["pA"]) + sum(d["pM"]))
    head = 2 * 3072 * 50_176
    scores = lambda g, w: 4 * 128 * (2 * 48 * g + 3 * 72 * w)
    pairs = lambda T: T * (T + 1) / 2
    window = lambda T: 512 * 513 // 2 + (T - 512) * 512
    want = lambda T: (T * (dense + 4 * 5.0 * 2 * d["pX"])
                      + scores(pairs(T), window(T)) + head)
    assert counts.prefill_work(f, REAL, 2) == (want(600) + want(3000), 0)
    step = lambda r: (r["steps"] * (dense + head) + 2 * r["picks"] * d["pX"]
                      + scores(r["keys"], r["keys_window"]))
    rows = f["chunks"]
    assert counts.decode_work(f, REAL, 2) == (step(rows[1]) + step(rows[2]),
                                              0)
    assert counts.decode_work(f, REAL, 1) == (step(rows[1]), 0)
    assert [r["t"] for r in counts.chunks_traced(f)] == [10.5, 11.5]
    # the grouped products: 3 a routed layer and step, 96 a chunk
    fl, by = counts.experts_decode_work(f, REAL, 192)
    assert fl == 2 * (3200 + 3600) * d["pX"]
    assert by == (2100 + 2300) * d["pX"] * 2 + (3200 + 3600) * (
        2 * 3072 * 2 + 2 * 1024 * 2 + 4 * 3072)
    # a prefill's five flash_fwd calls: the window layers' pairs alone
    fl, by = counts.flash_fwd_work(f, REAL, 10)
    assert fl == sum(2 * 2 * 128 * 48 * T * T + 3 * 4 * 128 * 72 * window(T)
                     for T in (600, 3000))
    # five paged calls a step, 40 a chunk
    fl, by = counts.flash_decode_paged_work(f, REAL, 80)
    assert fl == scores(100000 + 120000, 40000 + 45000)
    assert by == 2 * (2 * 2 * (100000 + 120000) * 8 * 128
                      + 3 * 2 * (40000 + 45000) * 8 * 128
                      + 2 * 2 * (80 + 90) * 48 * 128
                      + 3 * 2 * (80 + 90) * 72 * 128)


def test_the_window_share_of_the_kv_bytes():
    assert counts.window_kv_share(REAL, 40, 20) == pytest.approx(
        100 * 60 / (60 + 80))
    assert counts.window_kv_share(REAL, 0, 0) is None


def test_counts_read_nothing_where_the_program_logs_nothing():
    f = dict(_facts(), chunks=[])
    assert counts.decode_work(f, REAL, 2) == (0, 0)
    assert counts.experts_decode_work(f, REAL, 192) == (0, 0)
    assert counts.flash_decode_paged_work(f, REAL, 80) == (0, 0)
    del f["moe_picks_per_token_prefill"]
    assert counts.prefill_work(f, REAL, 2) == (0, 0)


def test_chunk_facts_are_what_the_sums_grew_by():
    log = [{"t": float(i), "pos": [10 * i, 600], "steps": [8, 2],
            "route": np.array([500 * i, 64 * i, 9, 40 * i, 12 * i])}
           for i in range(3)]
    got = serve_laguna.chunk_facts(log, 512)
    assert [g["t"] for g in got] == [1.0, 2.0]
    keys = [21 + s for s in range(8)] + [601, 602]
    assert got[1] == {"t": 2.0, "rows": 2, "steps": 10, "keys": sum(keys),
                      "keys_window": sum(min(k, 512) for k in keys),
                      "picks": 500, "touched": 40, "calls": 12}


# -- the weights' rules and scales ---------------------------------------------------

def test_a_leafs_numbers_do_not_depend_on_who_asks():
    m = W.model_dims(TINY)
    key = W.seed_key(2**31 + 5)
    built = jax.jit(lambda k: W.build(k, m, jnp.float32))(key)
    same = lambda a, b: np.testing.assert_allclose(a, b, rtol=3e-7, atol=0)
    for i, kind in enumerate(m["pattern"]):
        for name, a in W.layer(key, m, i, kind).items():
            same(built["layers"][i][name], a)
        if kind != "D":
            for e in (0, m["held"] - 1):
                for name, a in W.expert(key, m, i, e).items():
                    same(built["layers"][i][name][e], a)
    same(built["embed"], jnp.concatenate(
        [W.embed_block(key, m, b) for b in range(W.vocab_blocks(m))]))
    low = jax.jit(lambda k: W.build(k, m, jnp.bfloat16))(key)
    assert low["layers"][1]["router"].dtype == jnp.float32
    assert low["layers"][1]["w_gate"].dtype == jnp.bfloat16
    assert set(low["layers"][0]) == {
        "ln1_scale", "ln2_scale", "wqkv", "wo", "attn_gate", "w_gate",
        "w_up", "w_down"}
    assert set(low["layers"][1]) == set(low["layers"][0]) | {
        "router", "ws_gate", "ws_up", "ws_down"}


def test_the_other_chips_experts_are_made_alike():
    """The held share starts at ``experts_held_from``: experts 8 .. 15 of
    a share that starts there are the same numbers as of the whole."""
    m = W.model_dims(TINY)
    key = W.seed_key(11)
    other = dict(m, held0=8)
    lw = jax.jit(lambda k: W.build_layer(k, other, 1, jnp.float32))(key)
    np.testing.assert_array_equal(lw["w_up"][3],
                                  W.expert(key, m, 1, 11)["w_up"])


def test_scales_give_scores_of_order_one_unsaturated_gates_a_spread_route():
    """At the published widths, one window layer and the route of the
    first routed layer over 256 positions of random tokens: a head's
    scores spread by about one, the per-head gates stay inside
    (0.05, 0.95) for nearly every head and token, and the route reaches
    most of the 256 experts."""
    m = W.model_dims(REAL)
    key = W.seed_key(2**31 + 17)
    T = 256
    x = jax.random.normal(jax.random.fold_in(key, 5), (T, m["D"]))
    lw = jax.jit(lambda k: ref.served_layer(k, m, 1, "W"))(key)
    h = ref.rmsnorm(x, lw["ln1_scale"], m["eps"])
    q, k = jnp.split(h @ lw["wqkv"], [72 * 128], axis=-1)
    q = q.reshape(T, 72, 128)
    k = k[:, :8 * 128].reshape(T, 8, 128)
    s = jnp.einsum("thd,shd->hts", q[:, :8], k) / np.sqrt(128)
    assert 0.5 < float(jnp.std(s)) < 2.0
    g = jax.nn.sigmoid(h @ lw["attn_gate"])
    assert float(jnp.mean((g > 0.05) & (g < 0.95))) > 0.95
    h2 = ref.rmsnorm(x, lw["ln2_scale"], m["eps"])
    idx, gates = ref.route(h2, lw, m)
    assert len(np.unique(np.asarray(idx))) > 200
    assert float(jnp.max(gates)) < 2.5 * 0.5       # no pick takes it all


# -- the cell ------------------------------------------------------------------------

def test_the_cells_parameters_are_as_specified():
    t, e = CELL["traffic"], CELL["engine"]
    assert CELL["config"] == "laguna-s-2.1-ep2"
    assert CELL["driver"] == "serve_laguna"
    assert t["prompt"] == {"median": 2048, "sigma": 0.6, "lo": 512,
                           "hi": 3840}
    assert t["output"] == {"median": 256, "sigma": 0.7, "lo": 32, "hi": 1024}
    assert (t["max_total"], t["lead_in_s"], t["lead_out_s"]) == (4096, 6.0,
                                                                 3.0)
    assert t["arrivals"] == {"process": "poisson"}
    assert t["rate_rps"] == pytest.approx(0.8 * t["knee_rps"])
    assert (e["slots"], e["page_size"], e["pages_per_seq"], e["pool_pages"],
            e["prompt_buckets"], e["chunk"], e["overlap"],
            e["decode_attn"]) == (64, 256, 16, 1024,
                                  [512, 1024, 2048, 4096], 8, True, "flash")
    assert e["pool_pages"] == e["slots"] * e["pages_per_seq"]
    assert CELL["check"]["sample"] == 6
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == "serve-repo")
    assert entry == {"name": "serve-repo", "config": "laguna-s-2.1-ep2",
                     "traffic": "coding-agent", "chips": 1,
                     "why": entry["why"]}
    assert len(entry["why"]) <= 200 and "2.5" in entry["why"]


def test_the_cells_limits_lie_between_their_two_readings():
    """``serve-repo``'s check block against the chip's readings that
    ``check.set_from`` records: the program's largest over its seeds
    passes each limit with room; the fp8 control's smallest fails the
    mean and the tail's share; one wrong token fails the widest."""
    check = CELL["check"]
    assert 2 * 0.001024 < check["gap_limit"] < 0.006528 / 2
    assert check["tail_above"] == 0.05
    assert 2 * 0.00799 < check["tail_share_limit"] < 0.0545 / 2
    assert 4 * 0.3335 < check["widest_limit"] <= 1.5 < 6.25
    for k in ("statistic", "set_from"):
        assert "PLACEHOLDER" not in check[k]
    assert "PLACEHOLDER" not in CELL["traffic"]["knee_found"]


# -- the manifest: the accepted entries first -----------------------------------------

ACCEPTED = json.loads((ROOT / f"{FIX}/accepted-manifest-pr37.json")
                      .read_text())
NEW = ["laguna_prefill_mfu_pct", "laguna_decode_mfu_pct",
       "laguna_moe_decode_ms_chunk", "laguna_global_attn_decode_ms_chunk",
       "laguna_window_attn_decode_ms_chunk",
       "laguna_experts_decode_roofline", "laguna_flash_fwd_roofline",
       "laguna_flash_decode_paged_roofline", "laguna_window_kv_share"]


@pytest.mark.parametrize("section", ["configs", "workloads", "end_to_end",
                                     "per_layer"])
def test_the_accepted_entries_come_first_and_their_lists_only_grew(section):
    rules.check_prefix(MANIFEST, ACCEPTED, section)


def test_this_prs_entries_follow_the_accepted_ones():
    """Found by name, anywhere after the accepted entries."""
    for section, own in (("configs", ["laguna-s-2.1-ep2"]),
                         ("workloads", ["serve-repo"]),
                         ("per_layer", NEW)):
        rules.check_own_after(MANIFEST, section,
                              rules.names(ACCEPTED, section), own)
    rules.check_cell(MANIFEST, "serve-repo", "laguna-s-2.1-ep2", 1)
    for name in NEW:
        rules.check_entry(MANIFEST, name, cells=["serve-repo"])
        args = rules.spec_of(name)["args"]
        assert args["counts"].split(".")[0] == "laguna" \
            if "counts" in args else True
        if "counts" in args:
            assert callable(getattr(counts, args["counts"].split(".")[1]))


def test_accepted_metrics_asked_of_the_cell_read_something_there():
    """The cell joined an accepted metric's list only where the metric's
    spec selects no program the cell does not run and carries no other
    configuration's count; it reports both tails."""
    rules.check_joins(MANIFEST, rules.names(ACCEPTED, "per_layer"),
                      "serve-repo", foreign=("block_chunk", "jit_step"))
    for e in MANIFEST["end_to_end"]:
        if e["name"] in ("ttft_p95_ms", "tpot_p95_ms"):
            assert "serve-repo" in e["workloads"]
