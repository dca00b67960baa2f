"""The ``serve_sdar`` driver end to end at a tiny fixture on the CPU, in
``test_chipbench_falcon_h1``'s manner (sound, the timed path broken, the
control in the program's place), the configuration against the catalog's
row, the counts of ``counts/sdar.py`` against hand counts, the weights'
rules, the cell file's parameters, and the manifest pinned as a PREFIX."""

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
from chipbench import traffic  # noqa: E402
from chipbench import weights_sdar as W  # noqa: E402
from chipbench.counts import sdar as counts  # noqa: E402
from chipbench.drivers import serve_sdar  # noqa: E402
from chipbench.reference import sdar as ref  # noqa: E402
import manifest_rules as rules  # noqa: E402

FIX = "tests/chipbench/fixtures"
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
BENCH = {
    "workloads": [{"name": "tiny-diffuse", "config": "tiny-sdar",
                   "traffic": "x", "chips": 1,
                   "file": f"{FIX}/tiny-diffuse.json"}],
    "configs": [{"name": "tiny-sdar", "file": f"{FIX}/tiny-sdar.json"}],
    "end_to_end": [{"name": n, "unit": u} for n, u in (
        ("ttft_p95_ms", "ms"), ("tpot_p95_ms", "ms"), ("setup_s", "s"))],
    "per_layer": [],
}
REAL = json.loads(
    (ROOT / "chipbench/configs/sdar-30b-a3b-stage.json").read_text())
TINY = json.loads((ROOT / f"{FIX}/tiny-sdar.json").read_text())
CELL = json.loads(
    (ROOT / "chipbench/workloads/serve-diffuse.json").read_text())
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())

# the catalog row's ``config`` (model-configs guide, architectures.jsonl,
# SDAR-30B-A3B-Chat), key for key
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}

TOKEN = ("settled_token_gap_mean", "settled_token_gap_tail_share",
         "settled_token_gap_widest")
PICK = ("position_pick_gap_mean", "position_pick_gap_tail_share",
        "position_pick_gap_widest")


def drive(control=None):
    return runlib.run_cell(BENCH, "tiny-diffuse", 2**31 + 7, 0.5, False,
                           jax.devices()[:1], PEAKS, control=control,
                           readings=True)


def over(r, names):
    return [n for n in names
            if r["checks"][n]["value"] > r["checks"][n]["limit"]]


# -- the driver, sound and broken ------------------------------------------------

def test_driver_runs_end_to_end_and_proves_correct():
    r = drive()
    assert r["correct"] is True, r["checks"]
    assert set(r["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["checks"]) == {*TOKEN, *PICK, "requests_failed"}
    readings = r["readings"]
    assert readings["diffusion_tokens_per_forward"] <= 4 / 3 + 1e-9
    assert readings["diffusion_blocks"] * 4 >= readings["diffusion_tokens"]
    assert readings["moe_picks_per_token_decode"] == 2.0   # all are held
    assert readings["gaps"]["token"]["one_wrong"]["mean"] > 1.0
    backwards = readings["gaps"]["pick"]["backwards"]
    assert backwards["mean"] > r["checks"][PICK[0]]["limit"]
    assert backwards["widest"] > r["checks"][PICK[2]]["limit"]
    json.dumps(r)


def test_control_in_the_programs_place_is_not_correct():
    """By the settled tokens' gaps (their mean and their tail's share);
    the program's own numbers ride along in the control's readings and
    pass."""
    r = drive(control="ref-fp8")
    assert r["correct"] is False and len(over(r, TOKEN)) >= 2
    gaps = r["readings"]["gaps"]
    for family, names in (("token", TOKEN), ("pick", PICK)):
        own = gaps[family]["program"]
        assert own["mean"] <= r["checks"][names[0]]["limit"]
        assert own["widest"] <= r["checks"][names[2]]["limit"]


def test_a_token_altered_in_the_block_chunk_is_not_correct(monkeypatch):
    """One position of every block handed out wrong (the device's own
    state goes on with the right one)."""
    from hpc_patterns_tpu.models import serving
    real = serving._block_chunk

    def altered(*a, **kw):
        *state, (toks, fidx, commit) = real(*a, **kw)
        wrong = jnp.where((fidx >= 0) & (jnp.arange(4) == 2),
                          (toks + 1) % 2000, toks)
        return (*state, (wrong, fidx, commit))

    monkeypatch.setattr(serving, "_block_chunk", altered)
    r = drive()
    assert r["correct"] is False and set(over(r, TOKEN)) == set(TOKEN)


def _recompiled(monkeypatch, target, name, fn, *programs):
    """``drive()`` with ``target.name`` replaced inside freshly traced
    programs, which are dropped again afterwards."""
    monkeypatch.setattr(target, name, fn)
    for p in programs:
        p.clear_cache()
    try:
        return drive()
    finally:
        for p in programs:
            p.clear_cache()


def test_positions_settled_out_of_confidence_order_are_not_correct(
        monkeypatch):
    from hpc_patterns_tpu.models import serving
    real = serving._unmask

    def least_first(logits, msk, **kw):
        cand, _ = real(logits, msk, **kw)
        conf = jnp.where(msk, jnp.max(jax.nn.softmax(logits, -1), -1), 2.0)
        _, worst = jax.lax.top_k(-conf, 2)
        return cand, jnp.any(worst[:, :, None] == jnp.arange(4),
                             axis=1) & msk

    r = _recompiled(monkeypatch, serving, "_unmask", least_first,
                    serving._block_chunk)
    assert r["correct"] is False and over(r, PICK)
    assert not over(r, TOKEN)     # every token is its position's best


def test_the_commit_forward_left_out_is_not_correct(monkeypatch):
    from hpc_patterns_tpu.models import serving
    real = serving.paged_block_step

    def no_commit(params, cache, pos, blk, cfg, active=None):
        settled = ~jnp.any(blk == cfg.mask_id, axis=-1)
        return real(params, cache, pos, blk, cfg, active=active & ~settled)

    r = _recompiled(monkeypatch, serving, "paged_block_step", no_commit,
                    serving._block_chunk)
    assert r["correct"] is False


def test_the_causal_mask_in_place_of_the_block_mask_is_not_correct(
        monkeypatch):
    """In the prefill (both of its attention routes lose ``mask_block``)
    and in the block step (a position sees its block only up to itself)."""
    from hpc_patterns_tpu.models import decode, serving
    from hpc_patterns_tpu.ops import flash_attention as kernel
    from hpc_patterns_tpu.ops import flash_decode
    from hpc_patterns_tpu.parallel.ring_attention import full_attention
    import hpc_patterns_tpu.ops as ops

    def causal_block(q, k_pool, v_pool, table, pos, **kw):
        return jnp.stack([flash_decode.flash_decode_paged(
            q[:, i], k_pool, v_pool, table, pos + i, **kw)
            for i in range(q.shape[1])], axis=1)

    monkeypatch.setattr(flash_decode, "flash_decode_paged_block",
                        causal_block)
    monkeypatch.setattr(
        ops, "flash_attention",
        lambda q, k, v, causal=True, mask_block=1: kernel(q, k, v,
                                                          causal=causal))
    r = _recompiled(
        monkeypatch, decode, "full_attention",
        lambda q, k, v, causal, mask_block=1: full_attention(
            q, k, v, causal=causal), serving._prefill_one,
        serving._block_chunk)
    assert r["correct"] is False


def test_a_program_without_the_block_step_is_refused_at_once(monkeypatch,
                                                             capsys):
    from hpc_patterns_tpu.models import transformer

    @dataclasses.dataclass(frozen=True)
    class Older:
        vocab: int = 0

    monkeypatch.setattr(transformer, "TransformerConfig", Older)
    with pytest.raises(SystemExit) as stop:
        serve_sdar.model_config(REAL, {"decode_attn": "flash"})
    assert stop.value.code == 2
    assert "refused" in capsys.readouterr().err


# -- the configuration -------------------------------------------------------------

def test_configuration_holds_the_catalog_rows_numbers_key_for_key():
    assert REAL["reduced"] == ["num_hidden_layers"]
    assert REAL["num_hidden_layers"] == 6
    assert REAL["published"] == {"num_hidden_layers": 48}
    for key, value in PUBLISHED.items():
        if key != "num_hidden_layers":
            assert REAL[key] == value, key
    for key in ("source", "deployment", "precision", "assumed",
                "departures"):
        assert REAL[key], key
    assert "eight pipeline stages" in REAL["deployment"]
    assert len(REAL["source"]) <= 200 and "sdar_moe" in REAL["source"]
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == "sdar-30b-a3b-stage")
    assert entry["reduced"] == REAL["reduced"]
    assert REAL["source"].startswith(entry["source"])
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200


def test_what_is_assumed_is_written_down():
    gen = REAL["generation"]
    assert gen == {"block_length": 4, "mask_token_id": 151669,
                   "remasking": "low_confidence_static",
                   "denoising_steps": 2, "confidence_threshold": 0.9}
    said = " ".join(REAL["assumed"])
    for word in ("block_length 4", "low_confidence_static", "0.9", "151669",
                 "q and of k", "unshifted", "remainder", "weight scales"):
        assert word in said, word
    for word in ("random weights", "greedy", "4,096"):
        assert word in " ".join(REAL["departures"]), word


def test_the_driver_hands_the_program_the_published_numbers():
    cfg = serve_sdar.model_config(REAL, {"decode_attn": "flash"})
    assert cfg.layer_pattern == "RRRRRR" and cfg.vocab == 151936
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (
        2048, 32, 4, 128)
    assert (cfg.moe_experts, cfg.experts_held, cfg.moe_top_k, cfg.moe_d_ff,
            cfg.moe_renorm) == (128, 128, 8, 768, True)
    assert cfg.rope_theta == 1e6 and cfg.norm_eps == 1e-6 and cfg.qk_norm
    assert (cfg.block_len, cfg.mask_id) == (4, 151669)
    assert serve_sdar.unmask_args(REAL) == {
        "unmask_rule": "static", "unmask_steps": 2, "unmask_threshold": 0.9}


def test_reference_imports_nothing_of_the_program():
    for f in ("chipbench/reference/sdar.py", "chipbench/weights_sdar.py",
              "chipbench/counts/sdar.py"):
        assert "hpc_patterns_tpu" not in (ROOT / f).read_text()


# -- counts against hand counts ---------------------------------------------------

def test_parameters_and_bytes_are_the_issues():
    d = counts.dims(REAL)
    assert d["pA"] == 2 * 2048 * 4096 + 2 * 2048 * 512 == 18_874_368
    assert d["pR"] == 262_144 and d["E"] * d["pX"] == 603_979_776
    assert counts.layer_params(REAL) == 18_874_368 + 262_144 \
        + 603_979_776 + 4_352 == 623_120_640        # 1.246 GB in bfloat16
    assert counts.model_params(REAL) == 6 * 623_120_640 \
        + 2 * 151_936 * 2048 + 2048 == 4_361_055_744    # 8.72 GB
    assert round(counts.model_params(REAL) * 2 / 1e9, 2) == 8.72
    # the whole model: 48 layers, 61 GB; a seventh layer: 9.97 GB
    assert round((48 * 623_120_640 + 622_331_904) * 2 / 1e9) == 61
    assert round((7 * 623_120_640 + 622_331_904) * 2 / 1e9, 2) == 9.97
    assert counts.kv_bytes_token(REAL) == 12_288
    eng = CELL["engine"]
    pool = (eng["pool_pages"] + 1) * eng["page_size"] * 12_288
    assert round(pool / 1e9, 2) == 3.22


def _facts():
    # two prefills and two chunks in the trace, one chunk before it
    return {
        "trace_host_window": (10.0, 14.0), "chunk": 6,
        "admissions": [(9.0, 512, 300), (11.0, 512, 402), (12.0, 1024, 700)],
        "block_chunks": [
            {"t": 9.5, "rows": 9, "ctx_tokens": 9000, "forwards": 54,
             "blocks": 18, "tokens": 70, "picks": 10368, "touched": 4300,
             "calls": 36},
            {"t": 10.5, "rows": 10, "ctx_tokens": 8000, "forwards": 60,
             "blocks": 20, "tokens": 80, "picks": 11520, "touched": 4400,
             "calls": 36},
            {"t": 11.5, "rows": 12, "ctx_tokens": 9600, "forwards": 66,
             "blocks": 22, "tokens": 85, "picks": 12672, "touched": 4500,
             "calls": 36}],
        "moe_picks_per_token_prefill": 8.0,
        "moe_experts_touched_prefill": 127.5,
    }


def test_work_of_the_traced_window_counts_what_the_traced_chunks_did():
    f, d = _facts(), counts.dims(REAL)
    per_token = 2 * (d["pA"] + d["pR"] + 8 * d["pX"])
    assert d["pX"] == 3 * 2048 * 768
    want = lambda T: T * 6 * (per_token + 4 * (T / 2) * 32 * 128)
    assert counts.prefill_work(f, REAL, 2) == (want(402) + want(700), 0)
    head = 2 * 2048 * 151936
    fwd = lambda rf, ctx: rf * 4 * (6 * (per_token + 4 * ctx * 32 * 128)
                                    + head)
    assert counts.decode_work(f, REAL, 2) == (
        fwd(60, 800 + 4) + fwd(66, 800 + 4), 0)
    # by the count of chunk programs the trace shows, not by the window
    assert counts.decode_work(f, REAL, 1) == (fwd(60, 804), 0)
    assert [r["t"] for r in counts.chunks_traced(f)] == [10.5, 11.5]
    # the grouped products: 3 a layer and forward, 108 a chunk
    fl, by = counts.experts_decode_work(f, REAL, 216)
    picks, touched = 11520 + 12672, 4400 + 4500
    assert fl == 2 * picks * d["pX"]
    assert by == touched * d["pX"] * 2 + picks * (
        2 * 2048 * 2 + 2 * 768 * 2 + 4 * 2048)
    # the prefills': 18 calls each, whole blocks of the true tokens
    fl, by = counts.experts_prefill_work(f, REAL, 36)
    assert fl == 2 * (400 + 700) * 8 * 6 * d["pX"]
    assert by == 2 * 6 * 127.5 * d["pX"] * 2 + (400 + 700) * 8 * 6 * (
        2 * 2048 * 2 + 2 * 768 * 2 + 4 * 2048)
    fl, by = counts.flash_fwd_work(f, REAL, 12)
    assert fl == 6 * 2 * 128 * 32 * (402 ** 2 + 700 ** 2)
    # a row's keys read once for its four queries: 36 calls a chunk
    fl, by = counts.flash_decode_paged_work(f, REAL, 72)
    keys = (60 + 66) * 804
    assert fl == 6 * 4 * keys * 4 * 32 * 128
    assert by == 6 * 2 * (2 * keys * 4 * 128 + 2 * (60 + 66) * 4 * 32 * 128)


def test_counts_read_nothing_where_the_program_logs_nothing():
    f = dict(_facts(), block_chunks=[])
    assert counts.decode_work(f, REAL, 2) == (0, 0)
    assert counts.experts_decode_work(f, REAL, 216) == (0, 0)
    assert counts.flash_decode_paged_work(f, REAL, 72) == (0.0, 0.0)
    del f["moe_experts_touched_prefill"]
    assert counts.experts_prefill_work(f, REAL, 36) == (0, 0)


def test_chunk_facts_are_what_the_sums_grew_by():
    log = [{"t": float(i), "rows": 3 + i, "ctx_tokens": 100 * i,
            "route": np.array([500 * i, 64 * i, 9, 40 * i, 12 * i]),
            "diffusion": np.array([16 * i, 5 * i, 19 * i])}
           for i in range(3)]
    got = serve_sdar.chunk_facts(log)
    assert [g["t"] for g in got] == [1.0, 2.0]
    assert got[1] == {"t": 2.0, "rows": 5, "ctx_tokens": 200, "forwards": 16,
                      "blocks": 5, "tokens": 19, "picks": 500, "touched": 40,
                      "calls": 12}


# -- the cell ------------------------------------------------------------------------

def test_the_cells_parameters_are_the_issues():
    t, e = CELL["traffic"], CELL["engine"]
    assert CELL["config"] == "sdar-30b-a3b-stage"
    assert CELL["driver"] == "serve_sdar"
    assert t["prompt"] == {"median": 512, "sigma": 0.8, "lo": 64, "hi": 3072}
    assert t["output"] == {"median": 256, "sigma": 0.6, "lo": 32, "hi": 1024}
    assert (t["max_total"], t["lead_in_s"], t["lead_out_s"]) == (4096, 6.0,
                                                                 3.0)
    assert t["arrivals"] == {"process": "poisson"}
    assert t["rate_rps"] == pytest.approx(0.8 * t["knee_rps"])
    assert (e["slots"], e["page_size"], e["pages_per_seq"], e["pool_pages"],
            e["prompt_buckets"], e["chunk"], e["overlap"],
            e["decode_attn"]) == (64, 256, 16, 1024,
                                  [512, 1024, 2048, 4096], 6, True, "flash")
    assert e["pool_pages"] == e["slots"] * e["pages_per_seq"]
    assert CELL["check"]["sample"] == 6
    entry = next(w for w in MANIFEST["workloads"]
                 if w["name"] == "serve-diffuse")
    assert entry == {"name": "serve-diffuse", "config": "sdar-30b-a3b-stage",
                     "traffic": "block-diffusion-chat", "chips": 1,
                     "why": entry["why"]}
    assert len(entry["why"]) <= 200


def test_the_cells_limits_lie_between_their_two_readings():
    """``serve-diffuse``'s check block against the chip's readings that
    ``check.set_from`` records: the program's largest over 20 seeds passes
    each limit with room; the fp8 control's smallest fails the means and
    the tails' shares; one wrong token, and the order of confidence
    backwards, fail too."""
    token, pick = CELL["check"]["token"], CELL["check"]["pick"]
    assert 2 * 0.0162 < token["mean_limit"] < 0.07005 / 2
    assert token["tail_above"] == 0.2
    assert 2 * 0.0229 < token["tail_share_limit"] < 0.128 / 2
    assert 2 * 0.7334 < token["widest_limit"] <= 1.5 < 4.09
    assert 1.5 * 0.0277 < pick["mean_limit"] < 0.0796 / 1.5 < 0.160
    assert pick["tail_above"] == 0.1
    assert 1.5 * 0.097 < pick["tail_share_limit"] < 0.321 / 1.5 < 0.667
    assert 1.9 * 0.5078 < pick["widest_limit"]


def test_requests_hold_no_mask_and_whole_blocks():
    m = W.model_dims(REAL)
    mix = dict(CELL["traffic"], rate_rps=6.0)
    got = serve_sdar.block_requests(mix, m, 2**31 + 11, 8.0)
    plain = traffic.serving_requests(mix, m["V"] - 1, 2**31 + 11, 8.0)
    assert len(got) == len(plain) > 40
    for r, p in zip(got, plain):
        assert r.max_new % 4 == 0 and 0 <= r.max_new - p.max_new + 4 <= 7
        assert len(r.prompt) + r.max_new <= 4096
        assert not (r.prompt == m["mask_id"]).any() and r.prompt.max() < m["V"]
        assert (r.index, r.due_s, r.measured) == (p.index, p.due_s,
                                                  p.measured)
    # an id at or above the mask's moved up by one
    moved = np.concatenate([r.prompt for r in got]) - np.concatenate(
        [p.prompt for p in plain])
    assert set(moved.tolist()) == {0, 1}


# -- the weights' rules -----------------------------------------------------------------

def test_a_leafs_numbers_do_not_depend_on_who_asks():
    m = W.model_dims(TINY)
    key = W.seed_key(2**31 + 5)
    built = jax.jit(lambda k: W.build(k, m, jnp.float32))(key)
    same = lambda a, b: np.testing.assert_allclose(a, b, rtol=3e-7, atol=0)
    for i in range(m["L"]):
        for name, a in W.layer(key, m, i).items():
            same(built["layers"][i][name], a)
        for e in (0, m["E"] - 1):
            for name, a in W.expert(key, m, i, e).items():
                same(built["layers"][i][name][e], a)
    same(built["embed"], W.embed_block(key, m, 0))
    same(built["lm_head"], W.head_block(key, m, 0))
    low = jax.jit(lambda k: W.build(k, m, jnp.bfloat16))(key)
    for name, a in low["layers"][0].items():
        want = jnp.float32 if name in W.FLOAT32_LEAVES else jnp.bfloat16
        assert a.dtype == want, name
    assert set(low["layers"][0]) == {
        "ln1_scale", "ln2_scale", "wqkv", "wo", "q_norm", "k_norm",
        "router", "w_gate", "w_up", "w_down"}


def test_the_vocabulary_comes_in_sixteen_blocks_that_tile_it():
    m = W.model_dims(REAL)
    assert (m["Vb"], W.vocab_blocks(m)) == (9496, 16)
    assert (m["B"], m["mask_id"], m["E"], m["k"], m["F"]) == (4, 151669,
                                                             128, 8, 768)
    small = dict(W.model_dims(TINY), V=96, Vb=32)
    key = W.seed_key(3)
    built = jax.jit(lambda k: W.build(k, small, jnp.float32))(key)
    for b in range(3):
        np.testing.assert_allclose(built["embed"][32 * b:32 * (b + 1)],
                                   W.embed_block(key, small, b), rtol=3e-7)
        np.testing.assert_allclose(built["lm_head"][:, 32 * b:32 * (b + 1)],
                                   W.head_block(key, small, b), rtol=3e-7)
    tok = jnp.array([0, 31, 32, 95, 64])
    np.testing.assert_allclose(
        ref._embed(key, tok, m=ref._freeze(small)), built["embed"][tok],
        rtol=1e-6)
    x = jax.random.normal(key, (5, small["D"]))
    z = ref.rmsnorm(x, W.final_norm(key, small), small["eps"]) \
        @ built["lm_head"]
    best, lse, arg, at = ref._head(key, x, jnp.array([3, 40, 95, 0, 64]),
                                   m=ref._freeze(small), lowp=None)
    np.testing.assert_allclose(best, z.max(-1), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(lse, jax.nn.logsumexp(z, -1), rtol=1e-4)
    assert arg.tolist() == z.argmax(-1).tolist()
    np.testing.assert_allclose(
        at, z[jnp.arange(5), jnp.array([3, 40, 95, 0, 64])], rtol=1e-4,
        atol=1e-5)


def test_the_seeded_replay_is_the_trees():
    """``replay_numbers`` (weights made inside, a layer and an expert at a
    time, plans padded to one length) against the same forward over the
    built tree."""
    m = W.model_dims(TINY)
    seed = 2**31 + 21
    params = jax.jit(lambda k: W.build(k, m, jnp.float32))(W.seed_key(seed))
    prompt = np.arange(9, dtype=np.int32) + 5
    _, blocks = ref.generate(params, prompt, 7, m, pad_to=32)
    plan = ref.replay_plan(prompt, blocks, m)
    best, lse, arg, at = ref.replay_numbers(seed, m, [plan], pad_to=64)[0]
    tree = ref.tree_replay_numbers(params, m, plan)
    for got, want in zip((best, lse, at), (tree[0], tree[1], tree[3])):
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-4)
    tok, pick = ref.replay_gaps(plan, best, lse, at, steps=2)
    assert tok.max() < 1e-3 and pick.max() < 1e-3


# -- the manifest: the accepted entries first ---------------------------------------------

ACCEPTED = json.loads((ROOT / f"{FIX}/accepted-manifest-pr31.json")
                      .read_text())
NEW = ["sdar_prefill_mfu_pct", "sdar_decode_mfu_pct",
       "sdar_decode_dev_ms_forward", "sdar_moe_decode_ms_chunk",
       "sdar_attn_decode_ms_chunk", "sdar_unmask_ms_chunk",
       "sdar_experts_decode_roofline", "sdar_experts_prefill_roofline",
       "sdar_flash_fwd_roofline", "sdar_flash_decode_paged_roofline",
       "sdar_tokens_per_forward"]


@pytest.mark.parametrize("section", ["configs", "workloads", "end_to_end",
                                     "per_layer"])
def test_the_accepted_entries_come_first_and_their_lists_only_grew(section):
    """A PREFIX is pinned, so that the next appended entry does not break
    this test: what the accepted manifest held stands first, in order,
    each entry as it was but for a ``workloads`` list that may have grown
    at its end (less the entries taken out since)."""
    rules.check_prefix(MANIFEST, ACCEPTED, section)


def test_this_prs_entries_follow_the_accepted_ones():
    """Found by name, anywhere after the accepted entries."""
    for section, own in (("configs", ["sdar-30b-a3b-stage"]),
                         ("workloads", ["serve-diffuse"]),
                         ("per_layer", NEW)):
        rules.check_own_after(MANIFEST, section,
                              rules.names(ACCEPTED, section), own)
    rules.check_cell(MANIFEST, "serve-diffuse", "sdar-30b-a3b-stage", 1)
    for name in NEW:
        rules.check_entry(MANIFEST, name, cells=["serve-diffuse"])
        args = rules.spec_of(name)["args"]
        if "counts" in args:
            assert args["counts"].split(".")[0] == "sdar"
            assert callable(getattr(counts, args["counts"].split(".")[1]))


def test_accepted_metrics_asked_of_the_cell_read_something_there():
    """The cell joined an accepted metric's list only where the metric's
    spec selects nothing of another program (``jit__chunk_step``) and
    carries no other configuration's count; it reports both tails."""
    rules.check_joins(MANIFEST, rules.names(ACCEPTED, "per_layer"),
                      "serve-diffuse", foreign=("chunk_step",))
    for e in MANIFEST["end_to_end"]:
        if e["name"] in ("ttft_p95_ms", "tpot_p95_ms"):
            assert "serve-diffuse" in e["workloads"]
