"""The layer pattern on the CPU at a small size: each mixer and the whole
served path against the plain reference (``chipbench/reference/
nemotron_h.py``) on seeded weights, the state's rules in the engine, the
expert share tied to the whole layer, and the default pattern tied to the
parent's programs.

Tolerances. The program runs here in float32 (``dtype="float32"``) against
a float32 reference, so what separates them is the order of the sums: the
chunked form of the recurrence against the sequential one, the grouped
products against the masked loop. ``TOL`` = 2e-4 on logits of size ~1 is
an order above the largest such gap seen (3e-5) and two below what holding
the recurrent state in bfloat16 does (>= 1e-2), which has to fail it.
"""

import dataclasses
import hashlib
import json
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from chipbench import weights_nemotron_h as W  # noqa: E402
from chipbench.drivers import serve_hybrid  # noqa: E402
from chipbench.reference import nemotron_h as ref  # noqa: E402
from hpc_patterns_tpu.models import decode as D  # noqa: E402
from hpc_patterns_tpu.models import serving as S  # noqa: E402
from hpc_patterns_tpu.models import transformer as T  # noqa: E402
from hpc_patterns_tpu.parallel import moe  # noqa: E402

TOL = 2e-4
SEED = 2**31 + 11
CONFIG = json.loads(
    (ROOT / "tests/chipbench/fixtures/tiny-nemotron.json").read_text())
M = W.model_dims(CONFIG)
ENGINE = {"decode_attn": "gather"}
CFG = dataclasses.replace(serve_hybrid.model_config(CONFIG, ENGINE),
                          dtype="float32", attention="full")
KEY = W.seed_key(SEED)


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: W.build(k, M, jnp.float32))(KEY)


def tokens(n, stream=0):
    return np.asarray(jax.random.randint(
        jax.random.fold_in(KEY, 77 + stream), (n,), 0, M["V"]), np.int32)


def ref_logits(seq, rows, **kw):
    return np.asarray(ref.logits_at(SEED, M, [seq], [rows], pad_to=16,
                                    **kw)[0])


# -- each mixer against the reference ---------------------------------------

def _layer_of(kind):
    return M["pattern"].index(kind)


# jitted once a shape: op-by-op dispatch would compile every primitive
_ssm_mixer = jax.jit(lambda x, lp, last: T.ssm_mixer(x, lp, CFG, last))
_prefill = jax.jit(D.paged_prefill, static_argnames=("cfg", "page_size"))
_step = jax.jit(D.paged_decode_step, static_argnames=("cfg",))


def _program_mixer(kind, x, lp):
    if kind == "M":
        return _ssm_mixer(x, lp, None)[0]
    if kind == "E":
        return jax.jit(lambda x, lp: T.moe_mixer(x, lp, CFG)[0])(x, lp)
    q, k, v = T._qkv_block(x, lp, CFG, None)
    return x + T.attn_proj(T._attention(q, k, v, CFG, None), lp, CFG,
                           x.dtype)


@pytest.mark.parametrize("kind", ["M", "*", "E"])
def test_mixer_matches_the_reference(params, kind):
    i = _layer_of(kind)
    x = jax.random.normal(jax.random.fold_in(KEY, 5), (1, 40, M["D"]))
    got = _program_mixer(kind, x, params["layers"][i])[0]
    lw = W.layer(KEY, M, i)
    h = ref.rmsnorm(x[0], lw["ln1_scale"], M["eps"])
    want = x[0] + ref.mixer(kind, h, lw,
                            lambda e: W.expert(KEY, M, i, e), M)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


@pytest.mark.parametrize("length", [5, 16, 37, 48])
def test_chunked_prefill_equals_the_sequential_recurrence(params, length):
    """Lengths below, at, off and at a multiple of the chunk (16)."""
    i = _layer_of("M")
    x = jax.random.normal(jax.random.fold_in(KEY, length), (1, length, M["D"]))
    got, (tail, S_end) = _ssm_mixer(x, params["layers"][i], None)
    lw = W.layer(KEY, M, i)
    want = x[0] + ref.mamba(ref.rmsnorm(x[0], lw["ln1_scale"], M["eps"]),
                            lw, M)
    np.testing.assert_allclose(got[0], want, atol=TOL, rtol=0)
    assert tail.shape == (1, M["K"] - 1, M["conv_dim"])
    assert S_end.shape == (1, M["Hm"], M["P"], M["N"])


def test_state_under_bucket_padding_is_the_true_last_positions(params):
    """dt masked past last_pos keeps S; the tail is gathered there."""
    lp = params["layers"][_layer_of("M")]
    x = jax.random.normal(jax.random.fold_in(KEY, 9), (1, 32, M["D"]))
    _, (tail, S_true) = _ssm_mixer(x[:, :21], lp, None)
    _, (tail_p, S_pad) = _ssm_mixer(x, lp, jnp.array([20]))
    np.testing.assert_allclose(S_pad, S_true, atol=1e-6, rtol=0)
    np.testing.assert_array_equal(tail_p, tail)


# -- the served path against the reference's full forward --------------------

def _serve_logits(params, cfg, prompt, n_new, rung, slots=3, row=1,
                  state_dtype=None):
    """What the engine's programs wrap, with the logits kept: a
    bucket-padded paged prefill of one row, its state installed in row
    ``row`` of ``slots``, then ragged decode steps with the other rows
    idle. Returns (n_new + 1, V). ``state_dtype``: hold S in that type
    instead of the program's float32 (a step writes S back as it came)."""
    page = 16
    cache = D.init_paged_cache(cfg, slots, 8, page)
    if state_dtype is not None:
        cache["ssm"] = tuple(a.astype(state_dtype) for a in cache["ssm"])
    one = {k: v for k, v in cache.items() if k not in D.STATE_KEYS}
    one["table"] = cache["table"][row:row + 1]
    padded = np.zeros((1, rung), np.int32)
    padded[0, :len(prompt)] = prompt
    logits, out = _prefill(params, jnp.asarray(padded), cfg=cfg, cache=one,
                           page_size=page,
                           last_pos=jnp.int32(len(prompt) - 1))
    for k, v in out.items():
        if k in D.STATE_KEYS:
            cache[k] = tuple(a.at[row].set(r[0].astype(a.dtype))
                             for a, r in zip(cache[k], v))
        elif k != "table":
            cache[k] = v
    got = [logits[0]]
    active = jnp.arange(slots) == row
    pos = jnp.zeros((slots,), jnp.int32).at[row].set(len(prompt))
    tok = jnp.zeros((slots,), jnp.int32)
    for _ in range(n_new):
        tok = tok.at[row].set(jnp.argmax(got[-1]).astype(jnp.int32))
        logits, cache = _step(params, cache, pos, tok, cfg=cfg,
                              active=active)
        got.append(logits[row])
        pos = pos + active
    return np.stack(got), cache


def _teacher_forced(prompt, got):
    toks = np.argmax(got, axis=-1)[:-1]
    seq = np.concatenate([prompt, toks])
    rows = np.arange(len(prompt) - 1, len(seq))
    return ref_logits(seq, rows)


def test_padded_prefill_then_decode_is_the_reference_forward(params):
    prompt = tokens(21)
    got, _ = _serve_logits(params, CFG, prompt, 12, rung=32)
    np.testing.assert_allclose(got, _teacher_forced(prompt, got),
                               atol=TOL, rtol=0)


def test_a_bfloat16_state_fails_the_same_tolerance(params):
    """The configuration says float32, and the program holds S so: a
    lower precision of the state alone has to come out as not the
    reference."""
    prompt = tokens(21)
    got, cache = _serve_logits(params, CFG, prompt, 12, rung=32,
                               state_dtype=jnp.bfloat16)
    assert cache["ssm"][0].dtype == jnp.bfloat16
    assert np.abs(got - _teacher_forced(prompt, got)).max() > TOL


def test_idle_rows_state_does_not_move_across_a_chunk(params):
    cache = D.init_paged_cache(CFG, 3, 8, 16)
    fill = lambda a, i: jax.random.normal(
        jax.random.fold_in(KEY, 300 + i), a.shape).astype(a.dtype)
    for name in D.STATE_KEYS:
        cache[name] = tuple(fill(a, i) for i, a in enumerate(cache[name]))
    before = {n: [np.asarray(a) for a in cache[n]] for n in D.STATE_KEYS}
    pos = jnp.array([4, 7, 9], jnp.int32)
    limit = jnp.array([4, 20, 9], jnp.int32)    # rows 0 and 2 are idle
    out = S._chunk_step(
        S.serving_weights(params, CFG), cache, pos, limit,
        jnp.array([1, 2, 3], jnp.int32), jnp.zeros((3, 2), jnp.uint32),
        jnp.ones((3,), jnp.float32), cfg=CFG, chunk=4, eos_id=-1,
        greedy=True, top_k=0, mesh=None)
    after = out[0]
    for name in D.STATE_KEYS:
        for a, b in zip(before[name], after[name]):
            np.testing.assert_array_equal(np.asarray(b)[[0, 2]], a[[0, 2]])
            assert np.abs(np.asarray(b)[1] - a[1]).max() > 0
    # the route counted the live row alone: 4 steps x 1 token a layer
    assert after["moe_stats"].dtype == jnp.int32
    assert int(after["moe_stats"][1, 1]) == 4 * CFG.layer_pattern.count("E")


def _engine(params, **kw):
    return S.ContinuousBatcher(
        params, CFG, slots=kw.pop("slots", 3), pool_pages=24,
        pages_per_seq=8, page_size=16, chunk=4, prompt_buckets=[32, 64],
        **kw)


@pytest.fixture(scope="module")
def served(params):
    """Through ``ContinuousBatcher.run``: five requests over three slots
    (so slots are reused), bucket-padded, chunked. (engine, [(prompt,
    tokens)], the slot that served each)."""
    slot_of = {}
    eng = _engine(params, emit=lambda **kw: kw.get("kind") == "serve_admit"
                  and slot_of.update({kw["seq_id"]: kw["slot"]}))
    prompts = [tokens(18 + 5 * i, i) for i in range(5)]
    for i, p in enumerate(prompts):
        eng.submit(p, 5 + i, seq_id=i)
    done = eng.run()
    return (eng, [(p, np.asarray(done[i])) for i, p in enumerate(prompts)],
            [slot_of[i] for i in range(5)])


CHECK = json.loads((ROOT / "tests/chipbench/fixtures/tiny-hybrid.json")
                   .read_text())["check"]


def _judge(pairs):
    gap = serve_hybrid.serving_gap(SEED, M, pairs, CHECK["tail_above"],
                                   pad_to=64)
    checks = serve_hybrid.gap_checks(gap["judged"], CHECK)
    return gap, {n: v <= lim for n, v, lim in checks}


def test_engine_serves_what_the_reference_puts_first(served):
    """Every served token's reference logit lies within TOL of the
    reference's best, and the cell's comparison says so."""
    eng, pairs, _ = served
    gap, ok = _judge(pairs)
    assert [len(t) for _, t in pairs] == [5 + i for i in range(5)]
    assert gap["judged"]["widest"] <= TOL and all(ok.values())
    assert gap["judged"]["positions"] == sum(5 + i for i in range(5))
    stats = eng.route_stats()
    n_e = CFG.layer_pattern.count("E")
    prompts = [p for p, _ in pairs]
    assert stats[0, 1] == n_e * sum(len(p) for p in prompts)   # true tokens
    assert stats[1, 1] == n_e * sum(4 + i for i in range(5))   # live steps
    assert 0 < stats[:, 0].sum() / stats[:, 1].sum() < CFG.moe_top_k
    assert eng.state_bytes == 3 * sum(
        a[0].nbytes for k in D.STATE_KEYS for a in eng.cache[k])


@pytest.mark.parametrize("fault", ["one_slot", "a_reused_slots_second",
                                   "one_token"])
def test_a_fault_in_one_slot_is_not_correct(served, fault):
    """What the mean alone cannot be trusted to see: wrong tokens from one
    slot of three (every request it served; only the one that reused it),
    and ONE wrong token among the sample's 35."""
    _, pairs, slots = served
    wrong = lambda t: (t + 1) % M["V"]
    reused = next(s for s in slots if slots.count(s) > 1)
    hit = [i for i, s in enumerate(slots) if s == reused]
    if fault == "a_reused_slots_second":
        hit = hit[1:]
    pairs = [(p, t.copy()) for p, t in pairs]
    if fault == "one_token":
        pairs[0][1][2] = wrong(pairs[0][1][2])
    else:
        for i in hit:
            pairs[i] = (pairs[i][0], wrong(pairs[i][1]))
    gap, ok = _judge(pairs)
    assert not ok["served_gap_widest"], gap["judged"]
    if fault != "one_token":   # a request's worth of them: the tail too
        assert not ok["served_gap_tail_share"]


def test_route_counters_follow_the_sums_and_cost_no_read_when_off(params):
    """Metrics on: the counters are what the device sums grew by. Off: the
    engine never looks at the sums (``route_stats()`` is the caller's)."""
    from hpc_patterns_tpu.harness import metrics as metricslib
    eng = _engine(params)
    eng.submit(tokens(20, 5), 9, seq_id=0)
    eng.run()
    assert eng._route_seen is None
    mx = metricslib.configure(enabled=True)
    try:
        eng = _engine(params)
        eng.submit(tokens(20, 5), 9, seq_id=0)
        eng.run()
        stats = eng.route_stats()
        assert mx.counter("moe.local_picks").value == stats[:, 0].sum()
        assert mx.counter("moe.tokens").value == stats[:, 1].sum()
        assert 0 < mx.gauge("moe.experts_touched").last <= CFG.experts_held
        assert mx.gauge("moe.load_max_over_mean").last >= 1.0
    finally:
        metricslib.configure(enabled=False)


def test_a_reused_slot_starts_clean(params):
    first, second = tokens(20, 1), tokens(27, 2)
    eng = _engine(params, slots=1)
    eng.submit(first, 6, seq_id=0)
    eng.submit(second, 6, seq_id=1)
    both = eng.run()
    fresh = _engine(params, slots=1)
    fresh.submit(second, 6, seq_id=1)
    np.testing.assert_array_equal(both[1], fresh.run()[1])


def test_linear_cache_generation_follows_the_full_forward(params):
    prompt = jnp.asarray(np.stack([tokens(24, 3), tokens(24, 4)]))
    out = D.greedy_generate(params, prompt, CFG, 8)
    seq = jnp.concatenate([prompt, out], axis=1)
    want = jnp.argmax(T.forward(params, seq, CFG)[:, 23:-1], axis=-1)
    np.testing.assert_array_equal(out, want)


# -- the expert layer told which experts it holds -----------------------------

E_ALL, K_TOP, R, F = 32, 4, 16, 24


@pytest.fixture(scope="module")
def expert_layer():
    k = iter(jax.random.split(jax.random.PRNGKey(3), 12))
    n = lambda *s: jax.random.normal(next(k), s, jnp.float32) * s[-2] ** -0.5
    Dm = 32
    return {"x": jax.random.normal(next(k), (50, Dm)),
            "router": n(Dm, E_ALL),
            "bias": 0.1 * jax.random.normal(next(k), (E_ALL,)),
            "w_down": n(Dm, R), "w_up": n(R, Dm), "w1": n(E_ALL, R, F),
            "w2": n(E_ALL, F, R), "ws1": n(Dm, 40), "ws2": n(40, Dm)}


def _share(L, start, held, router=None, bias=None, valid=None):
    return moe.latent_moe(
        L["x"], L["router"] if router is None else router,
        L["bias"] if bias is None else bias, L["w_down"], L["w_up"],
        L["w1"][start:start + held], L["w2"][start:start + held],
        L["ws1"], L["ws2"], held_start=start, top_k=K_TOP, scale=2.5,
        valid=valid)


def _whole_reference(L, router=None, bias=None):
    """The uncut layer by the reference's masked loop over all experts."""
    m = {"k": K_TOP, "scale": 2.5, "held0": 0, "held": E_ALL}
    lw = {"router": L["router"] if router is None else router,
          "router_bias": L["bias"] if bias is None else bias,
          "w_down": L["w_down"], "w_up": L["w_up"], "ws1": L["ws1"],
          "ws2": L["ws2"]}
    return ref.moe(L["x"], lw, lambda e: (L["w1"][e], L["w2"][e]), m)


def test_the_shares_add_up_to_the_uncut_layer(expert_layer):
    """Four shares of 8 of 32 experts: their routed parts, with the
    shared expert (which every chip computes alike) counted once."""
    L = expert_layer
    shared = jnp.dot(moe.relu2(jnp.dot(L["x"], L["ws1"])), L["ws2"])
    parts = [_share(L, 8 * s, 8) for s in range(4)]
    total = sum(out - shared for out, _ in parts) + shared
    np.testing.assert_allclose(total, _whole_reference(L), atol=1e-5, rtol=0)
    picks = sum(float(st[0]) for _, st in parts)
    assert picks == 50 * K_TOP            # every pick computed exactly once


def test_holding_every_expert_is_the_dense_route(expert_layer):
    out, stats = _share(expert_layer, 0, E_ALL)
    np.testing.assert_allclose(out, _whole_reference(expert_layer),
                               atol=1e-5, rtol=0)
    assert float(stats[0]) == 50 * K_TOP and float(stats[1]) == 50


def test_no_pick_is_dropped_when_every_token_picks_the_same_experts(
        expert_layer):
    """A selection bias that sends all 50 tokens to experts 3, 9, 10, 11:
    a capacity route would drop most of them; this one computes all."""
    L = expert_layer
    bias = jnp.zeros((E_ALL,)).at[jnp.array([3, 9, 10, 11])].set(10.0)
    out, stats = _share(L, 8, 8, bias=bias)     # holds 9, 10, 11, not 3
    held_only = dict(L, w1=L["w1"].at[:8].set(0).at[16:].set(0),
                     w2=L["w2"].at[:8].set(0).at[16:].set(0))
    np.testing.assert_allclose(out, _whole_reference(held_only, bias=bias),
                               atol=1e-5, rtol=0)
    assert stats.dtype == jnp.int32
    picks, toks, max_load, touched, calls = (int(s) for s in stats)
    # the fullest held expert has all 50 tokens; 8 / 3 over the mean load
    assert (picks, toks, max_load, touched, calls) == (150, 50, 50, 3, 1)


def test_tokens_that_do_not_count_pick_nothing(expert_layer):
    valid = jnp.arange(50) < 20
    out, stats = _share(expert_layer, 0, E_ALL, valid=valid)
    full, _ = _share(expert_layer, 0, E_ALL)
    np.testing.assert_allclose(out[:20], full[:20], atol=1e-6, rtol=0)
    assert float(stats[0]) == 20 * K_TOP and float(stats[1]) == 20


# -- the default pattern is the parent's program -------------------------------

DENSE = T.TransformerConfig(
    vocab=256, d_model=64, n_heads=4, n_kv_heads=2, n_layers=3, d_ff=128,
    max_seq=128, dtype="float32", attention="full", pos_embed="rope",
    decode_attn="gather")
# sha256 of the StableHLO text that PR 25 (commit 6db83cf) lowers for the
# engine's three programs at DENSE (the prefill, the admission's device
# bookkeeping, whose signature and donation this PR changed, and the
# chunk), and its logits (prefill of 24 tokens, 16 decode steps, 2 rows):
# tests/fixtures/dense_block_pr25_logits.npy. PR 31 re-pinned the chunk:
# its K/V write indexes the head axis (decode._pool_write), another
# scatter of the same values, which the logits test below still holds to
# the PR 25 fixture
PARENT_PROGRAMS = {
    "_admit_row":
    "1bb6625a4fc7306bd9709f4592a8a4b7641654e1da0609877d86508b92c5918c",
    "_prefill_one":
    "31f87a71d09866b850979ced4ab3da6347112ce64df33d0581d1dc2e8dfeb196",
    "_chunk_step":
    "9c13bd1fb61bdca77a5bc66c73026fc5b0eaa948775605edcd01e8e131379c99",
}


@pytest.fixture(scope="module")
def dense():
    params = T.init_params(jax.random.PRNGKey(7), DENSE)
    prompt = jax.random.randint(jax.random.PRNGKey(8), (2, 24), 0, 256)
    return params, prompt, D.init_paged_cache(DENSE, 2, 4, 16)


@pytest.mark.parametrize("program", sorted(PARENT_PROGRAMS))
def test_default_pattern_lowers_to_the_parents_program(dense, program):
    """Bit-identity shown where it is decided: the same StableHLO, so the
    same executable, whatever machine runs the test."""
    params, prompt, cache = dense
    if program == "_prefill_one":
        one = dict(cache, table=cache["table"][:1])
        low = S._prefill_one.lower(params, prompt[:1], jnp.int32(20), one,
                                   cfg=DENSE, page_size=16, mesh=None)
    elif program == "_admit_row":   # no state: the default pattern's call
        z = jnp.zeros((2,), jnp.int32)
        low = S._admit_row.lower(
            z, z, z, jnp.zeros((2, 2), jnp.uint32),
            jnp.ones((2,), jnp.float32), jnp.zeros((1, 256), jnp.float32),
            jnp.zeros((2,), jnp.uint32), jnp.float32(1.0), jnp.int32(1),
            jnp.int32(20), jnp.int32(6), eos_id=-1, greedy=True, top_k=0)
    else:
        z = jnp.zeros((2,), jnp.int32)
        low = S._chunk_step.lower(
            params, cache, z, z, z, jnp.zeros((2, 2), jnp.uint32),
            jnp.ones((2,), jnp.float32), cfg=DENSE, chunk=4, eos_id=-1,
            greedy=True, top_k=0, mesh=None)
    assert hashlib.sha256(low.as_text().encode()).hexdigest() \
        == PARENT_PROGRAMS[program]


def test_default_pattern_gives_the_parents_logits(dense):
    params, prompt, cache = dense
    logits, cache = _prefill(params, prompt, cfg=DENSE, cache=cache,
                             page_size=16)
    got = [np.asarray(logits)]
    for i in range(16):
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        logits, cache = _step(
            params, cache, jnp.full((2,), 24 + i, jnp.int32), tok, cfg=DENSE)
        got.append(np.asarray(logits))
    want = np.load(ROOT / "tests/fixtures/dense_block_pr25_logits.npy")
    # recorded op by op on the parent; here each program is jitted whole,
    # and XLA:CPU's fusions round in another order (1.7e-6 seen). The
    # identity of the programs themselves is pinned to the bit above
    np.testing.assert_allclose(np.stack(got), want, atol=1e-5, rtol=0)


# -- what the engine holds, counts and refuses ---------------------------------

def test_serving_weights_keep_the_float32_leaves(params):
    cfg = dataclasses.replace(CFG, dtype="bfloat16")
    held = S.serving_weights(params, cfg)
    for lp, kind in zip(held["layers"], cfg.layer_pattern):
        for name, a in lp.items():
            keep = name in ("router", "router_bias", "A_log", "D", "dt_bias")
            assert a.dtype == (jnp.float32 if keep else jnp.bfloat16), \
                (kind, name)
    assert held["embed"].dtype == jnp.bfloat16


class _Residency:
    pass


@pytest.mark.parametrize("feature,kwargs", [
    ("prefix_cache", {"prefix_cache": True}),
    ("preempt", {"preempt": True}),
    ("residency", {"residency": _Residency()}),
    ("draft_params", {"draft_params": {}, "draft_cfg": CFG}),
])
def test_engine_refuses_by_feature_and_says_why(params, feature, kwargs):
    with pytest.raises(ValueError, match=f"{feature} with a patterned model"):
        _engine(params, **kwargs)


def test_engine_refuses_to_migrate_recurrent_state(params):
    eng = _engine(params)
    with pytest.raises(ValueError, match="migration with a patterned"):
        eng.export_migration(0)


@pytest.mark.parametrize("route", ["extend_step", "paged_extend_step",
                                   "paged_tail_prefill"])
def test_multi_token_routes_refuse_a_patterned_model(params, route):
    tok = jnp.zeros((1, 4), jnp.int32)
    call = {
        "extend_step": lambda: D.extend_step(params, {}, 0, tok, CFG),
        "paged_extend_step": lambda: D.paged_extend_step(
            params, {}, jnp.zeros((1,), jnp.int32), tok, CFG),
        "paged_tail_prefill": lambda: D.paged_tail_prefill(
            params, tok, CFG, {}, 16, 1),
    }[route]
    with pytest.raises(ValueError, match="default layer pattern only"):
        call()


@pytest.mark.parametrize("bad", [
    {"layer_pattern": "MEM"},                       # not one a layer
    {"layer_pattern": "MEM*X"},                     # unknown kind
    {"layer_pattern": "MEM*B"},                     # the default's block
    {"ssm_heads": 0},
    {"moe_held": 16, "moe_held_start": 8},          # range past the experts
    {"moe_top_k": 0},
    {"pos_embed": "sinusoid"},
])
def test_config_says_what_a_pattern_needs(bad):
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, **bad)


def test_engine_serves_the_blocks_own_moe():
    """``n_experts`` (the block's capacity-free decode route) is no longer
    refused: the engine's tokens are the linear cache's."""
    cfg = dataclasses.replace(DENSE, n_experts=4, n_experts_top_k=2)
    params = T.init_params(jax.random.PRNGKey(5), cfg)
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(6), (20,), 0,
                                           256), np.int32)
    eng = S.ContinuousBatcher(params, cfg, slots=2, pool_pages=8,
                              pages_per_seq=4, page_size=16, chunk=4)
    eng.submit(prompt, 6, seq_id=0)
    want = D.greedy_generate(params, jnp.asarray(prompt)[None], cfg, 6)[0]
    np.testing.assert_array_equal(eng.run()[0], want)


# -- the phases a device trace is read by ---------------------------------------

def _abstract(tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        tree)


@pytest.fixture(scope="module")
def lowered(params):
    cache = _abstract(jax.eval_shape(
        lambda: D.init_paged_cache(CFG, 2, 4, 16)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    one = {k: v for k, v in cache.items() if k not in D.STATE_KEYS}
    one["table"] = i32(1, 4)
    p = _abstract(params)
    return {
        "prefill": S._prefill_one.lower(
            p, i32(1, 32), i32(), one, cfg=CFG, page_size=16,
            mesh=None).as_text(debug_info=True),
        "chunk": S._chunk_step.lower(
            p, cache, i32(2), i32(2), i32(2),
            jax.ShapeDtypeStruct((2, 2), jnp.uint32),
            jax.ShapeDtypeStruct((2,), jnp.float32), cfg=CFG, chunk=2,
            eos_id=-1, greedy=True, top_k=0, mesh=None
        ).as_text(debug_info=True),
    }


COMMON = ["ssm/conv", "moe/route", "moe/latent", "moe/experts", "moe/shared",
          "attn", "head", "kv_write", "embed"]
SCOPES = {"prefill": COMMON + ["ssm/scan", "ssm/state_write"],
          "chunk": COMMON + ["ssm/step", "ssm/step/state_write",
                             "ssm/step/jit(_call)", "sample"]}


@pytest.mark.parametrize("program,path", [
    (prog, path) for prog, paths in SCOPES.items() for path in paths])
def test_scope_shows_in_the_programs_metadata(lowered, program, path):
    want = path.split("/")
    locs = {loc for loc in re.findall(r'loc\("([^"]*)"', lowered[program])}
    assert any(parts[i:i + len(want)] == want
               for parts in (loc.split("/") for loc in locs)
               for i in range(len(parts))), (program, path)


@pytest.mark.parametrize("program", ["prefill", "chunk"])
def test_the_grouped_products_are_the_repos_kernel(lowered, program):
    """The held experts' products are calls of the jitted kernel wrapper
    under ``moe/experts`` whose body is the call named ``grouped_matmul``
    (XLA joins the two into the path a device trace finds it by:
    tests/test_chip_compile.py), and the plain formulation they replaced
    is in neither program."""
    locs = set(re.findall(r'loc\("([^"]*)"', lowered[program]))
    assert any(loc.endswith("moe/experts/jit(_call)") for loc in locs)
    assert any(loc.startswith("grouped_matmul/") for loc in locs)
    assert "ragged_dot" not in lowered[program]


def test_the_pass_over_the_state_is_the_repos_kernel(lowered):
    """A decode step's pass over S is the jitted wrapper of the call named
    ``ssm_step`` under ``ssm/step`` (the scope ``ssm_step_decode_roofline``
    reads), and nothing in the chunk selects over a whole state: idle rows
    keep theirs because the kernel never visits them."""
    chunk = lowered["chunk"]
    locs = set(re.findall(r'loc\("([^"]*)"', chunk))
    assert any(loc.endswith("ssm/step/jit(_call)") for loc in locs)
    assert any(loc.startswith("ssm_step/") for loc in locs)
    state = "x".join(map(str, (2, CFG.ssm_heads, CFG.ssm_head_dim,
                               CFG.ssm_state)))
    assert f"tensor<{state}xf32>" in chunk      # the state is there
    assert not [line for line in chunk.splitlines()
                if "stablehlo.select" in line and f"<{state}x" in line]
