"""The parallel hybrid block (``H``: attention and Mamba-2 off one norm,
summed, then a SiLU-gated MLP, under the config's multipliers) on the CPU
at a small size: the whole served path against the plain reference
(``chipbench/reference/falcon_h1.py``) on seeded weights, the rules of a
layer that holds K/V AND a row of state, each multiplier and each half of
the block shown to matter, and the recurrence's two forms at the published
state shape.

Tolerances. The program runs here in float32 (``dtype="float32"``) against
a float32 reference, so what separates them is the order of the sums (the
chunked recurrence against the sequential one, the fused qkv product, a
multiplier applied to a product's input where the reference scales the
same value). ``TOL`` = 2e-4 on logits of size ~1 is an order above the
largest such gap seen (1.4e-6 on the full forward, 2e-5 through the paged
decode) and far below what any of the faults reads: a multiplier left out
>= 2e-3 (the smallest: ``attention_out_multiplier``), a half left out
>= 0.1, the state held in bfloat16 >= 1e-3.
"""

import dataclasses
import json
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from chipbench import weights_falcon_h1 as W  # noqa: E402
from chipbench.drivers import serve_falcon_h1  # noqa: E402
from chipbench.reference import falcon_h1 as ref  # noqa: E402
from hpc_patterns_tpu.models import decode as D  # noqa: E402
from hpc_patterns_tpu.models import serving as S  # noqa: E402
from hpc_patterns_tpu.models import ssm  # noqa: E402
from hpc_patterns_tpu.models import transformer as T  # noqa: E402
from hpc_patterns_tpu.ops.ssm_step import ssm_step, ssm_step_reference  # noqa: E402

TOL = 2e-4
SEED = 2**31 + 13
CONFIG = json.loads(
    (ROOT / "tests/chipbench/fixtures/tiny-falcon-h1.json").read_text())
M = W.model_dims(CONFIG)
CFG = dataclasses.replace(
    serve_falcon_h1.model_config(CONFIG, {"decode_attn": "gather"}),
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


_forward = jax.jit(T.forward, static_argnames=("cfg",))
_prefill = jax.jit(D.paged_prefill, static_argnames=("cfg", "page_size"))
_step = jax.jit(D.paged_decode_step, static_argnames=("cfg",))


# -- the block, whole ---------------------------------------------------------

def test_the_fixture_is_the_published_shape_in_small():
    """Heads x head size is not the hidden size (6 x 16 against 64, as 20
    x 128 against 5120), every layer holds K/V and a row of state, and no
    multiplier is 1 (``attention_in_multiplier`` is, as published, and
    would hide its own omission: the fixture gives it 1.5)."""
    assert CFG.attn_width == 96 != CFG.d_model == 64
    assert CFG.head_dim == 16 and CFG.layer_pattern == "HHH"
    assert CFG.n_attn_layers == CFG.n_state_layers == 3
    assert len(CFG.multipliers) == 14 and 1.0 not in CFG.multipliers


def test_full_forward_is_the_reference(params):
    seq = tokens(48)
    got = _forward(params, jnp.asarray(seq)[None], cfg=CFG)[0]
    np.testing.assert_allclose(got, ref_logits(seq, np.arange(48)),
                               atol=TOL, rtol=0)


def _serve_logits(params, cfg, prompt, n_new, rung, slots=3, row=1,
                  state_dtype=None):
    """What the engine's programs wrap, with the logits kept: a
    bucket-padded paged prefill of one row, its state installed in row
    ``row`` of ``slots``, then ragged decode steps with the other rows
    idle. Returns ((n_new + 1, V), the cache)."""
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


def _teacher_forced(prompt, got, **kw):
    toks = np.argmax(got, axis=-1)[:-1]
    seq = np.concatenate([prompt, toks])
    return ref_logits(seq, np.arange(len(prompt) - 1, len(seq)), **kw)


@pytest.fixture(scope="module")
def served_logits(params):
    prompt = tokens(21)
    got, cache = _serve_logits(params, CFG, prompt, 12, rung=32)
    return prompt, got, cache


def test_padded_prefill_then_paged_decode_is_the_reference_forward(
        served_logits):
    prompt, got, cache = served_logits
    np.testing.assert_allclose(got, _teacher_forced(prompt, got),
                               atol=TOL, rtol=0)
    # the same three layers hold pools of pages AND rows of state
    assert len(cache["k"]) == len(cache["ssm"]) == len(cache["conv"]) == 3
    assert cache["k"][0].shape[1:] == (CFG.kv_heads, 16, CFG.head_dim)


def test_a_bfloat16_state_fails_the_same_tolerance(params, served_logits):
    prompt, _, _ = served_logits
    got, cache = _serve_logits(params, CFG, prompt, 12, rung=32,
                               state_dtype=jnp.bfloat16)
    assert cache["ssm"][0].dtype == jnp.bfloat16
    assert np.abs(got - _teacher_forced(prompt, got)).max() > TOL


# -- nothing can be dropped unseen ---------------------------------------------

def _without(index):
    """The config with the ``index``-th of the fourteen multiplier values
    set to 1: in the PROGRAM alone, the weights and the reference keep
    the fixture's."""
    flat = list(CFG.multipliers)
    flat[index] = 1.0
    return dataclasses.replace(
        CFG, embedding_multiplier=flat[0], attention_in_multiplier=flat[1],
        key_multiplier=flat[2], attention_out_multiplier=flat[3],
        ssm_in_multiplier=flat[4], ssm_multipliers=tuple(flat[5:10]),
        ssm_out_multiplier=flat[10], mlp_multipliers=tuple(flat[11:13]),
        lm_head_multiplier=flat[13])


MULTIPLIERS = ["embedding", "attention_in", "key", "attention_out", "ssm_in",
               "ssm_z", "ssm_x", "ssm_B", "ssm_C", "ssm_dt", "ssm_out",
               "mlp_gate", "mlp_down", "lm_head"]


@pytest.mark.parametrize("index", range(14), ids=MULTIPLIERS)
def test_a_multiplier_left_out_moves_the_logits(params, index):
    seq = tokens(48)
    got = _forward(params, jnp.asarray(seq)[None], cfg=_without(index))[0]
    assert np.abs(got - ref_logits(seq, np.arange(48))).max() > 10 * TOL


@pytest.mark.parametrize("half", ["attn", "mamba"])
def test_a_half_left_out_of_the_sum_reads_not_equal(params, half):
    """The reference with one mixer's output dropped from the residual
    (what a block that ran its mixers in sequence, or forgot one, would
    compute) is not what the program computes."""
    seq = tokens(48)
    x = ref._embed(KEY, jnp.asarray(seq), m=ref._freeze(M))
    for l in range(M["L"]):
        x = ref.block(x, W.layer(KEY, M, l), M, halves=(half,))
    want = ref._head(KEY, x, m=ref._freeze(M), lowp=None)
    got = _forward(params, jnp.asarray(seq)[None], cfg=CFG)[0]
    assert np.abs(got - np.asarray(want)).max() > 0.1


def test_multipliers_belong_to_an_all_h_pattern():
    with pytest.raises(ValueError, match="'H' alone"):
        T.TransformerConfig(n_layers=2, layer_pattern="H*", ssm_heads=2,
                            key_multiplier=0.5)
    with pytest.raises(ValueError, match="'H' alone"):
        T.TransformerConfig(lm_head_multiplier=0.5)
    with pytest.raises(ValueError, match="five values"):
        T.TransformerConfig(n_layers=1, layer_pattern="H", ssm_heads=2,
                            ssm_multipliers=(1.0, 1.0))


def test_drawn_weights_leave_attention_and_the_decays_alive():
    """At unit scale ``key_multiplier`` 0.011 would flatten every
    attention row and the comparison would see neither rope nor the keys;
    at the scales of ``weights_falcon_h1`` the scores are of order one,
    and every step's decay exp(dt A) lies strictly between 0 and 1."""
    lw = W.layer(KEY, M, 0)
    h = jax.random.normal(jax.random.fold_in(KEY, 9), (64, M["D"]))
    H, Hkv, Dh = M["H"], M["Hkv"], M["Dh"]
    qkv = (h * M["m_attn_in"]) @ lw["wqkv"]
    q, k, _ = jnp.split(qkv, [H * Dh, (H + Hkv) * Dh], axis=-1)
    pos = jnp.arange(64)
    q = ref.rope(q.reshape(64, H, Dh), pos, M["theta"])
    k = ref.rope((k * M["m_key"]).reshape(64, Hkv, Dh), pos, M["theta"])
    s = jnp.einsum("qd,kd->qk", q[:, 0], k[:, 0]) / Dh ** 0.5
    p = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((64, 64), bool)), s,
                                 -jnp.inf), axis=-1)
    assert 0.3 < float(jnp.std(s)) < 3.0
    assert float(p[-1].max()) > 2.0 / 64   # the uniform row's is 1 / 64
    di, bc = M["d_inner"], M["G"] * M["N"]
    dt = ((h * M["m_ssm_in"]) @ lw["in_proj"])[:, 2 * di + 2 * bc:]
    dt = jax.nn.softplus(dt * M["m_ssm"][4] + lw["dt_bias"])
    decay = jnp.exp(-dt * jnp.exp(lw["A_log"]))
    assert 0.0 < float(decay.min()) and float(decay.max()) < 1.0


# -- the engine: a row of state for every layer beside the pools ---------------

def _engine(params, **kw):
    return S.ContinuousBatcher(
        params, CFG, slots=kw.pop("slots", 3), pool_pages=24,
        pages_per_seq=8, page_size=16, chunk=4, prompt_buckets=[32, 64],
        **kw)


def test_engine_serves_what_the_reference_puts_first(params):
    """Through ``ContinuousBatcher.run``: five requests over three slots
    (so slots are reused), bucket-padded, chunked; every served token's
    reference logit lies within TOL of the reference's best."""
    eng = _engine(params)
    prompts = [tokens(18 + 5 * i, i) for i in range(5)]
    for i, p in enumerate(prompts):
        eng.submit(p, 5 + i, seq_id=i)
    done = eng.run()
    pairs = [(p, np.asarray(done[i])) for i, p in enumerate(prompts)]
    gap = serve_falcon_h1.serving_gap(SEED, M, pairs, pad_to=64)
    assert gap["judged"]["positions"] == sum(5 + i for i in range(5))
    assert gap["judged"]["widest"] <= TOL
    assert gap["one_wrong"]["widest_of_a_request_min"] > 1.0
    state_row = sum(a[0].nbytes for k in D.STATE_KEYS for a in eng.cache[k])
    assert eng.state_bytes == 3 * state_row
    assert eng.kv_bytes_per_token == (
        3 * 2 * CFG.kv_heads * CFG.head_dim * 4)   # float32 here


def test_a_reused_slot_starts_clean(params):
    first, second = tokens(20, 1), tokens(27, 2)
    eng = _engine(params, slots=1)
    eng.submit(first, 6, seq_id=0)
    eng.submit(second, 6, seq_id=1)
    both = eng.run()
    fresh = _engine(params, slots=1)
    fresh.submit(second, 6, seq_id=1)
    np.testing.assert_array_equal(both[1], fresh.run()[1])


def test_idle_rows_keep_state_and_pages_bit_for_bit(params):
    cache = D.init_paged_cache(CFG, 3, 8, 16)
    fill = lambda a, i: jax.random.normal(
        jax.random.fold_in(KEY, 300 + i), a.shape).astype(a.dtype)
    for name in D.STATE_KEYS + ("k", "v"):
        cache[name] = tuple(fill(a, i) for i, a in enumerate(cache[name]))
    before = {n: [np.asarray(a) for a in cache[n]]
              for n in D.STATE_KEYS + ("k", "v")}
    table = np.asarray(cache["table"])
    cursor = [4, 7, 9]
    limit = jnp.array([4, 20, 9], jnp.int32)    # rows 0 and 2 are idle
    after = S._chunk_step(
        S.serving_weights(params, CFG), cache,
        jnp.array(cursor, jnp.int32), limit,
        jnp.array([1, 2, 3], jnp.int32), jnp.zeros((3, 2), jnp.uint32),
        jnp.ones((3,), jnp.float32), cfg=CFG, chunk=4, eos_id=-1,
        greedy=True, top_k=0, mesh=None)[0]
    for name in D.STATE_KEYS:
        for a, b in zip(before[name], after[name]):
            np.testing.assert_array_equal(np.asarray(b)[[0, 2]], a[[0, 2]])
            assert np.abs(np.asarray(b)[1] - a[1]).max() > 0
    # an idle row's step still lands at its cursor, in a page it owns (the
    # engine's idle slots point at the trash page): a position no query
    # can see. Every position that can be read keeps its bits
    for name in ("k", "v"):
        for a, b in zip(before[name], after[name]):
            b = np.array(b)
            for row in (0, 2):
                at = (table[row, 0], slice(None), cursor[row])
                assert np.abs(b[at] - a[at]).max() > 0
                b[at] = a[at]
            idle_pages = np.concatenate([table[0], table[2]])
            np.testing.assert_array_equal(b[idle_pages], a[idle_pages])
            assert np.abs(b[table[1, 0]] - a[table[1, 0]]).max() > 0


@pytest.mark.parametrize("feature,kwargs", [
    ("prefix_cache", {"prefix_cache": True}),
    ("preempt", {"preempt": True}),
])
def test_engine_refuses_for_h_what_it_refuses_for_a_pattern(params, feature,
                                                            kwargs):
    with pytest.raises(ValueError, match=f"{feature} with a patterned"):
        _engine(params, **kwargs)


def test_multi_token_routes_refuse_the_block(params):
    cache = D.init_paged_cache(CFG, 1, 8, 16)
    with pytest.raises(ValueError, match="default layer pattern only"):
        D.paged_extend_step(params, cache, jnp.zeros((1,), jnp.int32),
                            jnp.zeros((1, 2), jnp.int32), CFG)


# -- spans and scopes -----------------------------------------------------------

def test_spans_carry_the_bytes_of_both_halves_of_the_cache(params):
    """``serve.prefill`` says the state and the K/V bytes an admission
    wrote, ``serve.decode_dispatch`` the live rows' summed positions; with
    metrics off the engine computes neither."""
    from hpc_patterns_tpu.harness import metrics as metricslib
    seen = []

    class Sink:
        def span_begin(self, path, attrs, t0):
            seen.append((path.rsplit("/", 1)[-1], dict(attrs)))

        def span_end(self, *a, **kw):
            pass

    mx = metricslib.configure(enabled=True)
    metricslib._trace_sink, was = Sink(), metricslib._trace_sink
    try:
        eng = _engine(params)
        eng.submit(tokens(20, 5), 9, seq_id=0)
        eng.run()
    finally:
        metricslib._trace_sink = was
        metricslib.configure(enabled=False)
    pre = next(a for n, a in seen if n == "serve.prefill")
    assert pre["kv_bytes"] == 32 * eng.kv_bytes_per_token   # the rung: 2 pages
    assert pre["state_bytes"] == eng.state_bytes // 3
    chunks = [a for n, a in seen if n == "serve.decode_dispatch"]
    assert chunks[0]["ctx_tokens"] == 20 and chunks[0]["rows"] == 1
    assert chunks[1]["ctx_tokens"] == 24
    # the pages of 16 a K/V head's fetches cover: positions 0 .. 20, 0 .. 24
    assert [c["kv_pages"] for c in chunks[:2]] == [2, 2]
    assert mx.gauge("engine.kv_bytes_per_token").last == \
        eng.kv_bytes_per_token
    assert mx.gauge("engine.state_bytes").last == eng.state_bytes


@pytest.fixture(scope="module")
def lowered(params):
    cfg = dataclasses.replace(CFG, decode_attn="flash")
    abstract = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    cache = abstract(jax.eval_shape(
        lambda: D.init_paged_cache(cfg, 2, 4, 16)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    one = {k: v for k, v in cache.items() if k not in D.STATE_KEYS}
    one["table"] = i32(1, 4)
    p = abstract(params)
    return {
        "prefill": S._prefill_one.lower(
            p, i32(1, 32), i32(), one, cfg=cfg, page_size=16,
            mesh=None).as_text(debug_info=True),
        "chunk": S._chunk_step.lower(
            p, cache, i32(2), i32(2), i32(2),
            jax.ShapeDtypeStruct((2, 2), jnp.uint32),
            jax.ShapeDtypeStruct((2,), jnp.float32), cfg=cfg, chunk=2,
            eos_id=-1, greedy=True, top_k=0, mesh=None
        ).as_text(debug_info=True),
    }


COMMON = ["attn", "ssm/conv", "mlp/gate_up", "mlp/down", "kv_write", "embed",
          "head"]
SCOPES = {"prefill": COMMON + ["ssm/scan", "ssm/state_write"],
          "chunk": COMMON + ["ssm/step", "ssm/step/state_write",
                             "ssm/step/jit(_call)",
                             "attn/jit(_paged_call)", "sample"]}


@pytest.mark.parametrize("program,path", [
    (prog, path) for prog, paths in SCOPES.items() for path in paths])
def test_scope_shows_in_the_programs_metadata(lowered, program, path):
    want = path.split("/")
    locs = set(re.findall(r'loc\("([^"]*)"', lowered[program]))
    assert any(parts[i:i + len(want)] == want
               for parts in (loc.split("/") for loc in locs)
               for i in range(len(parts))), (program, path)


# -- the recurrence at the published state shape ------------------------------

def _published_shape(b, T=None):
    """State 256, head 128, 2 groups (of 4 heads here, 16 as published)."""
    H, P, N, G = 8, 128, 256, 2
    k = iter(jax.random.split(jax.random.fold_in(KEY, 41), 8))
    lead = (b,) if T is None else (b, T)
    x = jax.random.normal(next(k), (*lead, H, P))
    dt = jax.nn.softplus(jax.random.normal(next(k), (*lead, H)) - 3.0)
    A = -jnp.exp(jnp.log(jax.random.uniform(next(k), (H,), minval=1.0,
                                            maxval=16.0)))
    B = jax.random.normal(next(k), (*lead, G, N))
    C = jax.random.normal(next(k), (*lead, G, N))
    S0 = jax.random.normal(next(k), (b, H, P, N))
    return S0, x, dt, A, B, C


def test_ssm_step_at_the_published_state_shape():
    """8 heads of (128, 256) float32 are one 1 MiB tile of the kernel
    (``_head_block``); against the plain one-pass formulation to float32
    rounding (the sum over N runs in another order), idle rows bit for
    bit."""
    S0, x, dt, A, B, C = _published_shape(3)
    active = jnp.array([True, False, True])
    y, S1 = ssm_step(S0, x, dt, A, B, C, active)
    yr, Sr = ssm_step_reference(S0, x, dt, A, B, C, active)
    np.testing.assert_allclose(y, yr, atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(S1, Sr, atol=1e-6, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(S1)[1], np.asarray(S0)[1])


def test_ssd_chunked_at_the_published_state_shape():
    """The chunked form (chunk 128, the last one ragged) against the plain
    scan over positions from the same entering state: float32 on both
    sides, the order of the sums apart (1e-3 of values of size ~10)."""
    S0, x, dt, A, B, C = _published_shape(1, T=160)
    y, S_end = jax.jit(ssm.ssd_chunked, static_argnames=("chunk",))(
        x, dt, A, B, C, chunk=128, S0=S0)

    def step(Sc, t):
        x_t, dt_t, B_t, C_t = t
        y_t, Sn = ssm_step_reference(Sc, x_t, dt_t, A, B_t, C_t)
        return Sn, y_t

    S_ref, y_ref = lax.scan(step, S0, tuple(
        jnp.moveaxis(a, 1, 0) for a in (x, dt, B, C)))
    np.testing.assert_allclose(y, jnp.moveaxis(y_ref, 0, 1), atol=2e-3,
                               rtol=1e-4)
    np.testing.assert_allclose(S_end, S_ref, atol=2e-3, rtol=1e-4)
