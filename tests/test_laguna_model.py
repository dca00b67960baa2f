"""The "D" and "W" layer kinds (global and window attention side by side,
each with its own head count and rotation, a per-head output gate, the
window layers' K/V in a ring of pages a row) and the routed layer's shared
expert and scale, against ``chipbench/reference/laguna.py`` on the CPU:
seeded random weights, tiny widths, float32. Beside them, the windowed
kernels against a dense masked form, YaRN against its equations, the
shares of the experts against the uncut layer, and the accepted cells'
programs against the parent's."""

import dataclasses
import hashlib
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from chipbench import weights_laguna as W  # noqa: E402
from chipbench.drivers import serve_laguna  # noqa: E402
from chipbench.reference import laguna as ref  # noqa: E402

from hpc_patterns_tpu.harness import metrics as metricslib  # noqa: E402
from hpc_patterns_tpu.models import decode, serving  # noqa: E402
from hpc_patterns_tpu.models import transformer as tm  # noqa: E402
from hpc_patterns_tpu.models.serving import ContinuousBatcher  # noqa: E402
from hpc_patterns_tpu.ops.flash_attention import flash_attention  # noqa: E402
from hpc_patterns_tpu.ops.flash_decode import flash_decode_paged  # noqa: E402
from hpc_patterns_tpu.parallel import moe  # noqa: E402
from hpc_patterns_tpu.parallel.ring_attention import full_attention  # noqa: E402

TINY = json.loads(
    (ROOT / "tests/chipbench/fixtures/tiny-laguna.json").read_text())
SEED = 2**31 + 3
PAGE = 8


def config(route="gather"):
    cfg = serve_laguna.model_config(TINY, {"decode_attn": route})
    return dataclasses.replace(cfg, dtype="float32", attention="full")


def served_params(m, seed=SEED):
    """The driver's tree, its bfloat16 leaves read back in float32: the
    numbers the reference computes with."""
    tree = jax.jit(lambda k: W.build(k, m, jnp.bfloat16))(W.seed_key(seed))
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


@pytest.fixture(scope="module")
def model():
    m = W.model_dims(TINY)
    return m, served_params(m)


def tokens(n, seed=0, vocab=512):
    return np.random.default_rng(seed).integers(0, vocab, size=n,
                                                dtype=np.int32)


paged_prefill = jax.jit(decode.paged_prefill,
                        static_argnames=("cfg", "page_size"))
paged_step = jax.jit(decode.paged_decode_step, static_argnames=("cfg",))


# -- the model against its reference --------------------------------------------

def test_the_pattern_and_the_kinds_are_the_configurations(model):
    m, _ = model
    cfg = config()
    assert cfg.layer_pattern == m["pattern"] == "DWWWR"
    assert cfg.window_layers == (False, True, True, True, False)
    assert cfg.attn_kind("W") == tm.AttnKind(6, 24, (1e4, 16, ()), True)
    assert cfg.attn_kind("D") == cfg.attn_kind("R") == tm.AttnKind(
        4, 0, m["rope_g"], True)
    assert cfg.ring_pages(PAGE) == 4 and cfg.n_routed_layers == 4
    assert tm.LAYER_KINDS["D"].mlp == "gated"
    assert tm.LAYER_KINDS["W"] == tm.LayerKind(True, False, "routed", True)


def test_the_forward_is_the_references(model):
    m, params = model
    toks = tokens(70, 1)
    got = jax.jit(lambda t: tm.forward(params, t[None], config())[0])(toks)
    want = ref.logits_at(SEED, m, [toks], [np.arange(70)], pad_to=70)[0]
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)


@pytest.mark.parametrize("route", ["gather", "flash"])
def test_prefill_then_decode_through_the_rings_is_the_reference(model,
                                                                route):
    """40 prompt tokens, then 48 steps: every window layer's ring of 4
    pages of 8 wraps more than once (the window is 24)."""
    m, params = model
    cfg = config(route)
    toks = tokens(88, 2)
    cache = decode.init_paged_cache(cfg, 1, 12, PAGE)
    assert cache["k"][1].shape == (4, 2, PAGE, 16)     # the ring of row 0
    assert cache["k"][0].shape == (12, 2, PAGE, 16)    # the arena
    logits, cache = paged_prefill(params, jnp.asarray(toks[None, :40]), cfg,
                                  cache, PAGE)
    got = [logits[0]]
    for t in range(40, 87):
        logits, cache = paged_step(params, cache, jnp.array([t]),
                                   jnp.asarray(toks[t:t + 1]), cfg)
        got.append(logits[0])
    want = ref.logits_at(SEED, m, [toks], [np.arange(39, 87)], pad_to=88)[0]
    np.testing.assert_allclose(np.stack(got), want, atol=2e-4, rtol=0)


def test_a_padded_prompt_writes_its_last_pages_into_the_ring(model):
    """A prompt of 37 true tokens prefilled at a rung of 64: the ring
    takes the pages that end at position 36 (2..4), the decode steps see
    the true prefix alone."""
    m, params = model
    cfg = config()
    toks = tokens(60, 3)
    cache = decode.init_paged_cache(cfg, 1, 12, PAGE)
    prompt = np.concatenate([toks[:37], np.zeros(27, np.int32)])
    logits, cache = paged_prefill(params, jnp.asarray(prompt[None]), cfg,
                                  cache, PAGE, last_pos=jnp.int32(36))
    got = [logits[0]]
    for t in range(37, 59):
        logits, cache = paged_step(params, cache, jnp.array([t]),
                                   jnp.asarray(toks[t:t + 1]), cfg)
        got.append(logits[0])
    want = ref.logits_at(SEED, m, [toks], [np.arange(36, 59)], pad_to=60)[0]
    np.testing.assert_allclose(np.stack(got), want, atol=2e-4, rtol=0)


def test_the_window_changes_the_model(model):
    """Without its window a window layer is another model: the test above
    can see the window."""
    m, params = model
    toks = tokens(70, 1)
    whole = dataclasses.replace(config(), window=512)
    a = tm.forward(params, toks[None], config())[0, -1]
    b = tm.forward(params, toks[None], whole)[0, -1]
    assert float(jnp.max(jnp.abs(a - b))) > 1e-2


# -- the windowed kernels ----------------------------------------------------------

def dense_window(q, k, v, window):
    """q (B, T, H, D), k/v (B, T, Hkv, D): softmax over i - window < j <= i,
    an explicit mask, float32."""
    B, T, H, D = q.shape
    g = H // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(D)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    s = jnp.where((j <= i) & (j > i - window), s, -jnp.inf)
    return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("window", [24, 128, 200, 512])
def test_the_windowed_flash_fwd_is_dense_masked_attention(window):
    """Tiles of 128 over 512 positions: a query tile's keys a window back
    start in another tile, and the tiles wholly behind it are skipped."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(window), 3)
    q = jax.random.normal(k1, (1, 512, 6, 16))
    k = jax.random.normal(k2, (1, 512, 2, 16))
    v = jax.random.normal(k3, (1, 512, 2, 16))
    want = dense_window(q, k, v, window)
    got = flash_attention(q, k, v, window=window, block_q=128, block_k=128)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    oracle = full_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(oracle, want, atol=2e-5, rtol=0)


def test_flash_fwd_refuses_a_window_with_another_mask():
    x = jnp.zeros((1, 128, 2, 16))
    with pytest.raises(ValueError, match="window"):
        flash_attention(x, x, x, window=8, mask_block=4)
    with pytest.raises(ValueError, match="window"):
        flash_attention(x, x, x, window=8, causal=False)


@pytest.mark.parametrize("pos", [[3, 23, 24, 25], [30, 61, 47, 90]])
def test_the_windowed_flash_decode_paged_is_dense_masked_attention(pos):
    """Rows at their own positions through a ring table: the kernel
    fetches the pages of pos - 23 .. pos and masks the rest, as the dense
    form over the row's whole history; an idle row is not visited."""
    B, Hkv, g, D, P, R, window = 4, 2, 3, 16, PAGE, 4, 24
    rng = np.random.default_rng(sum(pos))
    hist = rng.standard_normal((B, 2, 96, Hkv, D)).astype(np.float32)
    q = jnp.asarray(rng.standard_normal((B, Hkv * g, D)), jnp.float32)
    table = np.asarray(decode.ring_table(np.arange(B), 12, R))
    pools = np.zeros((2, B * R, Hkv, P, D), np.float32)
    for b, p in enumerate(pos):   # the ring as the steps leave it
        for j in range(p + 1):
            pools[:, table[b, j // P], :, j % P] = hist[b, :, j]
    pos = jnp.asarray(pos, jnp.int32)
    active = jnp.array([True, True, False, True])
    got = flash_decode_paged(q, jnp.asarray(pools[0]), jnp.asarray(pools[1]),
                             jnp.asarray(table), pos, active=active,
                             window=window)
    gather = decode._paged_attend_gather(
        q, jnp.asarray(pools[0]), jnp.asarray(pools[1]), None, None,
        jnp.asarray(table), pos, config(), 1 / math.sqrt(D), window=window)
    for b in range(B):
        T = int(pos[b]) + 1
        want = dense_window(q[b][None, None].reshape(1, 1, Hkv * g, D)
                            .repeat(T, 1), jnp.asarray(hist[b, 0, :T][None]),
                            jnp.asarray(hist[b, 1, :T][None]), window)[0, -1]
        np.testing.assert_allclose(gather[b], want, atol=2e-5, rtol=0)
        if active[b]:
            np.testing.assert_allclose(got[b], want, atol=2e-5, rtol=0)
        else:
            assert not np.any(np.asarray(got[b]))


# -- the rotation, the gate, the shared expert ------------------------------------------

def yarn_by_hand(theta, dims, factor, orig, beta_fast, beta_slow):
    """transformers' ``_compute_yarn_parameters`` written out: the
    frequencies over the rotated dims, extrapolated below the truncated
    correction range, divided by ``factor`` above it, a linear ramp
    between."""
    pos_freqs = theta ** (np.arange(0, dims, 2) / dims)
    extra, inter = 1 / pos_freqs, 1 / (factor * pos_freqs)

    def corr(rot):
        return dims * math.log(orig / (rot * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dims - 1)
    ramp = np.clip((np.arange(dims // 2) - low) / (high - low), 0, 1)
    keep = 1 - ramp
    return inter * (1 - keep) + extra * keep, (low, high)


def test_yarn_is_the_published_equations():
    """Laguna's global layers: theta 500,000, 64 of 128 dims rotated,
    factor 128 over 8,192 original positions, betas 32 and 1."""
    want, (low, high) = yarn_by_hand(5e5, 64, 128, 8192, 32, 1)
    assert (low, high) == (9, 18)
    inv, scale = tm.rope_frequencies(5e5, 64, (128, 8192, 32, 1, 1.4852))
    np.testing.assert_allclose(inv, want, rtol=2e-6)
    assert scale == 1.4852
    # the frequencies below the range kept, above it divided by 128
    np.testing.assert_allclose(inv[:9], 5e5 ** (-np.arange(9) / 32),
                               rtol=2e-6)
    np.testing.assert_allclose(inv[18:], 5e5 ** (-np.arange(18, 32) / 32)
                               / 128, rtol=2e-6)
    np.testing.assert_allclose(ref.frequencies(
        (5e5, 64, (128, 8192, 32, 1, 1.4852)))[0], want, rtol=1e-12)


def test_the_partial_rotation_rotates_the_first_dims_and_passes_the_rest():
    cfg = config()
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 3, 16))
    pos = jnp.arange(5) * 300
    rope = cfg.attn_kind("R").rope
    got = tm.apply_rope(x, pos, cfg, rope)
    inv, scale = yarn_by_hand(*((rope[0], rope[1]) + rope[2][:4]))[0], \
        rope[2][4]
    ang = np.asarray(pos)[:, None, None] * inv
    x1, x2 = np.asarray(x[..., :4]), np.asarray(x[..., 4:8])
    c, s = np.cos(ang) * scale, np.sin(ang) * scale
    np.testing.assert_allclose(got[..., :8], np.concatenate(
        [x1 * c - x2 * s, x1 * s + x2 * c], -1), atol=1e-5)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    # a window layer's: the whole head at theta 10,000, no factor
    whole = tm.apply_rope(x, pos, cfg, cfg.attn_kind("W").rope)
    np.testing.assert_allclose(whole, tm.apply_rope(
        x, pos, dataclasses.replace(cfg, rope_theta=1e4)), atol=1e-6)


def test_the_per_head_gate_multiplies_each_head_before_wo(model):
    _, params = model
    cfg = config()
    lp, ak = params["layers"][1], cfg.attn_kind("W")
    hn = jax.random.normal(jax.random.PRNGKey(1), (3, 64))
    o = jax.random.normal(jax.random.PRNGKey(2), (3, 6, 16))
    gate = jax.nn.sigmoid(hn @ lp["attn_gate"])             # (3, 6)
    want = (o * gate[..., None]).reshape(3, 96) @ lp["wo"]
    got = tm.attn_proj(o, lp, cfg, jnp.float32, ak, hn)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
    off = tm.attn_proj(o, lp, cfg, jnp.float32, ak._replace(gate=False))
    np.testing.assert_allclose(off, o.reshape(3, 96) @ lp["wo"], atol=1e-5)
    assert float(jnp.max(jnp.abs(got - off))) > 1e-2


def _expert_layer(seed=0, E=16, D=64, F=32):
    k = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    n = lambda *s: jax.random.normal(next(k), s) * s[-2] ** -0.5
    return {"h": jax.random.normal(next(k), (40, D)), "router": n(D, E),
            "w_gate": n(E, D, F), "w_up": n(E, D, F), "w_down": n(E, F, D),
            "ws_gate": n(D, F), "ws_up": n(D, F), "ws_down": n(F, D)}


def _share(L, start, held, scale=2.5, shared=True):
    sl = slice(start, start + held)
    return moe.gated_moe(
        L["h"], L["router"], L["w_gate"][sl], L["w_up"][sl],
        L["w_down"][sl], held_start=start, top_k=3, scale=scale,
        shared=(L["ws_gate"], L["ws_up"], L["ws_down"]) if shared else None)


def test_gated_moe_scales_the_route_and_adds_the_shared_expert():
    L = _expert_layer()
    plain, _ = _share(L, 0, 16, scale=1.0, shared=False)
    out, stats = _share(L, 0, 16)
    shared = (jax.nn.silu(L["h"] @ L["ws_gate"]) * (L["h"] @ L["ws_up"])) \
        @ L["ws_down"]
    np.testing.assert_allclose(out, 2.5 * plain + shared, atol=1e-4)
    assert int(stats[0]) == 40 * 3


def test_the_two_halves_and_the_shared_expert_add_up_to_the_uncut_layer():
    """Two chips of 8 experts each: their routed parts, with the shared
    expert (which both compute alike) counted once, are the whole layer;
    in the program and in the reference."""
    L = _expert_layer()
    whole, _ = _share(L, 0, 16)
    halves = [_share(L, 8 * s, 8, shared=False)[0] for s in range(2)]
    shared = _share(L, 0, 16)[0] - _share(L, 0, 16, shared=False)[0]
    np.testing.assert_allclose(sum(halves) + shared, whole, atol=1e-4)
    # the reference, over the tiny configuration's 16 experts
    m = dict(W.model_dims(TINY), held=16, held0=0)
    key = W.seed_key(SEED)
    lw = ref.served_layer(key, m, 1, "W")
    x = jax.random.normal(key, (12, m["D"]))
    fn = lambda e: ref.served_expert(key, m, 1, e)
    ref_whole = ref.mlp(x, lw, fn, m, "W")
    ref_halves = [ref.mlp(x, lw, fn, m, "W", first=8 * s, count=8,
                          shared=False) for s in range(2)]
    ref_shared = ref_whole - ref.mlp(x, lw, fn, m, "W", shared=False)
    np.testing.assert_allclose(sum(ref_halves) + ref_shared, ref_whole,
                               atol=1e-4)


# -- the engine ------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served(model):
    m, params = model
    cfg = config()
    eng = ContinuousBatcher(params, cfg, slots=3, pool_pages=40,
                            pages_per_seq=16, page_size=PAGE, chunk=4,
                            prompt_buckets=[32, 64, 128])
    reqs = [(tokens(n, 10 + i), new) for i, (n, new) in
            enumerate([(20, 40), (70, 30), (33, 50), (100, 20), (8, 60)])]
    for i, (p, new) in enumerate(reqs):
        eng.submit(p, new, seq_id=i)
    return eng, reqs, eng.run()


def test_the_engine_serves_the_references_greedy_tokens(model, served):
    """Five requests through three slots (reused: a slot's ring starts
    from its new prompt), contexts to 120 of a window of 24; every served
    token is the reference's best at its position."""
    m, _ = model
    eng, reqs, out = served
    seqs = [np.concatenate([p, out[i][:-1]]) for i, (p, _) in
            enumerate(reqs)]
    rows = [np.arange(len(p) - 1, len(p) - 1 + len(out[i]))
            for i, (p, _) in enumerate(reqs)]
    logits = ref.logits_at(SEED, m, seqs, rows, pad_to=128)
    for i, z in enumerate(logits):
        gap = jnp.max(z, -1) - jnp.take_along_axis(
            z, jnp.asarray(out[i])[:, None], -1)[:, 0]
        assert float(jnp.max(gap)) < 1e-3, i
        assert len(out[i]) == reqs[i][1]


def test_the_rings_are_held_by_the_slots_and_not_the_arena(served):
    eng, _, _ = served
    ring = 3 * 4 * 2 * PAGE * 16 * 4            # slots x R x Hkv x P x D
    assert eng.window_kv_bytes == 3 * 2 * ring  # three layers, K and V
    assert eng.kv_bytes_per_token == 2 * 2 * 2 * 16 * 4  # the two global
    assert len(eng.free_pages) == eng.pool_pages
    # the ring layout is the slot's and the page's alone: no table is held
    assert eng.ring_pages == 4 and set(eng.cache) == {
        "k", "v", "table", "moe_stats"}
    np.testing.assert_array_equal(decode.ring_table([0, 2], 6, 4),
                                  [[0, 1, 2, 3, 0, 1], [8, 9, 10, 11, 8, 9]])


class Sink:
    def __init__(self):
        self.begun = []

    def span_begin(self, path, attrs, t0):
        self.begun.append((path.rsplit("/", 1)[-1],
                           {k: (v() if callable(v) else v)
                            for k, v in attrs.items()}))

    def span_end(self, path, t1):
        pass


def test_the_dispatch_says_what_the_window_layers_fetch(served):
    eng, _, _ = served
    eng.submit(tokens(50, 7), 6, seq_id=10)
    sink = Sink()
    m = metricslib.configure(enabled=True)
    metricslib._trace_sink = sink
    try:
        eng.run()
    finally:
        metricslib._trace_sink = None
        metricslib.configure(enabled=False)
    dispatch = [a for n, a in sink.begun if n == "serve.decode_dispatch"]
    assert dispatch[0]["rows"] == 1 and dispatch[0]["ctx_tokens"] == 50
    assert dispatch[0]["kv_pages"] == 50 // PAGE + 1            # 0 .. 6
    assert dispatch[0]["win_pages"] == 50 // PAGE - 27 // PAGE + 1   # 3 .. 6
    assert m.snapshot()["gauges"]["engine.window_kv_bytes"]["last"] \
        == eng.window_kv_bytes
    prefill = [a for n, a in sink.begun if n == "serve.prefill"][0]
    assert prefill["kv_bytes"] == 64 * eng.kv_bytes_per_token \
        + 4 * PAGE * eng.window_kv_bytes // (3 * 4 * PAGE)


def test_scopes_tell_the_two_attentions_apart_in_the_lowered_text(model):
    m, _ = model
    cfg = config("flash")
    params = jax.eval_shape(lambda: served_params(m))
    cache = jax.eval_shape(lambda: decode.init_paged_cache(cfg, 2, 8, PAGE,
                                                           pool_pages=17))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    text = serving._chunk_step.lower(
        params, cache, i32(2), i32(2), i32(2),
        jax.ShapeDtypeStruct((2, 2), jnp.uint32),
        jax.ShapeDtypeStruct((2,), jnp.float32), cfg=cfg, chunk=2, eos_id=-1,
        greedy=True, top_k=0, mesh=None).as_text(debug_info=True)
    for name in ("attn_win/jit(_paged_call)", "attn/jit(_paged_call)",
                 "flash_decode_paged", "attn/gate", "attn_win/gate", "moe/shared", "moe/route",
                 "moe/experts", "mlp/gate_up", "kv_write"):
        assert name in text, name


# -- what is refused --------------------------------------------------------------------

@pytest.mark.parametrize("kw,match", [
    ({"layer_pattern": "DWR", "n_layers": 3, "window": 0}, "window"),
    ({"layer_pattern": "DRR", "n_layers": 3, "window": 8}, "window"),
    ({"window_heads": 5}, "window_heads"),
    ({"kv_cache_dtype": "int8"}, "window_heads"),
    ({"rope_yarn": (1.0, 2)}, "YaRN"),
])
def test_configuration_checks_the_new_keys(kw, match):
    base = dataclasses.asdict(config())
    base.update(kw)
    with pytest.raises(ValueError, match=match):
        tm.TransformerConfig(**base)


def test_a_default_block_refuses_the_patterned_keys():
    with pytest.raises(ValueError, match="patterned"):
        tm.TransformerConfig(attn_gate=True)


def test_window_layers_decode_through_the_paged_cache_only(model):
    _, params = model
    cfg = config()
    cache = decode.init_cache(cfg, 1, 16)
    with pytest.raises(ValueError, match="paged"):
        decode.decode_step(params, cache, 0, jnp.zeros((1,), jnp.int32), cfg)


def test_an_engine_with_rings_refuses_migration(served):
    eng, _, _ = served
    with pytest.raises(ValueError, match="ring"):
        eng._no_state_migration()


# -- the accepted cells' programs are the parent's ---------------------------------------

DENSE = dict(vocab=256, d_model=64, n_heads=4, n_kv_heads=2, n_layers=2,
             d_ff=128, max_seq=256, dtype="float32", attention="flash",
             pos_embed="rope")
PINNED = {
    "dense": dict(DENSE),
    "hybrid": dict(DENSE, n_layers=3, layer_pattern="M*E", ssm_heads=4,
                   ssm_head_dim=16, ssm_state=16, ssm_chunk=32,
                   moe_experts=8, moe_held=4, moe_held_start=2, moe_top_k=2,
                   moe_latent=32, moe_d_ff=32, moe_shared_d_ff=64,
                   moe_scale=2.0, pos_embed="none"),
    "parallel": dict(DENSE, layer_pattern="HH", ssm_heads=4,
                     ssm_head_dim=16, ssm_state=16, ssm_chunk=32,
                     attn_head_dim=32, mlp_multipliers=(0.5, 2.0)),
    "routed": dict(DENSE, layer_pattern="RR", attn_head_dim=16,
                   qk_norm=True, moe_experts=8, moe_top_k=2, moe_d_ff=32),
}
# sha256 of the StableHLO text (Pallas kernels interpreted, as on the CPU)
# that the parent of the window layers (the commit before them) lowers for
# the engine's programs at these shapes: the prefill through the flash
# kernel at a rung of 128 and the chunk through the paged one
PARENT_PROGRAMS = {
    "dense/_prefill_one":
    "c3d1800598e1456e24c8114972e8a93938c50b73cfc7190ac4f73762204e5988",
    "dense/_chunk_step":
    "44157ce6ba32d6001eb2f2ec144ce2fbab4cbb1e1d335db0752ddbac98ae4b3e",
    "hybrid/_prefill_one":
    "ab28a98627b22c95977701cd4629867c40542fd7edaf6706bfeb35162e055cd2",
    "hybrid/_chunk_step":
    "b11c517f6b3d2816ea9f410e23cfbed813dee7e503ca2114b269e151b87fe450",
    "parallel/_prefill_one":
    "26bb401d761f9d8208464d1679399d81785e8462420078208749a73091e0af5a",
    "parallel/_chunk_step":
    "a9ecd1ee23c722964eb9f0b49ba48f791a4600c81d24e2fc9e1c4e429c7fcbdc",
    "routed/_prefill_one":
    "db15c4d556b595a4a57a6e2e304cacc28c609a2f70e55f16fc60dce580ebf3df",
    "routed/_block_chunk":
    "0e69743b0c499da4c4668c18f2501368a1010c00fe40fe668587343e820b012b",
}


def lowered_text(family: str, program: str) -> str:
    kw = dict(PINNED[family], decode_attn="flash")
    if program == "_block_chunk":
        kw.update(block_len=4, mask_id=255)
    cfg = tm.TransformerConfig(**kw)
    params = jax.eval_shape(lambda: tm.init_params(jax.random.PRNGKey(0),
                                                   cfg))
    cache = jax.eval_shape(lambda: decode.init_paged_cache(
        cfg, 2, 8, 16, pool_pages=17, table=jnp.full((2, 8), 16,
                                                     jnp.int32)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    if program == "_prefill_one":
        one = {k: v for k, v in cache.items() if k not in decode.STATE_KEYS}
        one["table"] = i32(1, 8)
        low = serving._prefill_one.lower(params, i32(1, 128), i32(), one,
                                         cfg=cfg, page_size=16, mesh=None)
    elif program == "_chunk_step":
        low = serving._chunk_step.lower(
            params, cache, i32(2), i32(2), i32(2),
            jax.ShapeDtypeStruct((2, 2), jnp.uint32),
            jax.ShapeDtypeStruct((2,), jnp.float32), cfg=cfg, chunk=2,
            eos_id=-1, greedy=True, top_k=0, mesh=None)
    else:
        low = serving._block_chunk.lower(
            params, cache, i32(2), i32(2), i32(2, 4),
            jax.ShapeDtypeStruct((2, 4), jnp.bool_), i32(2, 4), i32(2),
            i32(3), cfg=cfg, forwards=2, rule="static", steps=2,
            threshold=0.9)
    return low.as_text()


@pytest.mark.parametrize("name", sorted(PARENT_PROGRAMS))
def test_the_accepted_cells_programs_lower_to_the_parents(name):
    """Every layer kind the accepted cells run, through both kernels:
    a model without window layers, gates, YaRN or a shared routed expert
    lowers to the program it lowered to before they existed."""
    family, program = name.split("/")
    digest = hashlib.sha256(lowered_text(family, program).encode())
    assert digest.hexdigest() == PARENT_PROGRAMS[name]
