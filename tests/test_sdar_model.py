"""The "R" layer kind (attention with q/k head norms, then a softmax top-k
of SiLU-gated experts) and generation by diffusion over blocks, against
``chipbench/reference/sdar.py`` on the CPU: seeded random weights, tiny
widths, float32, block lengths 2 and 4. Logits are compared, not sampled
tokens, except where the engine's tokens and forward indices are held to
``generate``'s (float32 on both sides, a vocabulary of 64: no near tie).
"""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench.reference import sdar as ref  # noqa: E402

from hpc_patterns_tpu.harness import metrics as metricslib  # noqa: E402
from hpc_patterns_tpu.models import decode, serving  # noqa: E402
from hpc_patterns_tpu.models import transformer as tm  # noqa: E402
from hpc_patterns_tpu.models.serving import ContinuousBatcher  # noqa: E402
from hpc_patterns_tpu.ops.flash_attention import flash_attention  # noqa: E402
from hpc_patterns_tpu.ops.flash_decode import (  # noqa: E402
    flash_decode_paged_block)
from hpc_patterns_tpu.parallel import moe  # noqa: E402
from hpc_patterns_tpu.parallel.ring_attention import full_attention  # noqa: E402

V, MASK = 64, 63


def config(block_len=4, **kw):
    base = dict(
        vocab=V, d_model=32, n_heads=4, n_kv_heads=2, attn_head_dim=8,
        n_layers=2, d_ff=64, max_seq=256, dtype="float32", attention="full",
        pos_embed="rope", rope_theta=1e4, decode_attn="gather",
        layer_pattern="RR", moe_experts=8, moe_top_k=2, moe_d_ff=16,
        qk_norm=True, block_len=block_len,
        mask_id=MASK if block_len else -1)
    base.update(kw)
    return tm.TransformerConfig(**base)


def dims(cfg):
    return dict(D=cfg.d_model, H=cfg.n_heads, Hkv=cfg.kv_heads,
                Dh=cfg.head_dim, L=cfg.n_layers, V=cfg.vocab, Vb=cfg.vocab,
                E=cfg.moe_experts, k=cfg.moe_top_k, F=cfg.moe_d_ff,
                renorm=cfg.moe_renorm, eps=cfg.norm_eps,
                theta=cfg.rope_theta, B=cfg.block_len, mask_id=cfg.mask_id)


def weights(cfg, seed=0):
    """Seeded weights with norm scales that are not all one."""
    params = tm.init_params(jax.random.PRNGKey(seed), cfg)
    k = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    jitter = lambda a: a + 0.1 * jax.random.normal(next(k), a.shape)
    layers = tuple({n: jitter(a) if n.endswith(("_scale", "_norm")) else a
                    for n, a in lp.items()} for lp in params["layers"])
    return {**params, "layers": layers,
            "ln_f_scale": jitter(params["ln_f_scale"])}


#: one padded length for every reference forward of a request: later
#: whole blocks are invisible to earlier positions, so padding behind a
#: sequence changes nothing, and one program is compiled
PAD = 32


def jitted(cfg, params, m):
    """The reference's forward, compiled once a shape: over a sequence
    under the block mask, and over a replay plan's explicit mask."""
    return (jax.jit(lambda t: ref.forward(params, t, cfg.block_len, m)),
            jax.jit(lambda t, mask, pos: ref.forward(
                params, t, cfg.block_len, m, mask=mask, positions=pos)))


@pytest.fixture(scope="module", params=[2, 4], ids=["B2", "B4"])
def model(request):
    cfg = config(request.param)
    params, m = weights(cfg), dims(cfg)
    return cfg, params, m


@pytest.fixture(scope="module")
def refs(model):
    return jitted(*model)


def program(cfg):
    return jax.jit(lambda p, t: tm.forward(p, t[None], cfg)[0])


block_step = jax.jit(decode.paged_block_step, static_argnames=("cfg",))
paged_prefill = jax.jit(decode.paged_prefill,
                        static_argnames=("cfg", "page_size"))


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, MASK, size=n,
                                                dtype=np.int32)


# -- the layer's forward -----------------------------------------------------------

def test_forward_is_the_references_under_the_block_mask(model):
    cfg, params, m = model
    t = tokens(24)
    got = program(cfg)(params, jnp.asarray(t))
    want = ref.forward(params, t, cfg.block_len, m)
    # float32 on both sides, another order of summation: 2e-5 of logits
    # of order one
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_causal_mask_in_place_of_the_block_mask_is_another_model(model):
    cfg, params, m = model
    t = tokens(24)
    causal = program(dataclasses.replace(cfg, block_len=0, mask_id=-1))(
        params, jnp.asarray(t))
    want = ref.forward(params, t, cfg.block_len, m)
    assert float(jnp.abs(causal - want).max()) > 1e-2
    # and under the reference's own causal mask it is that model
    np.testing.assert_allclose(causal, ref.forward(params, t, 1, m),
                               atol=2e-5, rtol=0)


def test_qk_norms_and_renormalised_gates_are_in_the_forward(model):
    cfg, params, m = model
    t = jnp.asarray(tokens(24))
    want = ref.forward(params, t, cfg.block_len, m)
    flat = {**params, "layers": tuple(
        {**lp, "q_norm": jnp.ones_like(lp["q_norm"])}
        for lp in params["layers"])}
    assert float(jnp.abs(program(cfg)(flat, t) - want).max()) > 1e-3
    raw = program(dataclasses.replace(cfg, moe_renorm=False))(params, t)
    assert float(jnp.abs(raw - want).max()) > 1e-3
    np.testing.assert_allclose(
        raw, ref.forward(params, t, cfg.block_len, dict(m, renorm=False)),
        atol=2e-5, rtol=0)


# -- the route and the shares ------------------------------------------------------

def test_softmax_route_takes_the_largest_ties_by_index():
    h = jnp.eye(4, 6)
    w = jnp.zeros((6, 5)).at[0].set(jnp.array([1., 3., 3., 0., 2.]))
    idx, g = moe.softmax_route(h, w, top_k=2)
    assert idx[0].tolist() == [1, 2]          # the tie: the lower index first
    assert idx[1].tolist() == [0, 1]          # all equal: the first two
    np.testing.assert_allclose(g.sum(-1), 1.0, rtol=1e-6)
    _, raw = moe.softmax_route(h, w, top_k=2, renorm=False)
    p = jax.nn.softmax(h @ w, axis=-1)
    np.testing.assert_allclose(raw[0], p[0, jnp.array([1, 2])], rtol=1e-6)


@pytest.mark.parametrize("shares", [1, 2, 4])
def test_shares_of_the_gated_layer_sum_to_the_uncut_reference(shares):
    """``held_start`` 0, E / shares, ...: what each share's experts give
    adds up to the whole layer's routed sum."""
    cfg = config(4)
    lp = weights(cfg)["layers"][0]
    m = dims(cfg)
    h = jax.random.normal(jax.random.PRNGKey(5), (24, cfg.d_model))
    G = ref.gates(h, lp["router"], m)
    want = ref.experts(h, G, lambda e: {n: lp[n][e] for n in
                                        ("w_gate", "w_up", "w_down")}, m)
    held = cfg.moe_experts // shares
    total, picks = 0.0, 0
    for s in range(shares):
        cut = slice(s * held, (s + 1) * held)
        out, stats = moe.gated_moe(
            h, lp["router"], lp["w_gate"][cut], lp["w_up"][cut],
            lp["w_down"][cut], held_start=s * held, top_k=cfg.moe_top_k)
        total, picks = total + out, picks + int(stats[0])
        assert int(stats[1]) == 24 and int(stats[4]) == 1
    assert picks == 24 * cfg.moe_top_k
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=0)


def test_tokens_that_do_not_count_pick_no_expert():
    cfg = config(4)
    lp = weights(cfg)["layers"][0]
    h = jax.random.normal(jax.random.PRNGKey(6), (8, cfg.d_model))
    valid = jnp.arange(8) < 5
    out, stats = moe.gated_moe(h, lp["router"], lp["w_gate"], lp["w_up"],
                               lp["w_down"], held_start=0, top_k=2,
                               valid=valid)
    assert int(stats[0]) == 10 and int(stats[1]) == 5
    assert float(jnp.abs(out[5:]).max()) == 0.0


# -- the kernels under the block mask ----------------------------------------------

@pytest.mark.parametrize("block", [2, 4, 128])
def test_flash_fwd_under_the_block_mask_is_plain_attention(block):
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k[0], (1, 256, 4, 32))
    kk = jax.random.normal(k[1], (1, 256, 2, 32))
    v = jax.random.normal(k[2], (1, 256, 2, 32))
    got = flash_attention(q, kk, v, mask_block=block, block_q=128,
                          block_k=128, interpret=True)
    want = full_attention(q, kk, v, causal=True, mask_block=block)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
    if block < 128:
        assert float(jnp.abs(full_attention(q, kk, v, causal=True)
                             - want).max()) > 0.1


def test_flash_fwd_refuses_a_block_that_does_not_divide_its_tiles():
    q = jnp.zeros((1, 128, 2, 8))
    for bad in (3, 256):
        with pytest.raises(ValueError, match="power of two"):
            flash_attention(q, q, q, mask_block=bad)
    with pytest.raises(ValueError, match="causal=True"):
        flash_attention(q, q, q, mask_block=4, causal=False)


@pytest.mark.parametrize("c", [2, 4])
def test_a_block_folded_into_the_group_is_plain_attention(c):
    """Every position of the block sees keys 0 .. pos + c - 1; one kernel
    call for the block."""
    Bt, H, Hkv, D, P, pages = 3, 4, 2, 8, 8, 4
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(k[0], (Bt, c, H, D))
    k_pool = jax.random.normal(k[1], (Bt * pages, Hkv, P, D))
    v_pool = jax.random.normal(k[2], (Bt * pages, Hkv, P, D))
    table = jnp.arange(Bt * pages, dtype=jnp.int32).reshape(Bt, pages)[::-1]
    pos = jnp.array([0, 12, 20], jnp.int32)
    got = flash_decode_paged_block(q, k_pool, v_pool, table, pos,
                                   interpret=True)
    for b in range(Bt):
        n = int(pos[b]) + c
        lin = lambda pool: jnp.einsum(
            "phsd->hpsd", pool[table[b]]).reshape(Hkv, pages * P, D)[:, :n]
        kb, vb = lin(k_pool), lin(v_pool)
        s = jnp.einsum("ikgd,ksd->ikgs",
                       q[b].reshape(c, Hkv, H // Hkv, D), kb) / D ** 0.5
        want = jnp.einsum("ikgs,ksd->ikgd", jax.nn.softmax(s, -1), vb)
        np.testing.assert_allclose(got[b], want.reshape(c, H, D),
                                   atol=2e-6, rtol=0)


# -- prefill of whole blocks, then block steps through the paged cache --------------

@pytest.mark.parametrize("route", ["gather", "flash"])
def test_prefill_then_block_steps_give_the_references_logits(model, route):
    """The prompt's whole blocks prefilled under the block mask, then each
    block in two states (half masked, settled) through the paged cache,
    against the reference's forward over the whole sequence (what lies
    behind the block is invisible to it)."""
    cfg, params, m = model
    fwd = jitted(cfg, params, m)[0]
    B, P = cfg.block_len, 16
    T = 128 if route == "flash" else 32
    cfg = dataclasses.replace(cfg, decode_attn=route,
                              attention="flash" if route == "flash"
                              else "full")
    seq = tokens(T + 3 * B, seed=3)
    cache = decode.init_paged_cache(cfg, 1, pages_per_seq=-(-len(seq) // P),
                                    page_size=P)
    logits, cache = paged_prefill(
        params, jnp.asarray(seq[:T])[None], cfg, cache, P, last_pos=T - 1)
    assert logits is None
    for b in range(3):
        at = T + b * B
        settled = seq[at:at + B]
        half = np.where(np.arange(B) % 2 == 0, MASK, settled).astype(np.int32)
        for state in (half, settled):       # the last one stands
            got, cache = block_step(
                params, cache, jnp.array([at], jnp.int32),
                jnp.asarray(state)[None], cfg)
            whole = seq.copy()
            whole[at:at + B] = state
            want = fwd(jnp.asarray(whole))[at:at + B]
            # float32; the flash routes sum in blocks: 5e-5
            np.testing.assert_allclose(got[0], want, atol=5e-5, rtol=0)
    assert np.asarray(cache["moe_stats"])[:, 4].tolist() == [2, 12]


def test_block_step_rows_that_do_not_count_write_nothing(model):
    cfg, params, _ = model
    B = cfg.block_len
    cache = decode.init_paged_cache(cfg, 2, pages_per_seq=2, page_size=8)
    blk = jnp.asarray(np.stack([tokens(B, 1), tokens(B, 2)]))
    _, out = block_step(
        params, cache, jnp.array([0, 8], jnp.int32), blk, cfg,
        active=jnp.array([True, False]))
    k = np.asarray(out["k"][0])
    assert np.abs(k[0, :, :B]).min() > 0 and np.abs(k[2:]).max() == 0
    assert int(out["moe_stats"][1, 1]) == B * cfg.n_layers   # one row routed


def test_block_step_checks_its_arguments(model):
    cfg, params, _ = model
    B = cfg.block_len
    cache = decode.init_paged_cache(cfg, 1, pages_per_seq=2, page_size=8)
    blk = jnp.zeros((1, B), jnp.int32)
    with pytest.raises(ValueError, match="multiples of"):
        decode.paged_block_step(params, cache, jnp.array([1]), blk, cfg)
    with pytest.raises(ValueError, match="end within"):
        decode.paged_block_step(params, cache, jnp.array([16]), blk, cfg)
    with pytest.raises(ValueError, match="block_len"):
        decode.paged_block_step(params, cache, jnp.array([0]),
                                jnp.zeros((1, B + 1), jnp.int32), cfg)
    quant = dataclasses.replace(cfg, kv_cache_dtype="int8")
    with pytest.raises(ValueError, match="compute-dtype"):
        decode.paged_block_step(params, cache, jnp.array([0]), blk, quant)


# -- the engine against generate -----------------------------------------------------

#: (prompt length, budget): remainders 1, 0, 3 (B = 4), shorter than a
#: block, a row that ends mid-block, more requests than slots
REQUESTS = ((5, 7), (8, 8), (13, 3), (2, 5), (9, 12), (16, 1), (7, 6))


def engine(cfg, params, **kw):
    return ContinuousBatcher(params, cfg, slots=3, pool_pages=12,
                             pages_per_seq=4, page_size=8, chunk=6,
                             prompt_buckets=(8, 16, 32), **kw)


@pytest.fixture(scope="module",
                params=[("static", 2), ("static", 1), ("dynamic", 2)],
                ids=["static2", "static1", "dynamic"])
def served(request, model):
    rule, steps = request.param
    cfg, params, m = model
    kw = dict(unmask_rule=rule, unmask_steps=steps, unmask_threshold=0.12)
    eng = engine(cfg, params, **kw)
    prompts = [tokens(P, seed=10 + i) for i, (P, _) in enumerate(REQUESTS)]
    for p, (_, n) in zip(prompts, REQUESTS):
        eng.submit(p, n)
    return eng, eng.run(), prompts, dict(rule=rule, steps=steps,
                                         threshold=0.12), model


def test_engine_tokens_and_forward_indices_are_generates(served, refs):
    eng, out, prompts, rule, (cfg, params, m) = served
    for sid, (p, (_, n)) in enumerate(zip(prompts, REQUESTS)):
        want, blocks = ref.generate(params, p, n, m, forward_fn=refs[0],
                                    pad_to=PAD, **rule)
        assert out[sid].tolist() == want.tolist(), sid
        got = eng.stats[sid]["blocks"]
        assert len(got) == len(blocks)
        for (tok, fidx), rec in zip(blocks, got):
            assert rec[0].tolist() == tok.tolist()
            assert rec[1].tolist() == fidx.tolist()


def test_the_serial_order_serves_the_same_blocks(model, refs):
    """An admitted row joins the chunk of its own round (``overlap``) or
    of the next (serial order): the same blocks, settled by the same
    forwards, and the tokens are the reference's in both."""
    cfg, params, m = model
    rule = dict(rule="static", steps=2, threshold=0.9)
    prompts = [tokens(P, seed=10 + i) for i, (P, _) in enumerate(REQUESTS)]
    runs = []
    for overlap in (True, False):
        eng = engine(cfg, params, overlap=overlap, unmask_rule="static",
                     unmask_steps=2)
        for p, (_, n) in zip(prompts, REQUESTS):
            eng.submit(p, n)
        runs.append((eng.run(), eng.stats))
    (out, stats), (serial, serial_stats) = runs
    for sid, (p, (_, n)) in enumerate(zip(prompts, REQUESTS)):
        want, _ = ref.generate(params, p, n, m, forward_fn=refs[0],
                               pad_to=PAD, **rule)
        assert out[sid].tolist() == serial[sid].tolist() == want.tolist()
        assert len(stats[sid]["blocks"]) == len(serial_stats[sid]["blocks"])
        for got, base in zip(stats[sid]["blocks"],
                             serial_stats[sid]["blocks"]):
            np.testing.assert_array_equal(got, base)


def test_a_row_hands_out_its_budget_whatever_the_block(served):
    eng, out, prompts, rule, (cfg, _, _) = served
    B = cfg.block_len
    for sid, (P, n) in enumerate(REQUESTS):
        assert len(out[sid]) == n
        s = eng.stats[sid]
        assert s["outcome"] == "ok" and s["tokens"] == n
        assert len(s["token_ts"]) == n and s["t_first"] == s["token_ts"][0]
        # whole blocks were run: from the prompt's last whole block to
        # the one that holds the limit
        assert len(s["blocks"]) == -(-(P + n) // B) - P // B
        given = P - P // B * B
        assert (s["blocks"][0][1][:given] == -1).all()
        assert (s["blocks"][0][1][given:] >= 0).all()


def test_a_forward_settles_what_its_rule_says(served):
    eng, out, prompts, rule, (cfg, params, m) = served
    counts = [np.bincount(b[1][b[1] >= 0])
              for s in eng.stats.values() for b in s["blocks"]]
    if rule["rule"] == "dynamic":
        # some forward settled several positions, some block took several
        assert max(c.max() for c in counts) > 1
        assert max(len(c) for c in counts) > 1
    else:   # ceil(B / steps) a forward, fewer only in a block's last
        n = -(-cfg.block_len // rule["steps"])
        assert all((c[:-1] == n).all() and 0 < c[-1] <= n for c in counts)


def test_the_sums_count_forwards_blocks_and_tokens(served):
    eng, out, *_ = served
    forwards, blocks, handed = eng.diffusion_stats()
    assert blocks == sum(len(s["blocks"]) for s in eng.stats.values())
    assert handed == sum(len(v) for v in out.values())
    per_block = sum(int(b[1].max()) + 2 for s in eng.stats.values()
                    for b in s["blocks"])   # its denoising forwards + commit
    assert forwards == per_block
    route = eng.route_stats()
    L = eng.cfg.n_layers
    assert route[1, 4] == eng.cfg.n_layers * (route[1, 1] // (
        eng.cfg.block_len * L)) or route[1, 4] > 0
    assert route[1, 1] == forwards * eng.cfg.block_len * L


def test_replaying_the_recorded_states_reads_no_gap(served, refs):
    eng, out, prompts, rule, (cfg, params, m) = served
    for sid, p in enumerate(prompts):
        plan = ref.pad_plan(
            ref.replay_plan(p, eng.stats[sid]["blocks"], m), 3 * PAD)
        best, lse, _, at = ref.tree_replay_numbers(params, m, plan,
                                                   forward_fn=refs[1])
        tok, pick = ref.replay_gaps(plan, best, lse, at, **rule)
        assert len(tok) == sum(int((b[1] >= 0).sum())
                               for b in eng.stats[sid]["blocks"])
        assert tok.max() < 1e-4 and (not len(pick) or pick.max() < 1e-4)


# -- faults a replay has to read ------------------------------------------------------

@pytest.fixture(scope="module")
def b4():
    cfg = config(4)
    params, m = weights(cfg), dims(cfg)
    return cfg, params, m, jitted(cfg, params, m)[1]


def _gaps(eng, prompts, params, m, rule, fwd):
    tok, pick = [], []
    for sid, p in enumerate(prompts):
        plan = ref.pad_plan(
            ref.replay_plan(p, eng.stats[sid]["blocks"], m), 3 * PAD)
        best, lse, _, at = ref.tree_replay_numbers(params, m, plan,
                                                   forward_fn=fwd)
        t, k = ref.replay_gaps(plan, best, lse, at, **rule)
        tok.append(t)
        pick.append(k)
    return np.concatenate(tok), np.concatenate(pick)


def _serve(cfg, params, **kw):
    eng = engine(cfg, params, **kw)
    prompts = [tokens(P, seed=10 + i) for i, (P, _) in enumerate(REQUESTS)]
    for p, (_, n) in zip(prompts, REQUESTS):
        eng.submit(p, n)
    eng.run()
    return eng, prompts


RULE = dict(rule="static", steps=2, threshold=0.9)


def test_a_token_altered_in_the_block_chunk_reads_a_gap(monkeypatch, b4):
    cfg, params, m, fwd = b4
    real = serving._block_chunk

    def altered(*a, **kw):
        *state, (toks, fidx, commit) = real(*a, **kw)
        wrong = jnp.where((fidx >= 0) & (jnp.arange(4) == 2),
                          (toks + 1) % MASK, toks)
        return (*state, (wrong, fidx, commit))

    monkeypatch.setattr(serving, "_block_chunk", altered)
    eng, prompts = _serve(cfg, params)
    tok, _ = _gaps(eng, prompts, params, m, RULE, fwd)
    assert tok.max() > 0.5 and (tok > 0.1).mean() > 0.1


def test_a_position_settled_out_of_confidence_order_reads_a_gap(monkeypatch, b4):
    cfg, params, m, fwd = b4
    real = serving._unmask

    def least_first(logits, msk, **kw):
        cand, _ = real(logits, msk, **kw)
        conf = jnp.where(msk, jnp.max(jax.nn.softmax(logits, -1), -1), 2.0)
        _, worst = jax.lax.top_k(-conf, 2)
        settle = jnp.any(worst[:, :, None] == jnp.arange(4), axis=1)
        return cand, settle & msk

    monkeypatch.setattr(serving, "_unmask", least_first)
    serving._block_chunk.clear_cache()
    try:
        eng, prompts = _serve(cfg, params)
    finally:
        serving._block_chunk.clear_cache()
    tok, pick = _gaps(eng, prompts, params, m, RULE, fwd)
    assert tok.max() < 1e-4          # every token is its position's best
    assert pick.max() > 0.05 and (pick > 0).mean() > 0.5


def test_the_commit_forward_left_out_reads_a_gap(monkeypatch, b4):
    """The stored K/V stay those of the last denoising forward, computed
    while the block still held masks."""
    cfg, params, m, fwd = b4
    real = serving.paged_block_step

    def no_commit(params, cache, pos, blk, cfg, active=None):
        settled = ~jnp.any(blk == cfg.mask_id, axis=-1)
        return real(params, cache, pos, blk, cfg, active=active & ~settled)

    monkeypatch.setattr(serving, "paged_block_step", no_commit)
    serving._block_chunk.clear_cache()
    try:
        eng, prompts = _serve(cfg, params)
    finally:
        serving._block_chunk.clear_cache()
    tok, pick = _gaps(eng, prompts, params, m, RULE, fwd)
    assert max(tok.max(), pick.max()) > 0.02


def test_the_causal_mask_in_the_prefill_reads_a_gap(monkeypatch, b4):
    cfg, params, m, fwd = b4
    monkeypatch.setattr(
        decode, "full_attention",
        lambda q, k, v, causal, mask_block=1: full_attention(q, k, v,
                                                             causal=causal))
    serving._prefill_one.clear_cache()
    try:
        eng, prompts = _serve(cfg, params)
    finally:
        serving._prefill_one.clear_cache()
    tok, pick = _gaps(eng, prompts, params, m, RULE, fwd)
    assert max(tok.max(), pick.max()) > 0.02


def test_the_fp8_control_reads_a_gap(model):
    """The reference with every matmul operand in fp8, judged by the
    float32 reference at the states a sound engine recorded."""
    cfg, params, m = model
    eng, prompts = _serve(cfg, params)
    fwd = jitted(cfg, params, m)[1]
    fp8 = jax.jit(lambda t, mask, pos: ref.forward(
        params, t, cfg.block_len, m, mask=mask, positions=pos, lowp="fp8"))
    tok = []
    for sid, p in enumerate(prompts):
        plan = ref.pad_plan(
            ref.replay_plan(p, eng.stats[sid]["blocks"], m), 3 * PAD)
        low = ref.tree_replay_numbers(params, m, plan, forward_fn=fp8)
        best, lse, _, at = ref.tree_replay_numbers(
            params, m, plan, target_of=low[2], forward_fn=fwd)
        picked = ref.control_picks(plan, low[0], low[1], **RULE)
        t, k = ref.replay_gaps(plan, best, lse, at, picked=picked, **RULE)
        tok.append(np.concatenate([t, k]))
    assert np.concatenate(tok).max() > 0.01


# -- tracing ----------------------------------------------------------------------------

class Sink:
    def __init__(self):
        self.begun = []

    def span_begin(self, path, attrs, t0):
        self.begun.append((path.rsplit("/", 1)[-1],
                           {k: (v() if callable(v) else v)
                            for k, v in attrs.items()}))

    def span_end(self, path, t1):
        pass


def test_spans_and_counters_of_a_block_engine():
    cfg = config(4)
    params = weights(cfg)
    eng = engine(cfg, params)
    eng.submit(tokens(9), 8, seq_id=0)
    eng.run()                                   # every shape, warm
    sink, records = Sink(), []
    eng._emit = lambda **kw: records.append(kw)
    m = metricslib.configure(enabled=True)
    metricslib._trace_sink = sink
    try:
        eng.submit(tokens(9, 1), 8, seq_id=1)
        eng.submit(tokens(6, 2), 5, seq_id=2)
        eng.run()
    finally:
        metricslib._trace_sink = None
        metricslib.configure(enabled=False)
    names = [n for n, _ in sink.begun]
    firsts = [a["seq_id"] for n, a in sink.begun if n == "serve.first_token"]
    assert sorted(firsts) == [1, 2]
    assert sorted(a["seq_id"] for n, a in sink.begun
                  if n == "serve.prefill") == [1, 2]
    dispatch = [a for n, a in sink.begun if n == "serve.decode_dispatch"]
    assert dispatch and all(
        {"rows", "forwards", "block", "ctx_tokens", "kv_pages", "round",
         "chunk"} <= set(a) for a in dispatch)
    assert dispatch[0]["block"] == 4 and dispatch[0]["forwards"] == 6
    assert dispatch[0]["ctx_tokens"] == 8 + 4     # 9 // 4 * 4 + 6 // 4 * 4
    assert dispatch[0]["kv_pages"] == 2 + 1       # pages of 8 up to 11, 7
    assert "serve.decode_round" in names and "serve.collect" in names
    # the first look counts everything since the engine was built
    counters = m.snapshot()["counters"]
    blocks = [len(eng.stats[sid]["blocks"]) for sid in (0, 1, 2)]
    assert blocks == [3, 3, 2]
    assert counters["diffusion.tokens"] == 8 + 8 + 5
    assert counters["diffusion.blocks"] == sum(blocks)
    assert counters["diffusion.forwards"] == eng.diffusion_stats()[0]
    gauge = m.snapshot()["gauges"]["diffusion.tokens_per_forward"]["last"]
    assert 0 < gauge <= 4 / 3 + 1e-9
    assert counters["moe.tokens"] > 0
    chunks = [r for r in records if r["kind"] == "serve_block_chunk"]
    assert chunks and sum(r["blocks"] for r in chunks) == 3 + 2
    assert {"rows", "ctx_tokens", "forwards", "round"} <= set(chunks[0])


def test_scopes_of_the_block_chunk_show_in_its_lowered_text():
    cfg = config(4, decode_attn="flash")
    params = jax.eval_shape(lambda: weights(cfg))
    cache = jax.eval_shape(lambda: decode.init_paged_cache(
        cfg, 2, pages_per_seq=2, page_size=8, pool_pages=5))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    text = serving._block_chunk.lower(
        params, cache, i32(2), i32(2), i32(2, 4),
        jax.ShapeDtypeStruct((2, 4), jnp.bool_), i32(2, 4), i32(2), i32(3),
        cfg=cfg, forwards=2, rule="static", steps=2,
        threshold=0.9).as_text(debug_info=True)
    for name in ("attn/qk_norm", "moe/route", "moe/experts", "unmask",
                 "kv_commit", "kv_write", "head", "embed",
                 "grouped_matmul", "flash_decode_paged"):
        assert name in text, name


# -- an "R" model that decodes a token a step --------------------------------------------

def test_an_r_pattern_without_blocks_decodes_through_the_token_step():
    cfg = config(0)
    params = weights(cfg)
    prompt = jnp.asarray(tokens(8))[None]
    got = decode.greedy_generate(params, prompt, cfg, 5)
    # causal: one forward over the finished sequence predicts every token
    seq = jnp.concatenate([prompt, got], axis=1)
    logits = program(cfg)(params, seq[0])
    assert got[0].tolist() == jnp.argmax(logits[7:12], -1).tolist()


# -- what is refused, and what the configuration checks ----------------------------------

@pytest.mark.parametrize("kw,what", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(preempt=True), "preempt"),
    (dict(residency=object()), "residency"),
    (dict(temperature=0.7), "temperature"),
    (dict(eos_id=3), "eos_id"),
    (dict(draft_params={}, draft_cfg=None), "draft_params"),
    (dict(mesh=object()), "mesh"),
])
def test_a_block_engine_refuses_what_it_does_not_carry(kw, what):
    cfg = config(4)
    with pytest.raises(ValueError, match=f"{what}.*block-diffusion"):
        engine(cfg, None, **kw)


def test_a_block_engine_refuses_migration_and_odd_geometry():
    cfg = config(4)
    params = weights(cfg)
    eng = engine(cfg, params)
    with pytest.raises(ValueError, match="block in flight"):
        eng.export_migration(0)
    with pytest.raises(ValueError, match="must divide page_size"):
        ContinuousBatcher(params, cfg, slots=1, pool_pages=4,
                          pages_per_seq=4, page_size=6, chunk=3)
    with pytest.raises(ValueError, match="every rung"):
        ContinuousBatcher(params, cfg, slots=1, pool_pages=4,
                          pages_per_seq=4, page_size=8, chunk=3,
                          prompt_buckets=(8, 18))
    with pytest.raises(ValueError, match="unmask_rule"):
        engine(cfg, params, unmask_rule="random")
    with pytest.raises(ValueError, match="unmask_steps"):
        engine(cfg, params, unmask_steps=5)


@pytest.mark.parametrize("kw,match", [
    (dict(block_len=3), "power of two"),
    (dict(block_len=256), "power of two"),
    (dict(mask_id=64), "outside the vocabulary"),
    (dict(mask_id=-1), "outside the vocabulary"),
    (dict(layer_pattern="R*"), "'R' alone"),
    (dict(attention="ring"), "block mask"),
    (dict(block_len=0, mask_id=5), "block_len > 0 only"),
    (dict(moe_top_k=9), "moe_experts >= moe_top_k"),
    (dict(moe_d_ff=0), "moe_d_ff > 0"),
    (dict(moe_held=6, moe_held_start=4), "held range"),
])
def test_configuration_checks_the_new_keys(kw, match):
    with pytest.raises(ValueError, match=match):
        config(**kw)


def test_dense_only_routes_name_the_block_step():
    cfg = config(4)
    with pytest.raises(ValueError, match="paged_block_step"):
        decode.paged_extend_step(None, {}, None, jnp.zeros((1, 2)), cfg)
