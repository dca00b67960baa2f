"""Speculative decoding: a small draft model proposes, the target
model verifies in one batched pass. Greedy AND sampling modes.

The serving-latency play the KV-cache machinery enables: plain decode
is one big-model forward per token (cache-read-bound); here a cheap draft model runs ``gamma``
sequential steps and the target scores the whole proposed chunk with
ONE ``decode.extend_step`` — large-matmul shapes instead of gamma
sequential single-token reads. With greedy acceptance the output is
PROVABLY identical to the target's own greedy decode, whatever the
draft proposes (the oracle the tests pin): accepted proposals are
exactly the tokens the target would have picked, and the first
disagreement is replaced by the target's token.

With ``temperature > 0`` the verify step is the standard
rejection-sampling acceptance (speculative sampling): proposal j drawn
from the draft's warped distribution q_j is accepted with probability
min(1, p_j(x_j)/q_j(x_j)) against the target's warped p_j; the first
rejection is replaced by a draw from the residual norm(max(p_j − q_j,
0)), and a fully-accepted round appends a bonus draw from p_gamma. The
emitted sequence is distributed EXACTLY as target-only sampling at the
same temperature/top_k (the warped distributions are what
decode._pick samples) — the distribution-exactness oracle in
tests/test_decode.py pins the accept/resample primitive against the
analytic law. Both modes share the distributions through one
``_accept_resample``: greedy is the temperature→0 limit evaluated
exactly (argmax + first-mismatch), not a separate bookkeeping path.

Bookkeeping invariant (both caches, one shared position cursor): at the
top of each iteration the caches hold K/V for the prompt and every
emitted token EXCEPT the last, which is ``cur`` (pending). The draft
runs gamma+1 steps (the +1 writes the last proposal's K/V so a fully
accepted round leaves no hole), the target extend writes
[cur, proposals...]; rejected rows go stale and are simply overwritten
when the cursor re-crosses them — position masking makes stale rows
invisible (the same static-shape trick as the cache itself).

Batch is 1 per call: acceptance lengths diverge per sequence, and a
per-row position cursor cannot drive a single dynamic_update_slice
(vmap over sequences instead if needed).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from hpc_patterns_tpu.models.decode import (
    _pick,
    _topk_mask,
    decode_step,
    extend_step,
    init_paged_cache,
    paged_decode_step,
    paged_extend_step,
    paged_prefill,
    prefill,
)
from hpc_patterns_tpu.models.transformer import TransformerConfig


def _warp(logits, temperature, top_k: int):
    """The warped next-token distribution ``decode._pick`` samples:
    the SHARED ``_topk_mask`` support then temperature softmax —
    _pick's categorical over masked-logits/temperature IS this softmax,
    by construction (one mask definition, no drift). (..., V) f32."""
    masked = _topk_mask(logits.astype(jnp.float32), top_k)
    return jax.nn.softmax(masked / temperature, axis=-1)


def _accept_resample(key, props, q_probs, p_probs):
    """The speculative-sampling verify primitive (one round).

    ``props``: (gamma,) proposal tokens drawn from the draft rows;
    ``q_probs``: (gamma, V) the draft's warped distributions;
    ``p_probs``: (gamma+1, V) the target's warped distributions at the
    same positions (+1 = the bonus row). Returns ``(a, nxt)``: the
    accepted-prefix length (proposal j accepted with probability
    min(1, p_j(x_j)/q_j(x_j)), stopping at the first rejection) and
    the round's closing token — a draw from the residual
    norm(max(p_a − q_a, 0)) on rejection, or from p_gamma when all
    gamma proposals were accepted (padding q with a zeros row makes
    those the same expression). The emitted law [props[:a], nxt] is
    exactly target-only ancestral sampling — the oracle test draws this
    many times and checks the first-token marginal equals p analytically.
    """
    gamma = props.shape[0]
    k_acc, k_nxt = jax.random.split(key)
    sel = jnp.arange(gamma)
    p_at = p_probs[sel, props]
    q_at = q_probs[sel, props]
    u = jax.random.uniform(k_acc, (gamma,))
    accept = u * q_at < jnp.minimum(q_at, p_at)  # u < min(1, p/q), q>0
    a = jnp.sum(jnp.cumprod(accept.astype(jnp.int32)))
    q_padded = jnp.concatenate(
        [q_probs, jnp.zeros_like(q_probs[:1])], axis=0
    )
    res = jnp.maximum(p_probs[a] - q_padded[a], 0.0)
    res_sum = jnp.sum(res)
    # p == q exactly leaves an empty residual; the limit law is p itself
    dist = jnp.where(res_sum > 1e-12, res / res_sum, p_probs[a])
    nxt = jax.random.categorical(k_nxt, jnp.log(dist + 1e-30))
    return a, nxt.astype(jnp.int32)


@partial(jax.jit, static_argnums=(1, 3, 5, 6, 8, 9, 11))
def _speculative_jit(params, cfg, draft_params, draft_cfg, prompt,
                     new_tokens, gamma, key=None, greedy=True, top_k=0,
                     temperature=1.0, mesh=None):
    B, T = prompt.shape
    max_len = T + new_tokens + gamma + 1  # slack for the final round
    logits, cache = prefill(params, prompt, cfg, max_len, mesh=mesh)
    _, dcache = prefill(draft_params, prompt, draft_cfg, max_len,
                        mesh=mesh)
    if key is None:
        key = jax.random.PRNGKey(0)  # unused in greedy mode
    key, sub = jax.random.split(key)
    first = _pick(logits, sub, temperature, greedy, top_k)  # (1,)

    out = jnp.zeros((new_tokens + gamma + 1,), jnp.int32)
    out = out.at[0].set(first[0])

    def cond(state):
        _, _, _, _, n_out, _ = state
        return n_out < new_tokens

    def iteration(state):
        cache, dcache, pos, cur, n_out, key = state
        # --- draft proposes gamma tokens (gamma+1 steps: the extra one
        # writes the last proposal's K/V — see module docstring)
        props = []
        qs = []
        tok = cur
        dc = dcache
        for j in range(gamma + 1):
            dlogits, dc = decode_step(draft_params, dc, pos + j, tok,
                                      draft_cfg, mesh=mesh)
            key, sub = jax.random.split(key)
            tok = _pick(dlogits, sub, temperature, greedy, top_k)
            if j < gamma:
                props.append(tok[0])
                if not greedy:
                    qs.append(_warp(dlogits[0], temperature, top_k))
        props = jnp.stack(props)  # (gamma,)

        # --- target verifies [cur, props] in ONE extend
        chunk = jnp.concatenate([cur, props])[None, :]  # (1, gamma+1)
        vlogits, cache = extend_step(params, cache, pos, chunk, cfg)

        if greedy:
            # exact temperature->0 limit: accept while the proposal IS
            # the target argmax; replace the first mismatch with it
            t_all = jnp.argmax(vlogits[0], axis=-1).astype(jnp.int32)
            matches = (props == t_all[:gamma]).astype(jnp.int32)
            a = jnp.sum(jnp.cumprod(matches))
            nxt = t_all[a]
        else:
            key, sub = jax.random.split(key)
            a, nxt = _accept_resample(
                sub, props, jnp.stack(qs),
                _warp(vlogits[0], temperature, top_k),
            )
        # emitted this round: props[:a] then nxt (positions > a are
        # filler, overwritten by the next round's slice)
        props_padded = jnp.concatenate([props, props[-1:]])
        emit = jnp.where(jnp.arange(gamma + 1) < a, props_padded, nxt)
        return cache, dc, pos + a + 1, nxt[None], n_out + a + 1, key, emit

    def body(state_out):
        state, out = state_out
        n_out = state[4]
        cache, dc, pos2, cur2, n_out2, key2, emit = iteration(state)
        out = lax.dynamic_update_slice(out, emit, (n_out,))
        return (cache, dc, pos2, cur2, n_out2, key2), out

    state = (cache, dcache, jnp.int32(T), first, jnp.int32(1), key)
    (state, out) = lax.while_loop(
        lambda so: cond(so[0]),
        body,
        (state, out),
    )
    return out[:new_tokens][None, :]


def _validate(cfg, draft_cfg, prompt_len, new_tokens, gamma):
    """The shared argument guards of both entry points."""
    if cfg.vocab != draft_cfg.vocab:
        raise ValueError(
            f"draft vocab {draft_cfg.vocab} != target vocab {cfg.vocab}"
        )
    if new_tokens < 1:
        raise ValueError(f"new_tokens must be >= 1, got {new_tokens}")
    if gamma < 1:
        raise ValueError(f"gamma must be >= 1, got {gamma}")
    if prompt_len + new_tokens + gamma + 1 > min(cfg.max_seq,
                                                 draft_cfg.max_seq):
        raise ValueError(
            f"prompt {prompt_len} + new {new_tokens} + gamma slack "
            f"{gamma + 1} exceeds max_seq "
            f"{min(cfg.max_seq, draft_cfg.max_seq)}"
        )


def _sampling_args(cfg, temperature, top_k, key):
    """Shared sampling-argument guards (mirrors decode.generate)."""
    if temperature > 0.0 and key is None:
        raise ValueError("sampling (temperature > 0) needs a PRNG key")
    if not 0 <= top_k <= cfg.vocab:
        raise ValueError(f"top_k {top_k} outside [0, vocab]")
    greedy = temperature <= 0.0
    return (key, greedy, int(top_k),
            jnp.float32(max(temperature, 1e-6)))


def speculative_generate(params, cfg: TransformerConfig, draft_params,
                         draft_cfg: TransformerConfig, prompt,
                         new_tokens: int, *, gamma: int = 4, key=None,
                         temperature: float = 0.0, top_k: int = 0,
                         mesh=None):
    """Continuation (1, new_tokens) int32. Greedy by default —
    token-identical to ``greedy_generate(params, prompt, cfg,
    new_tokens)``: the draft only changes HOW FAST tokens come, never
    which tokens. With ``temperature > 0`` (``key`` required), the
    rejection-sampling verify makes the output distributed exactly as
    ``generate(..., temperature, top_k)`` — same law, not same draws
    (the two consume randomness differently).

    ``prompt``: (1, T); ``gamma``: proposals per round (the draft/target
    cost ratio picks it — more acceptance, longer verified chunks).
    Both configs must share the vocabulary; compute-dtype caches.
    ``mesh``: tp-sharded serving — the prefills and the draft's decode
    steps take the shard_map flash route (decode.generate's contract);
    the verification extend is GSPMD-partitioned einsum math already.
    """
    if prompt.shape[0] != 1:
        raise ValueError(
            "speculative decoding is per-sequence (batch 1): acceptance "
            "lengths diverge per row; vmap over sequences instead"
        )
    _validate(cfg, draft_cfg, prompt.shape[1], new_tokens, gamma)
    key, greedy, top_k, temperature = _sampling_args(
        cfg, temperature, top_k, key
    )
    return _speculative_jit(params, cfg, draft_params, draft_cfg, prompt,
                            new_tokens, gamma, key, greedy, top_k,
                            temperature, mesh)


def paged_round(params, cfg, draft_params, draft_cfg, cache, dcache,
                pos_eff, cur, gamma: int, key, greedy: bool,
                top_k: int, temperature, mesh=None):
    """ONE batched draft/verify round on the ragged paged caches — THE
    shared speculative round body (``_speculative_batched_ragged_jit``
    and the serving engine's draft-assisted rounds both call it; an
    acceptance/emit fix lands in both or neither).

    The draft runs gamma+1 ragged steps from each row's own cursor
    (the extra one writes the last proposal's K/V, the cache
    invariant); the target verifies ``[cur, props]`` in one ragged
    paged extend; acceptance is greedy-exact or rejection-sampling per
    row. Returns ``(cache, dcache, a, emit, key)``: per-row
    accepted-prefix lengths (B,) and the round's tokens
    (B, gamma+1) — positions > a are filler the caller masks.

    ``mesh``: tp-sharded rounds — the draft's ragged steps take the
    shard_map paged-kernel route (kv-head blocks), while the ragged
    extend is pure XLA scatter/gather/einsum math and partitions via
    GSPMD from the sharded params/pools alone.

    ``temperature``: a scalar, or PER-ROW ``(B,)`` temperatures — the
    serving engine's per-request sampling knob; each row's draft picks
    and warped accept/resample distributions use its own value."""
    B = pos_eff.shape[0]
    temperature = jnp.asarray(temperature, jnp.float32)
    per_row = temperature.ndim == 1
    t_draft = temperature[:, None] if per_row else temperature
    t_verify = temperature[:, None, None] if per_row else temperature
    props = []
    qs = []
    tok = cur
    dc = dcache
    for j in range(gamma + 1):
        dlogits, dc = paged_decode_step(draft_params, dc, pos_eff + j,
                                        tok, draft_cfg, mesh=mesh)
        key, sub = jax.random.split(key)
        tok = _pick(dlogits, sub, t_draft, greedy, top_k)
        if j < gamma:
            props.append(tok)
            if not greedy:
                qs.append(_warp(dlogits, t_draft, top_k))
    props = jnp.stack(props, axis=1)  # (B, gamma)

    chunk = jnp.concatenate([cur[:, None], props], axis=1)
    vlogits, cache = paged_extend_step(params, cache, pos_eff, chunk,
                                       cfg, mesh=mesh)
    if greedy:
        t_all = jnp.argmax(vlogits, axis=-1).astype(jnp.int32)
        matches = (props == t_all[:, :gamma]).astype(jnp.int32)
        a = jnp.sum(jnp.cumprod(matches, axis=1), axis=1)  # (B,)
        nxt = t_all[jnp.arange(B), a]
    else:
        key, sub = jax.random.split(key)
        a, nxt = jax.vmap(_accept_resample)(
            jax.random.split(sub, B), props,
            jnp.stack(qs, axis=1),
            _warp(vlogits, t_verify, top_k),
        )
    props_padded = jnp.concatenate([props, props[:, -1:]], axis=1)
    emit = jnp.where(jnp.arange(gamma + 1)[None, :] < a[:, None],
                     props_padded, nxt[:, None])
    return cache, dc, a, emit, key


@partial(jax.jit, static_argnums=(1, 3, 5, 6, 8, 9, 11))
def _speculative_batched_ragged_jit(params, cfg, draft_params, draft_cfg,
                                    prompts, new_tokens, gamma, key,
                                    greedy, top_k, temperature,
                                    mesh=None):
    """Per-row-progress batched speculative decoding on the ragged
    paged machinery: ONE batched draft/verify round per iteration,
    every row advancing at its OWN acceptance rate through per-row
    position cursors (the serving building block), instead of vmap
    lifting B independent single-row loops (whose per-row cache DUS
    becomes a full-cache scatter per lane per step). Rows that reach
    ``new_tokens`` freeze: their cursors stop, their (masked) writes
    land inside pages they still own, and their emit slots re-write
    the existing values."""
    B, T = prompts.shape
    # slack: the final active round can run gamma+1 past new_tokens
    max_len = T + new_tokens + gamma + 1
    page = 128 if max_len > 128 else 16
    pages = -(-max_len // page)

    cache = init_paged_cache(cfg, B, pages, page)
    dcache = init_paged_cache(draft_cfg, B, pages, page)
    logits, cache = paged_prefill(params, prompts, cfg, cache, page,
                                  mesh=mesh)
    _, dcache = paged_prefill(draft_params, prompts, draft_cfg, dcache,
                              page, mesh=mesh)
    if key is None:
        key = jax.random.PRNGKey(0)  # unused in greedy mode
    key, sub = jax.random.split(key)
    first = _pick(logits, sub, temperature, greedy, top_k)  # (B,)

    out = jnp.zeros((B, new_tokens + gamma + 1), jnp.int32)
    out = out.at[:, 0].set(first)
    rows = jnp.arange(B)

    def cond(state):
        _, _, _, _, n_out, _, _ = state
        return jnp.any(n_out < new_tokens)

    def body(state):
        cache, dcache, pos, cur, n_out, key, out = state
        active = n_out < new_tokens
        # frozen rows keep stepping (one batched kernel serves all
        # rows) but at a CLAMPED position so they can never run past
        # their page allocation; their garbage lands in pages they own
        pos_eff = jnp.where(active, pos, 0)

        cache, dc, a, emit, key = paged_round(
            params, cfg, draft_params, draft_cfg, cache, dcache,
            pos_eff, cur, gamma, key, greedy, top_k, temperature,
            mesh=mesh)
        nxt = emit[rows, a]
        # emitted this round per row: props[:a], then nxt; frozen rows
        # re-write their existing slots (gather-old / where / scatter)
        idx = jnp.minimum(n_out[:, None] + jnp.arange(gamma + 1),
                          out.shape[1] - 1)
        old = out[rows[:, None], idx]
        out = out.at[rows[:, None], idx].set(
            jnp.where(active[:, None], emit, old))
        adv = jnp.where(active, a + 1, 0)
        return (cache, dc, pos + adv, jnp.where(active, nxt, cur),
                n_out + adv, key, out)

    state = (cache, dcache, jnp.full((B,), T, jnp.int32), first,
             jnp.ones((B,), jnp.int32), key, out)
    state = lax.while_loop(cond, body, state)
    return state[6][:, :new_tokens]


def speculative_generate_batched(params, cfg: TransformerConfig,
                                 draft_params,
                                 draft_cfg: TransformerConfig, prompts,
                                 new_tokens: int, *, gamma: int = 4,
                                 key=None, temperature: float = 0.0,
                                 top_k: int = 0, impl: str = "ragged",
                                 mesh=None):
    """Batched speculative decoding, (B, new_tokens) int32.

    ``impl="ragged"`` (default): per-row-progress on the ragged paged
    machinery — one batched draft/verify round per iteration with
    per-row position cursors, each row advancing at its own acceptance
    rate (greedy output row-wise token-identical to
    :func:`speculative_generate`; sampling rows draw from the same law
    but consume randomness differently than the vmap form). ``mesh``:
    tp-sharded serving — draft steps ride the shard_map paged-kernel
    route, the ragged extend partitions via GSPMD.

    ``impl="vmap"``: the round-3 form — ``jax.vmap`` over per-row
    loops (each lane's cache update lifts to a full-cache scatter;
    kept for comparison and for exact per-row key-fold reproducibility
    with per-sequence sampling calls). Single-device (vmap over the
    shard_map route is not supported).

    Wall-clock note (both impls): the CALL returns when the slowest
    row finishes — that is batch semantics, not an impl property; for
    throughput past it, serve via models/serving.py's continuous
    batching."""
    if prompts.ndim != 2:
        raise ValueError(f"prompts must be (B, T), got {prompts.shape}")
    _validate(cfg, draft_cfg, prompts.shape[1], new_tokens, gamma)
    key, greedy, top_k, temperature = _sampling_args(
        cfg, temperature, top_k, key
    )
    if impl == "ragged":
        return _speculative_batched_ragged_jit(
            params, cfg, draft_params, draft_cfg, prompts, new_tokens,
            gamma, key, greedy, top_k, temperature, mesh)
    if impl != "vmap":
        raise ValueError(f"impl must be 'ragged' or 'vmap', got {impl!r}")
    if mesh is not None:
        raise ValueError(
            "impl='vmap' is single-device (vmap over the shard_map "
            "route is unsupported); use impl='ragged' with a mesh")
    # greedy mode still threads per-row keys through vmap (unused by the
    # accept path); split a fixed root so the dummies share the REAL
    # keys' dtype/format — raw uint32 zeros relied on the deprecated
    # legacy-key acceptance and break under typed keys
    keys = jax.random.split(
        key if key is not None else jax.random.PRNGKey(0),
        prompts.shape[0])

    def one(row, k):
        return _speculative_jit(params, cfg, draft_params, draft_cfg,
                                row[None, :], new_tokens, gamma, k,
                                greedy, top_k, temperature)[0]

    return jax.vmap(one)(prompts, keys)
