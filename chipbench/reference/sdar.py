"""The plain reference of the ``sdar_moe`` layer and of generation by
diffusion over blocks: float32, ``jax.default_matmul_precision("highest")``
(every product here names ``Precision.HIGHEST`` itself), no kernel, no
cache, no batching. ``B`` the block length, ``M`` the mask id:

    n1 = RMSNorm(h)
    q, k, v = n1 Wqkv;  q <- RMSNorm_Dh(q) qn;  k <- RMSNorm_Dh(k) kn
    a  = h + softmax_mask(rope(q) rope(k)^T / sqrt(Dh)) v  Wo      GQA
    n2 = RMSNorm(a)
    p  = softmax_E(n2 Wr);  idx = top_k(p);  g = p[idx] / sum p[idx]
    out = a + sum_k g_k Wd_k (silu(Wg_k n2) * (Wu_k n2))
    logits = RMSNorm(h_L) Whead                        (no shift: the
                      logits AT a position predict that position)
    mask: position i sees j  iff  j // B <= i // B

The mask is an explicit (T, T) matrix, so that one forward can also hold
copies of a block in several states (:func:`replay_plan`). The experts are
a loop over ALL experts with the dense gate matrix (an expert that a token
did not pick is multiplied by 0): sixteen times the work of the picks, and
nothing to sort. :func:`generate` is the published loop as recalled,
recomputing the whole sequence every forward.

Departures from the published forward, each `assumed` in the
configuration: q/k norms over each head as the ``qwen3_moe`` forward that
``sdar_moe`` derives from; logits unshifted; the prompt's remainder joins
the first block; greedy; ``low_confidence_dynamic`` settles every masked
position above the threshold and at least the most confident one.

Weights: an explicit tree in the program's layout (``forward``,
``generate``: the tests), or from ``chipbench.weights_sdar`` a layer (an
expert, a block of the vocabulary) at a time (``replay_numbers``: the
cell). Imports nothing of the program. ``lowp`` names the control's
precision: the same mathematics with every matmul's two operands rounded
to that type first.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench import weights_sdar as weights
from chipbench.reference.transformer import mm, rope

HI = lax.Precision.HIGHEST


def rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * scale


def block_mask(n: int, block_len: int):
    """(n, n) bool: position i sees j iff j // B <= i // B."""
    blk = np.arange(n) // block_len
    return blk[None, :] <= blk[:, None]


def attention(q, k, v, mask):
    """GQA under an explicit mask: q (T, H, Dh), k/v (T, Hkv, Dh), mask
    (T, T) bool -> (T, H*Dh). One head at a time."""
    T, H, Dh = q.shape
    g = H // k.shape[1]

    def one(h):
        qh, kh, vh = q[:, h], k[:, h // g], v[:, h // g]
        s = jnp.matmul(qh, kh.T, precision=HI) / (Dh ** 0.5)
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.matmul(p, vh, precision=HI)

    o = lax.map(one, jnp.arange(H))                      # (H, T, Dh)
    return o.transpose(1, 0, 2).reshape(T, H * Dh)


def gates(h, router, m, lowp=None):
    """The dense gate matrix (T, E): a token's top-k softmax
    probabilities (renormalised to one where ``m["renorm"]``) at the
    experts it picked, 0 elsewhere."""
    p = jax.nn.softmax(mm(h, router, lowp), axis=-1)
    g, idx = lax.top_k(p, m["k"])          # ties by index
    if m["renorm"]:
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    return jnp.zeros_like(p).at[jnp.arange(h.shape[0])[:, None], idx].set(g)


def experts(h, G, expert_of, m, lowp=None):
    """sum_e G[:, e] Wd_e (silu(Wg_e h) * (Wu_e h)) over ALL experts;
    ``expert_of(e)`` gives expert e's {w_gate, w_up, w_down}."""

    def add(acc, e):
        w = expert_of(e)
        mid = jax.nn.silu(mm(h, w["w_gate"], lowp)) * mm(h, w["w_up"], lowp)
        return acc + G[:, e, None] * mm(mid, w["w_down"], lowp), None

    out, _ = lax.scan(add, jnp.zeros_like(h), jnp.arange(m["E"]))
    return out


def layer_forward(x, lw, expert_of, m, mask, positions, lowp=None,
                  gate_matrix=None):
    """One layer on one sequence: x (T, D) float32. ``gate_matrix``: a
    test's own gates in place of the route's."""
    T = x.shape[0]
    H, Hkv, Dh = m["H"], m["Hkv"], m["Dh"]
    h = rmsnorm(x, lw["ln1_scale"], m["eps"])
    q, k, v = jnp.split(mm(h, lw["wqkv"], lowp),
                        [H * Dh, (H + Hkv) * Dh], axis=-1)
    q = rmsnorm(q.reshape(T, H, Dh), lw["q_norm"], m["eps"])
    k = rmsnorm(k.reshape(T, Hkv, Dh), lw["k_norm"], m["eps"])
    o = attention(rope(q, positions, m["theta"]),
                  rope(k, positions, m["theta"]), v.reshape(T, Hkv, Dh),
                  mask)
    x = x + mm(o, lw["wo"], lowp)
    h = rmsnorm(x, lw["ln2_scale"], m["eps"])
    G = gates(h, lw["router"], m, lowp) if gate_matrix is None \
        else gate_matrix
    return x + experts(h, G, expert_of, m, lowp)


def forward(params, tokens, block_len: int, m: dict, *, mask=None,
            positions=None, lowp=None):
    """Logits (T, V) float32 of one sequence ``tokens`` (T,) over a tree
    in the program's layout, under the block mask of ``block_len`` (or an
    explicit ``mask`` (T, T) and ``positions`` (T,))."""
    tokens = jnp.asarray(tokens, jnp.int32)
    T = tokens.shape[0]
    mask = jnp.asarray(block_mask(T, block_len) if mask is None else mask)
    positions = jnp.arange(T) if positions is None else jnp.asarray(positions)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    x = f32(params["embed"])[tokens]
    for lw in params["layers"]:
        lw = {n: f32(a) for n, a in lw.items()}
        x = layer_forward(
            x, lw, lambda e, lw=lw: {n: lw[n][e]
                                     for n in weights.EXPERT_LEAVES},
            m, mask, positions, lowp)
    x = rmsnorm(x, f32(params["ln_f_scale"]), m["eps"])
    return mm(x, f32(params["lm_head"]), lowp)


def take_by_confidence(logc, masked, *, rule: str, steps: int,
                       threshold: float):
    """The masked positions a forward settles, from the positions'
    log-confidences (B,): ``static`` the ceil(B / steps) most confident,
    ties by index; ``dynamic`` every one whose confidence passes
    ``threshold``, and at least the most confident."""
    B = len(logc)
    order = np.argsort(-np.where(masked, logc, -np.inf), kind="stable")
    take = np.zeros(B, bool)
    if rule == "static":
        take[order[:-(-B // steps)]] = True
    else:
        take[order[0]] = True
        take |= np.exp(logc) > threshold
    return take & masked


def settle(logits, masked, *, rule: str, steps: int, threshold: float):
    """What one denoising forward settles, in numpy: ``logits`` (B, V) at
    the block's positions, ``masked`` (B,) bool. Returns (candidates (B,),
    settle (B,) bool inside ``masked``, log-confidences (B,)):
    :func:`take_by_confidence` on each position's largest softmax
    probability."""
    logits = np.asarray(logits, np.float64)
    best = logits.max(-1)
    logc = -np.log(np.exp(logits - best[:, None]).sum(-1))
    take = take_by_confidence(logc, masked, rule=rule, steps=steps,
                              threshold=threshold)
    return logits.argmax(-1), take, logc


def generate(params, prompt, max_new: int, m: dict, *, rule: str = "static",
             steps: int = 2, threshold: float = 0.9, lowp=None,
             forward_fn=None, pad_to: int = 1):
    """The generation loop, recomputing the WHOLE sequence every forward.
    The prompt's whole blocks stand; its remainder is the given part of
    the first block; a block starts as its given tokens and ``M``
    elsewhere, every denoising forward settles part of the masked
    positions (:func:`settle`), and the block is committed when no mask
    is left (here: appended; there is no cache to store into). Returns
    (tokens (max_new,), blocks: [(tokens (B,), forward index (B,), -1
    where given)], whole, the last one past the limit too).

    The sequence is padded to one length (the next multiple of the block
    and of ``pad_to``) with whole blocks of ``M`` behind it, which no
    earlier position sees: one compiled program. ``forward_fn``: tokens
    (n,) -> logits (n, V), the caller's own (jitted) :func:`forward`."""
    B, M = m["B"], m["mask_id"]
    prompt = np.asarray(prompt, np.int32)
    P = len(prompt)
    start, end = P // B * B, P + max_new
    n = -(-(-(-end // B) * B) // pad_to) * pad_to
    fwd = forward_fn or jax.jit(
        lambda t: forward(params, t, B, m, lowp=lowp))
    seq = np.full((n,), M, np.int32)
    seq[:start] = prompt[:start]
    blocks = []
    for cur in range(start, -(-end // B) * B, B):
        given = max(0, P - cur)
        blk = np.full((B,), M, np.int32)
        blk[:given] = prompt[cur:cur + given]
        masked = np.arange(B) >= given
        fidx = np.full((B,), -1, np.int32)
        f = 0
        while masked.any():
            seq[cur:cur + B] = blk
            logits = np.asarray(fwd(jnp.asarray(seq)))[cur:cur + B]
            cand, take, _ = settle(logits, masked, rule=rule, steps=steps,
                                   threshold=threshold)
            blk[take], fidx[take] = cand[take], f
            masked &= ~take
            f += 1
        seq[cur:cur + B] = blk          # the commit
        blocks.append((blk.copy(), fidx))
    return seq[P:end].copy(), blocks


# ---------------------------------------------------------------------------
# Replaying what an engine recorded: every (block, forward) state at once
# ---------------------------------------------------------------------------

def replay_plan(prompt, blocks, m: dict) -> dict:
    """One forward that holds every state a request's blocks went
    through. ``blocks``: [(tokens (B,), forward index (B,))] as the engine
    recorded them, whole. The sequence is [the settled sequence | a copy
    of block b at the start of its forward f, for every (b, f)]: a copy
    has its block's positions, sees the settled blocks before its own and
    itself, and nothing sees it. Returns the tokens, positions and mask
    of that sequence and, a copy, its rows, block tokens and forward
    indices."""
    B, M = m["B"], m["mask_id"]
    prompt = np.asarray(prompt, np.int32)
    start = len(prompt) // B * B
    toks = [np.asarray(b[0], np.int32) for b in blocks]
    fidx = [np.asarray(b[1], np.int32) for b in blocks]
    given = len(prompt) - start
    if not np.array_equal(toks[0][:given], prompt[start:]) \
            or np.any(fidx[0][:given] != -1):
        raise ValueError("the first block does not open with the prompt's "
                         "remainder")
    seq = [prompt[:start], *toks]
    pos = [np.arange(start + len(toks) * B)]
    blk_no = [pos[0] // B]
    copy_id = [np.zeros_like(pos[0])]
    copies = []
    at = len(pos[0])
    for b, (t, f) in enumerate(zip(toks, fidx)):
        for step in range(int(f.max()) + 1):
            seq.append(np.where(f >= step, M, t).astype(np.int32))
            p = start + b * B + np.arange(B)
            pos.append(p)
            blk_no.append(p // B)
            copy_id.append(np.full((B,), len(copies) + 1))
            copies.append({"rows": np.arange(at, at + B), "block": b,
                           "forward": step, "tokens": t, "fidx": f})
            at += B
    blk_no, copy_id = np.concatenate(blk_no), np.concatenate(copy_id)
    return {"tokens": np.concatenate(seq), "positions": np.concatenate(pos),
            "block_no": blk_no, "copy_id": copy_id, "copies": copies}


def plan_mask(block_no, copy_id):
    """(T, T) bool: row r sees key c iff c is settled (copy 0) in a block
    before r's, or c is of r's own copy and block."""
    settled_before = (copy_id[None, :] == 0) & (block_no[None, :]
                                                < block_no[:, None])
    own = (copy_id[None, :] == copy_id[:, None]) & (block_no[None, :]
                                                    == block_no[:, None])
    return settled_before | own


def pad_plan(plan: dict, n: int) -> dict:
    """The plan's sequence padded to ``n`` rows that only see themselves
    and their like, and that nothing else sees."""
    k = n - len(plan["tokens"])
    out = dict(plan)
    out["tokens"] = np.pad(plan["tokens"], (0, k))
    out["positions"] = np.pad(plan["positions"], (0, k))
    out["block_no"] = np.pad(plan["block_no"], (0, k),
                             constant_values=1 << 30)
    out["copy_id"] = np.pad(plan["copy_id"], (0, k), constant_values=-1)
    return out


def replay_gaps(plan: dict, best, lse, at_target, *, steps: int,
                rule: str = "static", threshold: float = 0.9,
                picked=None) -> tuple:
    """The two families of gaps of one replayed request, from the
    reference's numbers at the copies' rows (``best`` its largest logit,
    ``lse`` its log-sum-exp, ``at_target`` its logit of the judged token),
    each (copies, B):

    - a settled token's gap: ``best - at_target`` at every position the
      forward settled;
    - a pick of positions' gap: the reference's log-confidence (``best -
      lse``) of the best position LEFT masked less that of the least
      confident one settled, at least 0; none where the forward left
      nothing masked.

    ``picked`` (copies, B) bool: the positions judged as settled in place
    of the recorded ones (a control's own picks)."""
    token_gaps, pick_gaps = [], []
    logc = np.asarray(best, np.float64) - np.asarray(lse, np.float64)
    gap = np.asarray(best, np.float64) - np.asarray(at_target, np.float64)
    for c, cp in enumerate(plan["copies"]):
        masked = cp["fidx"] >= cp["forward"]
        done = (cp["fidx"] == cp["forward"]) if picked is None else picked[c]
        left = masked & ~done
        token_gaps.extend(gap[c][done])
        if left.any() and done.any():
            pick_gaps.append(max(0.0, logc[c][left].max()
                                 - logc[c][done].min()))
    return np.asarray(token_gaps), np.asarray(pick_gaps)


def control_picks(plan: dict, best, lse, *, steps: int, rule: str = "static",
                  threshold: float = 0.9):
    """What a reference whose numbers are ``best`` / ``lse`` would settle
    at every copy: (copies, B) bool (:func:`settle`'s rule on its own
    log-confidences)."""
    logc = np.asarray(best, np.float64) - np.asarray(lse, np.float64)
    return np.stack([take_by_confidence(
        logc[c], cp["fidx"] >= cp["forward"], rule=rule, steps=steps,
        threshold=threshold) for c, cp in enumerate(plan["copies"])])


def head_numbers(x_rows, ln_f, head_block_of, n_blocks: int, vb: int,
                 target, eps, lowp=None):
    """Of the logits at ``x_rows`` (R, D), a block of the vocabulary at a
    time: (largest, log-sum-exp, argmax, the logit of ``target`` (R,))."""
    xn = rmsnorm(x_rows, ln_f, eps)
    R = x_rows.shape[0]

    def add(carry, b):
        best, total, arg, at = carry
        z = mm(xn, head_block_of(b), lowp)                  # (R, vb)
        zb = jnp.max(z, axis=-1)
        new = jnp.maximum(best, zb)
        total = total * jnp.exp(best - new) + jnp.sum(
            jnp.exp(z - new[:, None]), axis=-1)
        arg = jnp.where(zb > best, b * vb + jnp.argmax(z, axis=-1), arg)
        local = target - b * vb
        inside = (local >= 0) & (local < vb)
        got = jnp.take_along_axis(z, jnp.clip(local, 0, vb - 1)[:, None],
                                  axis=-1)[:, 0]
        return (new, total, arg, jnp.where(inside, got, at)), None

    init = (jnp.full((R,), -jnp.inf, jnp.float32),
            jnp.zeros((R,), jnp.float32), jnp.zeros((R,), jnp.int32),
            jnp.zeros((R,), jnp.float32))
    (best, total, arg, at), _ = lax.scan(add, init, jnp.arange(n_blocks))
    return best, best + jnp.log(total), arg, at


def tree_replay_numbers(params, m: dict, plan: dict, target_of=None,
                        lowp=None, forward_fn=None):
    """:func:`replay_numbers` over an explicit tree (the tests): (best,
    lse, argmax, at_target), each (copies, B). ``target_of``: (copies, B)
    tokens judged, default the recorded ones. ``forward_fn``: (tokens,
    mask, positions) -> logits, the caller's own (jitted)
    :func:`forward`."""
    fwd = forward_fn or (lambda t, mask, pos: forward(
        params, t, m["B"], m, mask=mask, positions=pos, lowp=lowp))
    logits = fwd(jnp.asarray(plan["tokens"], jnp.int32),
                 jnp.asarray(plan_mask(plan["block_no"], plan["copy_id"])),
                 jnp.asarray(plan["positions"], jnp.int32))
    rows = np.stack([c["rows"] for c in plan["copies"]])
    target = (np.stack([c["tokens"] for c in plan["copies"]])
              if target_of is None else target_of)
    z = np.asarray(logits)[rows]                               # (C, B, V)
    best = z.max(-1)
    lse = best + np.log(np.exp(z - best[..., None]).sum(-1))
    at = np.take_along_axis(z, np.asarray(target)[..., None], -1)[..., 0]
    return best, lse, z.argmax(-1), at


def _freeze(m: dict):
    return tuple(sorted(m.items()))


@functools.partial(jax.jit, static_argnames=("m",))
def _embed(key, tokens, *, m):
    """The tokens' rows, a block of the vocabulary at a time."""
    m = dict(m)

    def add(x, b):
        at = tokens - b * m["Vb"]
        inside = (at >= 0) & (at < m["Vb"])
        rows = weights.embed_block(key, m, b)[jnp.clip(at, 0, m["Vb"] - 1)]
        return x + jnp.where(inside[:, None], rows, 0.0), None

    x, _ = lax.scan(add, jnp.zeros((tokens.shape[0], m["D"]), jnp.float32),
                    jnp.arange(weights.vocab_blocks(m)))
    return x


@functools.partial(jax.jit, static_argnames=("m", "lowp"))
def _layer_step(key, index, x, mask, positions, *, m, lowp):
    """One layer; ``index`` is traced, so one program serves every layer
    at a length. An expert's matrices are made inside the loop."""
    m = dict(m)
    return layer_forward(x, weights.layer(key, m, index),
                         lambda e: weights.expert(key, m, index, e), m,
                         mask, positions, lowp)


@functools.partial(jax.jit, static_argnames=("m", "lowp"))
def _head(key, x_rows, target, *, m, lowp):
    m = dict(m)
    return head_numbers(x_rows, weights.final_norm(key, m),
                        lambda b: weights.head_block(key, m, b),
                        weights.vocab_blocks(m), m["Vb"], target, m["eps"],
                        lowp)


def replay_hidden(seed: int, m: dict, plans, *, lowp=None,
                  pad_to: int = 512):
    """For each plan (:func:`replay_plan`), the seeded reference's last
    hidden states at every copy's rows, (rows padded to one count, D) on
    the device. Every plan is padded to ONE length, the longest's next
    multiple of ``pad_to``: one program a step. Layers outermost."""
    key = weights.seed_key(seed)
    fm = _freeze(m)
    n = -(-max(len(p["tokens"]) for p in plans) // pad_to) * pad_to
    padded = [pad_plan(p, n) for p in plans]
    masks = [jnp.asarray(plan_mask(p["block_no"], p["copy_id"]))
             for p in padded]
    poss = [jnp.asarray(p["positions"], jnp.int32) for p in padded]
    xs = [_embed(key, jnp.asarray(p["tokens"], jnp.int32), m=fm)
          for p in padded]
    for l in range(m["L"]):
        xs = [_layer_step(key, jnp.int32(l), x, mk, ps, m=fm, lowp=lowp)
              for x, mk, ps in zip(xs, masks, poss)]
    # every plan's copy rows padded to one count (its last row again)
    k = -(-max(len(p["copies"]) for p in plans) * m["B"] // 64) * 64
    out = []
    for p, x in zip(plans, xs):
        rows = np.concatenate([c["rows"] for c in p["copies"]])
        out.append(x[np.pad(rows, (0, k - len(rows)), "edge")])
    return out


def replay_head(seed: int, m: dict, plans, hidden, *, targets=None,
                lowp=None):
    """The head over :func:`replay_hidden`'s rows: a plan, (best, lse,
    argmax, at_target), each (copies, B). ``targets``: a plan, the
    (copies, B) tokens judged (default the recorded ones)."""
    key = weights.seed_key(seed)
    fm = _freeze(m)
    out = []
    for i, (p, x) in enumerate(zip(plans, hidden)):
        tgt = (np.concatenate([c["tokens"] for c in p["copies"]])
               if targets is None else np.asarray(targets[i]).reshape(-1))
        nums = _head(key, x, jnp.asarray(
            np.pad(tgt, (0, x.shape[0] - len(tgt)), "edge"), jnp.int32),
            m=fm, lowp=lowp)
        out.append(tuple(np.asarray(a)[:len(tgt)].reshape(-1, m["B"])
                         for a in nums))
    return out


def replay_numbers(seed: int, m: dict, plans, *, targets=None, lowp=None,
                   pad_to: int = 512):
    """:func:`replay_hidden`, then :func:`replay_head`."""
    return replay_head(
        seed, m, plans, replay_hidden(seed, m, plans, lowp=lowp,
                                      pad_to=pad_to),
        targets=targets, lowp=lowp)
