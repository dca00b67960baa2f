"""Single-query (decode-step) flash attention streaming the KV cache.

The serving-side analog of ops/flash_attention.py (the framework rule:
hot loops are Pallas — docs/ARCHITECTURE.md; reference analog: the
own-the-hot-loop principle of concurency/sycl_con.cpp:26-33). A decode
step is cache-read-bound, so a kernel's job is to make exactly one
streamed pass over the *live* prefix of the cache. Two kernels share the
arithmetic (:func:`_softmax_block`, one online-softmax update a block)
and differ in how the blocks reach VMEM:

- the LINEAR cache (:func:`flash_decode_attention`): grid =
  (batch·kv_heads, S_max/BLOCK_S); each step loads one (BLOCK_S,
  head_dim) cache block into VMEM while the previous block computes
  (Pallas double-buffers the stream); the online-softmax state (m, l,
  acc) for the g = n_heads/kv_heads grouped queries carries in f32
  scratch across the S axis. The current fill position arrives via
  scalar prefetch, and the cache index map CLAMPS blocks past it to the
  last live block — consecutive clamped steps revisit that block, Pallas
  elides the fetch, and ``pl.when`` skips the compute. Per-step HBM
  traffic is proportional to the POSITION, not the allocated cache
  length (the XLA gather path always reads all of max_len and masks).
  The cache must be kernel-layout: (batch·kv_heads, S_max, head_dim)
  with S contiguous — models/decode.py stores it that way from prefill
  on (a per-step transpose would itself read the whole cache and defeat
  the point).
- the PAGED cache (:func:`flash_decode_paged`): grid = (live rows,).
  The pools stay in HBM and the kernel copies pages itself, so its
  traffic follows the DATA it is given and not the engine's shape: the
  list of live rows arrives by scalar prefetch and the grid's extent is
  its length (as ops/ssm_step's is), so an idle slot is not visited; a
  visited row at position p has pages 0 .. p // page_size copied once
  each (all K/V heads of a page in one copy: they are contiguous in the
  pool) through a ring of ``pages_per_step`` page buffers that runs on
  from one row into the next, and no page past p. A clamped index map
  cannot do that: every page block of a grid step is an operand of its
  own, fetched whenever its index moves, so an idle slot costs as many
  fetches as a live one and a live row more than twice its pages
  (PERF.md section 6, PR 33).
- GQA is native in both: the q block is the (g, head_dim) group sharing
  a kv head; the cache is streamed kv_heads-narrow. MHA is g = 1.

A page's arithmetic is not free next to its copy: the float32
``HIGHEST`` products (six bfloat16 passes each over K and V cast to
float32, K transposed) took three times the page's copy (PERF.md section
6, PRs 33 and 35). So :func:`_softmax_block` computes the same float32
result from the products that are not zero: K and V stay in the cache's
dtype, the scores are one bfloat16 product with float32 accumulation,
and the float32 probabilities go in as three exact bfloat16 pieces
stacked into one product against V (:func:`_exact_dot`, :func:`_split3`).
What is left of a page is one chain (q·kᵀ, its row max, exp, p·v) whose
latency outlasts the copy; the paged kernel overlaps it with the next
page's q·kᵀ, and then runs at the copy's pace.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hpc_patterns_tpu.ops.tiling import live_rows, resolve_interpret

_NEG_INF = -1e30


def _split3(x):
    """float32 ``x`` as three bfloat16 pieces whose float32 sum is ``x``
    exactly: hi = bf16(x), mid = bf16(x − hi), lo = bf16(x − hi − mid).
    Each subtraction is exact in float32 and each residue has 8 fewer
    significant bits than the last, so lo holds the last 8 of x's 24."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _exact_dot(a, b, contract):
    """``lax.dot_general(a, b)`` over ``contract`` = (a's dim, b's dim),
    float32 out, with the terms of ``Precision.HIGHEST`` that are not
    zero, in ONE product: ``b`` (the page) is bfloat16 already, so
    HIGHEST's six bfloat16 passes reduce to a's three pieces against b.
    A float32 ``a`` (g rows) is split by :func:`_split3`, the pieces
    stacked to 3g rows (one push of b into the MXU serves all three) and
    the three row blocks summed; a bfloat16 ``a`` is one exact product
    with float32 accumulation. A float32 ``b`` (float32 pools, off the
    chip's cells) takes HIGHEST itself."""
    dims = (((contract[0],), (contract[1],)), ((), ()))
    if b.dtype == jnp.float32:
        return lax.dot_general(a.astype(jnp.float32), b, dims,
                               precision=lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
    if a.dtype != jnp.float32:
        return lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)
    g = a.shape[0]
    out = lax.dot_general(jnp.concatenate(_split3(a), axis=0), b, dims,
                          preferred_element_type=jnp.float32)
    return out[:g] + out[g:2 * g] + out[2 * g:]


def _scores(q, k, ks, scale: float):
    """A block's scores, float32 (g, block_s): q·kᵀ contracting on D of
    both, times ``scale`` and, for an int8 block, its lane-major
    (1, block_s) per-row scales ``ks`` (None otherwise)."""
    if ks is not None:
        k = k.astype(jnp.bfloat16)
    s = _exact_dot(q, k, (1, 1)) * scale
    if ks is not None:
        s = s * ks.astype(jnp.float32)
    return s


def _update(s, v, vs, m, l, acc, block_start, pos, window: int = 0):
    """The online-softmax state (m, l, acc) updated by a block's scores
    ``s`` and values ``v`` (with lane-major scales ``vs`` for an int8
    block, None otherwise), the block at logical rows [block_start, ...),
    rows past ``pos`` masked, and with ``window`` > 0 the rows at or
    before ``pos - window`` too."""
    k_pos = block_start + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if window:
        s = jnp.where((k_pos <= pos) & (k_pos > pos - window), s, _NEG_INF)
    else:
        s = jnp.where(k_pos <= pos, s, _NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    rescale = jnp.exp(m - m_new)
    l = l * rescale + p.sum(axis=-1, keepdims=True)
    if vs is not None:
        p = p * vs.astype(jnp.float32)
        v = v.astype(jnp.bfloat16)
    return m_new, l, acc * rescale + _exact_dot(p, v, (1, 0))


def _softmax_block(q_ref, k_ref, v_ref, ks_ref, vs_ref, m_ref, l_ref,
                   acc_ref, block_start, pos, scale: float,
                   quantized: bool):
    """One online-softmax update over the cache block at logical rows
    [block_start, block_start + block_s): THE streamed-attention math,
    :func:`_scores` then :func:`_update`, shared by the linear kernel
    (one block per grid step) and the paged kernel (one page of one K/V
    head at a time, its scores a page ahead).

    The float32 result of the float32 ``HIGHEST`` products, from the
    products that are not zero (:func:`_exact_dot`): the (block_s, D)
    K and V tiles are used in the cache's dtype as they are (int8 →
    bfloat16 is exact), never cast to float32 nor transposed.
    Scores: q·kᵀ contracting on D of both, ONE bfloat16 product with
    float32 accumulation when q is bfloat16 (the serving path: the
    HIGHEST product term for term, equal to it to the bit on the chip),
    q's three pieces stacked when it is float32. Values: the float32
    probabilities p split exactly into three bfloat16 pieces, stacked to
    3g rows, ONE product against V, the three row blocks summed: the
    same three partial products HIGHEST sums, in another order. A page
    so costs one push of K and one of V into the MXU where HIGHEST on
    float32 operands made six of each and a transpose (PERF.md section
    6, PR 35).
    ``quantized``: per-row dequant folded into the LANE axis of the
    score and probability blocks — s_ij = (q·k8_j)·kscale_j and out =
    (p∘vscaleᵀ)·v8 (p is scaled before its split); the (1, block_s)
    scale rows ride lane-major, and the (block_s, D) tiles are never
    rescaled elementwise (a sublane-oriented (block_s, 1) scale
    multiply measured ~3x slower than bf16)."""
    ks, vs = (ks_ref[:], vs_ref[:]) if quantized else (None, None)
    m_ref[:], l_ref[:], acc_ref[:] = _update(
        _scores(q_ref[:], k_ref[:], ks, scale), v_ref[:], vs, m_ref[:],
        l_ref[:], acc_ref[:], block_start, pos)


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, *rest, scale: float,
                   quantized: bool, hkv_per_row: int = 0):
    # grid (B·Hkv, n_s): one kv-cache block per step, grouped-query
    # online softmax carried in scratch over the S axis. ``quantized``:
    # the cache blocks are int8 with per-row scales (two extra refs) —
    # dequantized in VMEM, so HBM streams HALF the bytes of bf16 (the
    # whole cost of a decode step on a read-bound path). ``hkv_per_row``
    # > 0: RAGGED positions — pos_ref holds one fill position per
    # sequence and grid row r belongs to sequence r // hkv_per_row.
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_ref, l_ref, acc_ref = rest
    g, d = q_ref.shape
    block_s = k_ref.shape[0]
    si = pl.program_id(1)
    n_s = pl.num_programs(1)
    pos = (pos_ref[pl.program_id(0) // hkv_per_row] if hkv_per_row
           else pos_ref[0])

    @pl.when(si == 0)
    def _():
        m_ref[:] = jnp.full((g, 1), _NEG_INF, jnp.float32)
        l_ref[:] = jnp.zeros((g, 1), jnp.float32)
        acc_ref[:] = jnp.zeros((g, d), jnp.float32)

    # a block fully past the fill position contributes nothing: its
    # fetch was elided by the clamped index map, its compute is skipped
    @pl.when(si * block_s <= pos)
    def _():
        _softmax_block(q_ref, k_ref, v_ref, ks_ref, vs_ref, m_ref,
                       l_ref, acc_ref, si * block_s, pos, scale,
                       quantized)

    @pl.when(si == n_s - 1)
    def _():
        o_ref[:] = acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)


def flash_decode_attention(
    q,
    k_cache,
    v_cache,
    pos,
    *,
    k_scale=None,
    v_scale=None,
    scale: float | None = None,
    block_s: int = 2048,
    interpret: bool | None = None,
):
    """Attention of one new token per sequence against the KV cache.

    ``q``: (B, n_heads, head_dim) — the current token's queries;
    ``k_cache``/``v_cache``: (B, kv_heads, S_max, head_dim), the live
    prefix being rows [0, pos]; ``pos``: traced int32 scalar, the
    position being decoded (== number of already-cached tokens; the
    row at ``pos`` must already hold this token's K/V). Returns
    (B, n_heads, head_dim) f32. Numerically the gather-path softmax
    (models/decode.py) evaluated blockwise in f32.

    ``k_scale``/``v_scale``: (B, kv_heads, S_max) per-row dequant
    scales for an int8 cache (kv_cache_dtype="int8"): the kernel
    streams the int8 blocks — half the HBM bytes — and dequantizes in
    VMEM.
    """
    B, H, D = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    if H % Hkv or v_cache.shape[1] != Hkv:
        raise ValueError(
            f"kv heads {Hkv}/{v_cache.shape[1]} must match and divide "
            f"n_heads {H}"
        )
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    block_s = min(block_s, S)
    interpret = resolve_interpret(interpret, "flash_decode")
    g = H // Hkv

    quantized = k_scale is not None
    qr = q.reshape(B * Hkv, g, D)          # q head k·g+j -> row b·Hkv+k
    kr = k_cache.reshape(B * Hkv, S, D)
    vr = v_cache.reshape(B * Hkv, S, D)
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)

    # ceil-div grid: a ragged last block reads the padded tile and the
    # k_pos <= pos mask (pos < S always) zeroes whatever it holds
    n_s = -(-S // block_s)

    def kv_idx(r, si, pos_ref):
        # clamp past-the-fill blocks to the last live one: consecutive
        # clamped steps revisit it and Pallas skips the fetch
        return r, jnp.minimum(si, pos_ref[0] // block_s), 0

    row = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    in_specs = [
        row((None, g, D), lambda r, si, pos: (r, 0, 0)),
        row((None, block_s, D), kv_idx),
        row((None, block_s, D), kv_idx),
    ]
    operands = [pos_arr, qr, kr, vr]
    if quantized:
        # scales enter as LANE-major (1, block_s) rows (see kernel note)
        scale_idx = lambda r, si, pos: (
            kv_idx(r, si, pos)[0], 0, kv_idx(r, si, pos)[1]
        )
        in_specs += [row((None, 1, block_s), scale_idx),
                     row((None, 1, block_s), scale_idx)]
        operands += [k_scale.reshape(B * Hkv, 1, S),
                     v_scale.reshape(B * Hkv, 1, S)]
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=float(scale),
                          quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * Hkv, n_s),
            in_specs=in_specs,
            out_specs=row((None, g, D), lambda r, si, pos: (r, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),   # running max
                pltpu.VMEM((g, 1), jnp.float32),   # running sumexp
                pltpu.VMEM((g, D), jnp.float32),   # output accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, g, D), jnp.float32),
        name="flash_decode",
        interpret=interpret,
    )(*operands)
    return out.reshape(B, H, D)


def _decode_kernel_paged(rows_ref, pos_ref, table_ref, q_ref, *rest,
                         scale: float, pages: int, depth: int,
                         quantized: bool, ragged: bool, window: int = 0):
    # grid (visited rows,): grid step i is row rows_ref[i] with all of its
    # K/V heads. The pools stay in HBM and the kernel copies the pages
    # itself. The call's work is ONE stream of (row, page) items in
    # visiting order: item t lands in slot t % depth of a ring of page
    # buffers (a page's K/V heads are contiguous in the pool, so one copy
    # brings them all) and its copies start depth - 1 items before it is
    # attended over, so the fetches run on across the rows: a row's first
    # page is on its way while the row before it is attended over. ``cur``
    # (SMEM, carried over the grid) is the fetch cursor. ``window`` > 0: a
    # row's pages are those from the one holding ``pos - window + 1`` to
    # the one holding ``pos``, and its keys at or before ``pos - window``
    # are masked; the cursor counts pages from the row's first.
    n = 4 if quantized else 2               # k, v [, k scales, v scales]
    pools, o_ref, bufs = rest[:n], rest[n], rest[n + 1:2 * n + 1]
    sems, cur, m_ref, l_ref, acc_ref = rest[2 * n + 1:]
    kv_heads, page_size = bufs[0].shape[1], bufs[0].shape[2]
    i, count = pl.program_id(0), pl.num_programs(0)

    def row_pos(r):
        return pos_ref[r] if ragged else pos_ref[0]

    def first_page(r):
        return jnp.maximum(row_pos(r) - window + 1, 0) // page_size

    def live_pages(r):
        # the fetch cursor and the walk must count a row alike whatever
        # its position holds: at least its first page, at most its table
        n = jnp.clip(row_pos(r) // page_size + 1, 1, pages)
        return n - first_page(r) if window else n

    def copies(r, page, slot):
        if window:   # ``page`` counts from the row's first page
            page = first_page(r) + page
        at = table_ref[r * pages + page]
        return [pltpu.make_async_copy(pool.at[at], buf.at[slot],
                                      sems.at[j, slot])
                for j, (pool, buf) in enumerate(zip(pools, bufs))]

    def fetch():
        # start the copies of the next item not yet asked for, if any
        vi, page, t = cur[0], cur[1], cur[2]

        @pl.when(vi < count)
        def _():
            r = rows_ref[vi]
            for c in copies(r, page, t % depth):
                c.start()
            last = page + 1 >= live_pages(r)
            cur[0] = jnp.where(last, vi + 1, vi)
            cur[1] = jnp.where(last, 0, page + 1)
            cur[2] = t + 1

    @pl.when(i == 0)
    def _():
        cur[0] = cur[1] = cur[2] = cur[3] = 0
        for _ in range(depth - 1):
            fetch()

    r = rows_ref[i]
    pos = row_pos(r)
    m_ref[:] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
    l_ref[:] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[:] = jnp.zeros(acc_ref.shape, jnp.float32)

    def scores(slot):
        return [_scores(q_ref[h], bufs[0][slot, h],
                        bufs[2][slot, h] if quantized else None, scale)
                for h in range(kv_heads)]

    # The scores run a page ahead of the update: a page's two products
    # are one chain (q·kᵀ, its row max, exp, p·v), and its latency, not
    # the MXU's pushes, set the pace a page; with the next page's q·kᵀ
    # beside this page's update in one block the two chains overlap.
    # Item t + 1 is waited for while item t is attended over, so the
    # ring needs two slots (``depth`` >= 2).
    n_pages = live_pages(r)
    for c in copies(r, 0, cur[3] % depth):
        c.wait()

    start = first_page(r) if window else 0

    def attend(page, s):
        fetch()
        slot = cur[3] % depth
        last = page + 1 >= n_pages
        ahead = jnp.where(last, slot, (cur[3] + 1) % depth)

        @pl.when(jnp.logical_not(last))
        def _():
            for c in copies(r, page + 1, ahead):
                c.wait()

        s_ahead = scores(ahead)     # the row's last page: its own again
        for h in range(kv_heads):
            m_ref[h], l_ref[h], acc_ref[h] = _update(
                s[h], bufs[1][slot, h],
                bufs[3][slot, h] if quantized else None,
                m_ref[h], l_ref[h], acc_ref[h],
                (start + page if window else page) * page_size, pos,
                window)
        cur[3] = cur[3] + 1
        return s_ahead

    lax.fori_loop(0, n_pages, attend, scores(cur[3] % depth))
    o_ref[:] = acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)


# jitted: the layers of a program call it with one signature, so the outer
# trace lowers the kernel once
@functools.partial(jax.jit, static_argnums=(8, 9, 10, 11))
def _paged_call(q, k_pool, v_pool, table, pos, active, k_scale_pool,
                v_scale_pool, scale, depth, interpret, window=0):
    # ``pos`` (B,) a position a row, or (1,) one for all (B == 1: the same)
    B, H, D = q.shape
    _, Hkv, P, _ = k_pool.shape
    pages = table.shape[1]
    g = H // Hkv
    quantized = k_scale_pool is not None
    rows, count = live_rows(active, B)
    pools = [k_pool, v_pool]
    if quantized:
        # scales ride lane-major (1, page) rows (see _softmax_block)
        pools += [k_scale_pool, v_scale_pool]
    heads_of_row = pl.BlockSpec(
        (None, Hkv, g, D), lambda i, rows, pos, table: (rows[i], 0, 0, 0),
        memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_decode_kernel_paged, scale=scale, pages=pages,
                          depth=depth, quantized=quantized,
                          ragged=pos.shape[0] == B,
                          **({"window": window} if window else {})),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(count,),
            in_specs=[heads_of_row]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
            out_specs=heads_of_row,
            scratch_shapes=[pltpu.VMEM((depth,) + p.shape[1:], p.dtype)
                            for p in pools] + [
                pltpu.SemaphoreType.DMA((len(pools), depth)),
                pltpu.SMEM((4,), jnp.int32),       # the fetch cursor
                pltpu.VMEM((Hkv, g, 1), jnp.float32),   # running max
                pltpu.VMEM((Hkv, g, 1), jnp.float32),   # running sumexp
                pltpu.VMEM((Hkv, g, D), jnp.float32),   # accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="flash_decode_paged",
        interpret=interpret,
    )(rows, pos, table.reshape(-1).astype(jnp.int32),
      q.reshape(B, Hkv, g, D), *pools)
    out = out.reshape(B, H, D)
    if active is not None:   # the kernel left the idle rows unwritten
        out = jnp.where(active[:, None, None], out, 0.0)
    return out


def flash_decode_paged(
    q,
    k_pool,
    v_pool,
    table,
    pos,
    *,
    active=None,
    k_scale_pool=None,
    v_scale_pool=None,
    scale: float | None = None,
    pages_per_step: int | None = None,
    interpret: bool | None = None,
    window: int = 0,
):
    """Single-query attention against a PAGED KV cache.

    The block-table serving layout (vLLM-style, TPU-shaped): K/V live
    in a shared pool of fixed-size pages and each sequence owns an
    ordered page list — allocation follows ACTUAL generation length,
    not the declared maximum (the linear cache's
    allocate-for-the-longest waste is the round-3 capacity ceiling).
    The arithmetic is the linear ``flash_decode_attention``'s, one
    online-softmax update a page; the FETCHES follow the data: the
    kernel walks the rows that are live and, of each, the pages
    ``0 .. pos // page_size`` once each, copying them from the pools in
    HBM itself through the scalar-prefetched table, so pages can live
    ANYWHERE in the pool and a call's traffic is what its live rows
    attend over, whatever the engine's slots and pages a sequence.

    ``q``: (B, n_heads, head_dim); ``k_pool``/``v_pool``:
    (pool_pages, kv_heads, page_size, head_dim) in the compute dtype;
    ``table``: (B, pages_per_seq) int32 page ids (entries past the live
    prefix may be any id: they are never read); ``pos``: traced int32 —
    a scalar (batch-uniform position) or a (B,) vector of PER-SEQUENCE
    positions (ragged serving: every sequence at its own length).
    ``active``: (B,) bool, the rows to attend for, ``None`` for every
    row. A row where it does not hold is NOT VISITED (no page of it is
    fetched, whatever its position and table row say) and its output is
    zeros. Returns (B, n_heads, head_dim) f32, numerically identical to
    the linear kernel on the equivalent cache.

    ``k_scale_pool``/``v_scale_pool``: (pool_pages, kv_heads, 1,
    page_size) f32 per-row dequant scales for int8 pools — the linear
    kernel's half-the-HBM-bytes lever composed with the block table
    (the CAPACITY levers stack: int8 halves page bytes, paging frees
    the allocate-for-longest waste).

    ``pages_per_step``: the depth of the kernel's ring of page buffers
    (``pages_per_step`` x kv_heads x page bytes of VMEM for K and for
    V), at least 2: the page after the one attended over is waited for
    while it is attended over (its scores run a page ahead), and
    ``pages_per_step`` - 2 more are in flight. Default: the linear
    kernel's 2048-row streaming block in pages.

    ``window`` > 0 (static): a row attends over its keys
    ``pos - window < j <= pos`` alone, and only the pages they lie on,
    from ``max(0, pos - window + 1) // page_size`` to ``pos // page_size``,
    are fetched (a window layer's ring: the table sends each of those
    logical pages to its ring page).
    """
    B, H, D = q.shape
    n_pool, Hkv, P, Dp = k_pool.shape
    pages = table.shape[1]
    if H % Hkv or v_pool.shape != k_pool.shape or Dp != D:
        raise ValueError(
            f"shape mismatch: q {q.shape}, pools {k_pool.shape}/"
            f"{v_pool.shape}"
        )
    if table.shape[0] != B:
        raise ValueError(f"table rows {table.shape[0]} != batch {B}")
    ragged = jnp.ndim(pos) == 1
    if ragged and jnp.shape(pos)[0] != B:
        raise ValueError(
            f"ragged pos has {jnp.shape(pos)[0]} entries for batch {B}"
        )
    if active is not None and (jnp.shape(active) != (B,)
                               or active.dtype != jnp.bool_):
        raise ValueError(
            f"active {jnp.shape(active)} {active.dtype}; want ({B},) bool")
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if pages_per_step is None:
        # match the linear kernel's streaming block (block_s = 2048)
        pages_per_step = max(1, 2048 // P)
    return _paged_call(
        q, k_pool, v_pool, table,
        jnp.asarray(pos, jnp.int32).reshape(B if ragged else 1), active,
        k_scale_pool, v_scale_pool, float(scale),
        max(2, min(int(pages_per_step), pages)),
        resolve_interpret(interpret, "flash_decode_paged"),
        *((int(window),) if window else ()))


def fold_block(q, kv_heads: int):
    """q (B, c, n_heads, D) -> (B, kv_heads c g, D): a block's c positions
    folded into the group of each K/V head (q head k g + j of position i
    -> row k, group entry i g + j), for kernels that take one query a row
    and a group of them a K/V head."""
    B, c, H, D = q.shape
    g = H // kv_heads
    return jnp.einsum("bikjd->bkijd", q.reshape(B, c, kv_heads, g, D)
                      ).reshape(B, kv_heads * c * g, D)


def unfold_block(o, c: int, kv_heads: int):
    """:func:`fold_block`'s inverse on an attention output."""
    B, rows, D = o.shape
    g = rows // (kv_heads * c)
    return jnp.einsum("bkijd->bikjd", o.reshape(B, kv_heads, c, g, D)
                      ).reshape(B, c, kv_heads * g, D)


def flash_decode_paged_block(q, k_pool, v_pool, table, pos, **kw):
    """A BLOCK of query positions a sequence against the paged cache, all
    of them seeing the same keys: ``q`` (B, c, n_heads, head_dim), the
    block at positions ``pos .. pos + c - 1`` (``pos`` (B,) int32), whose
    own K/V rows are already in the pool; every query attends over keys
    ``0 .. pos + c - 1`` (generation by diffusion over blocks: a position
    sees all of its own block). The c positions fold into the kernel's
    group dimension, c x n_heads / kv_heads query rows over each K/V
    head, so this is ONE :func:`flash_decode_paged` call, which streams
    the keys once for all of them. Returns (B, c, n_heads, head_dim)
    float32; the other arguments are :func:`flash_decode_paged`'s."""
    c, Hkv = q.shape[1], k_pool.shape[1]
    o = flash_decode_paged(fold_block(q, Hkv), k_pool, v_pool, table,
                           pos + (c - 1), **kw)
    return unfold_block(o, c, Hkv)
