"""Kernels of the serving path compiled at their real widths for a TPU v5e
that is described, not attached: what the chip's compiler refuses (a tile
it cannot lay out, more VMEM than a kernel may take) fails here, at no
chip time. Nothing runs, so nothing here is a time or a result.

Keep every such test in THIS file: the process that describes the
topology holds the TPU's library until it exits, so a second file on
another worker could not (and would skip in silence).
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax import lax
from jax.sharding import SingleDeviceSharding

from hpc_patterns_tpu.models.decode import _pool_write
from hpc_patterns_tpu.ops.flash_attention import flash_attention
from hpc_patterns_tpu.ops.flash_decode import (flash_decode_paged,
                                               flash_decode_paged_block)
from hpc_patterns_tpu.ops.grouped_matmul import grouped_matmul
from hpc_patterns_tpu.ops.ssm_step import ssm_step
from hpc_patterns_tpu.parallel.moe import relu2, silu


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to guard
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: the next run would warn."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# nemotron3-super-ep4: 128 held experts, latent 1024, expert width 2688,
# 22 picks a token; a decode step of 64 slots, prefills of 512 and 4096
@pytest.mark.parametrize("rows", [64 * 22, 512 * 22, 4096 * 22])
@pytest.mark.parametrize("product", ["first", "second"])
def test_grouped_matmul_compiles_at_the_held_experts_widths(
        one_chip, no_compile_cache, rows, product):
    k, n = (1024, 2688) if product == "first" else (2688, 1024)
    kw = ({"activation": relu2} if product == "first"
          else {"preferred_element_type": jnp.float32})
    shape = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    compiled = jax.jit(
        lambda a, b, s: grouped_matmul(a, b, s, interpret=False, **kw)
    ).lower(shape((rows, k), jnp.bfloat16), shape((128, k, n), jnp.bfloat16),
            shape((128,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "grouped_matmul" in text


def test_the_kernel_carries_its_callers_scope(one_chip, no_compile_cache):
    """``moe_prefill_ms`` / ``moe_decode_ms_chunk`` read device time by
    ``jax.named_scope`` path: the kernel's wrapper is jitted (one Mosaic
    lowering a program, not one a layer), and the compiled call must
    still say under which scope it ran."""
    def layer(a, b, s):
        with jax.named_scope("moe"), jax.named_scope("experts"):
            return grouped_matmul(a, b, s, interpret=False)

    shape = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    text = jax.jit(layer).lower(
        shape((256, 128), jnp.bfloat16), shape((4, 128, 256), jnp.bfloat16),
        shape((4,), jnp.int32)).compile().as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert calls and all(
        re.search(r'op_name="[^"]*moe/experts/[^"]*grouped_matmul', line)
        and re.search(r"%grouped_matmul[.\d]* = ", line) for line in calls)


# nemotron3-super-ep4: 64 slots of 128 heads x 64 x 128 float32, 8 groups
@pytest.mark.parametrize("active", ["given", "none"])
def test_ssm_step_compiles_in_place_under_its_callers_scope(
        one_chip, no_compile_cache, active):
    """``ssm_step_decode_roofline`` reads device time under ``ssm/step``:
    the compiled call must say it ran there, and the donated state must
    come back aliased, with no copy or select of it beside the kernel."""
    def layer(S, x, dt, A, B, C, a=None):
        with jax.named_scope("ssm"), jax.named_scope("step"):
            return ssm_step(S, x, dt, A, B, C, a, interpret=False)

    shape = lambda *s, dt=jnp.float32: jax.ShapeDtypeStruct(
        s, dt, sharding=one_chip)
    args = [shape(64, 128, 64, 128), shape(64, 128, 64), shape(64, 128),
            shape(128), shape(64, 8, 128), shape(64, 8, 128)]
    if active == "given":
        args.append(shape(64, dt=jnp.bool_))
    compiled = jax.jit(layer, donate_argnums=(0,)).lower(*args).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 1
    assert re.search(r'op_name="[^"]*ssm/step/[^"]*ssm_step', calls[0])
    assert re.search(r"%ssm_step[.\d]* = ", calls[0])
    state = 64 * 128 * 64 * 128 * 4
    assert compiled.memory_analysis().alias_size_in_bytes == state
    whole = r"= f32\[64,128,64,128\]\S* (copy|select|fusion)\("
    assert not [line for line in text.splitlines() if re.search(whole, line)]


# falcon-h1-34b-stage: 32 slots of 32 heads x 128 x 256 float32, 2 groups.
# A head's slab of S is 128 KiB, so 8 heads make the kernel's 1 MiB tile
# (serve-chat's: 32 heads of 32 KiB)
def test_ssm_step_compiles_at_state_256_head_128_in_place(
        one_chip, no_compile_cache):
    def layer(S, x, dt, A, B, C, a):
        with jax.named_scope("ssm"), jax.named_scope("step"):
            return ssm_step(S, x, dt, A, B, C, a, interpret=False)

    shape = lambda *s, dt=jnp.float32: jax.ShapeDtypeStruct(
        s, dt, sharding=one_chip)
    args = [shape(32, 32, 128, 256), shape(32, 32, 128), shape(32, 32),
            shape(32), shape(32, 2, 256), shape(32, 2, 256),
            shape(32, dt=jnp.bool_)]
    compiled = jax.jit(layer, donate_argnums=(0,)).lower(*args).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == 1
    assert re.search(r'op_name="[^"]*ssm/step/[^"]*ssm_step', calls[0])
    assert compiled.memory_analysis().alias_size_in_bytes \
        == 32 * 32 * 128 * 256 * 4


# falcon-h1-34b-stage: 20 query heads on 4 K/V heads of 128, a group of 5
# (the accepted cells have 12 and 16): a prefill rung, and a decode step
# of 32 slots against 513 pages of 256 positions
@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_decode_paged"])
def test_attention_kernels_compile_at_a_group_of_five(
        one_chip, no_compile_cache, kernel):
    shape = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        s, dt, sharding=one_chip)
    if kernel == "flash_fwd":
        fn = lambda q, k, v: flash_attention(q, k, v, causal=True,
                                             interpret=False)
        args = [shape(1, 2048, 20, 128), shape(1, 2048, 4, 128),
                shape(1, 2048, 4, 128)]
    else:
        fn = lambda q, kp, vp, table, pos: flash_decode_paged(
            q, kp, vp, table, pos, scale=128 ** -0.5, interpret=False)
        args = [shape(32, 20, 128), shape(513, 4, 256, 128),
                shape(513, 4, 256, 128), shape(32, 16, dt=jnp.int32),
                shape(32, dt=jnp.int32)]
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 1 and re.search(rf"%{kernel}[.\d]* = ", calls[0])


#: ``reduce-window`` operations of ONE ``jnp.nonzero`` over the slots in a
#: compiled program (its running sum and its count), however many layers
#: call the kernel with the same ``active``
LIVE_LIST_SUMS = 2


# the five cells' decode calls (pool, slots, query heads; a block of 4
# folded where the model generates by diffusion), each with a traced
# ``active``: serve-code, serve-gen, serve-chat, serve-assist, serve-diffuse
@pytest.mark.parametrize("pool,slots,q_heads,block", [
    ((161, 2, 256, 128), 32, 24, 0),
    ((385, 2, 256, 128), 32, 24, 0),
    ((1025, 2, 256, 128), 64, 32, 0),
    ((513, 4, 256, 128), 32, 20, 0),
    ((1025, 4, 256, 128), 64, 32, 4),
])
def test_flash_decode_paged_compiles_over_the_live_rows_at_the_cells_shapes(
        one_chip, no_compile_cache, pool, slots, q_heads, block):
    """One kernel under its caller's scope (the four
    ``*flash_decode_paged_roofline`` find it by name, ``*_attn_decode_ms_chunk``
    by ``attn``), its ring of page buffers within the VMEM a kernel may
    take, the pools handed over where they lie (no copy of one)."""
    def layer(q, kp, vp, table, pos, active):
        kernel = flash_decode_paged_block if block else flash_decode_paged
        with jax.named_scope("attn"):
            return kernel(q, kp, vp, table, pos, active=active,
                          scale=128 ** -0.5, interpret=False)

    shape = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        s, dt, sharding=one_chip)
    q = (shape(slots, block, q_heads, 128) if block
         else shape(slots, q_heads, 128))
    text = jax.jit(layer).lower(
        q, shape(*pool), shape(*pool), shape(slots, 16, dt=jnp.int32),
        shape(slots, dt=jnp.int32), shape(slots, dt=jnp.bool_)
    ).compile().as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 1
    assert re.search(r"%flash_decode_paged[.\d]* = ", calls[0])
    assert re.search(r'op_name="[^"]*attn/[^"]*flash_decode_paged', calls[0])
    assert not re.search(r"= bf16\[{},{},{},{}\]\S* copy\(".format(*pool),
                         text)
    assert len(re.findall(r" reduce-window\(", text)) == LIVE_LIST_SUMS


# the two other forms of the page's arithmetic (ops/flash_decode._exact_dot):
# int8 pools, their pages cast to bfloat16 and their per-row scales copied
# beside them, at serve-gen's and serve-assist's shapes; and a float32 q,
# whose three bfloat16 pieces are stacked into one product
@pytest.mark.parametrize("pool,slots,q_heads,pool_dtype,q_dtype", [
    ((385, 2, 256, 128), 32, 24, jnp.int8, jnp.bfloat16),
    ((513, 4, 256, 128), 32, 20, jnp.int8, jnp.bfloat16),
    ((385, 2, 256, 128), 32, 24, jnp.bfloat16, jnp.float32),
])
def test_flash_decode_paged_compiles_on_int8_pools_and_a_float32_q(
        one_chip, no_compile_cache, pool, slots, q_heads, pool_dtype,
        q_dtype):
    shape = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        s, dt, sharding=one_chip)
    scales = ([shape(pool[0], pool[1], 1, pool[2], dt=jnp.float32)] * 2
              if pool_dtype == jnp.int8 else [None, None])

    def layer(q, kp, vp, table, pos, active, ks, vs):
        return flash_decode_paged(q, kp, vp, table, pos, active=active,
                                  k_scale_pool=ks, v_scale_pool=vs,
                                  scale=128 ** -0.5, interpret=False)

    text = jax.jit(layer).lower(
        shape(slots, q_heads, 128, dt=q_dtype), shape(*pool, dt=pool_dtype),
        shape(*pool, dt=pool_dtype), shape(slots, 16, dt=jnp.int32),
        shape(slots, dt=jnp.int32), shape(slots, dt=jnp.bool_), *scales
    ).compile().as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 1
    assert re.search(r"%flash_decode_paged[.\d]* = ", calls[0])


# the serving cells' K/V pools (pages + the trash page, K/V heads, page
# 256, head 128) with their slots and query heads: serve-assist, serve-gen
# (serve-code's pool is the same with 161 pages), serve-chat
@pytest.mark.parametrize("pool,slots,q_heads", [
    ((513, 4, 256, 128), 32, 20),
    ((385, 2, 256, 128), 32, 24),
    ((1025, 2, 256, 128), 64, 32),
])
def test_a_decode_steps_kv_row_lands_in_its_pool_in_place(
        one_chip, no_compile_cache, pool, slots, q_heads):
    """A chunk's loop of write-then-attend over donated pools: the scatter
    of ``decode._pool_write`` must come out in the layout
    ``flash_decode_paged`` reads, so that no step copies a whole pool
    (12 copies of 134 MB a step in serve-assist before PR 31)."""
    def chunk(pools, q, new, table, pos, limit):
        def step(carry, _):
            pools, pos, acc = carry
            active = pos < limit          # as serving._chunk_step has it
            page_ids = jnp.take_along_axis(
                table, (pos // 256)[:, None], axis=1)[:, 0]
            out = []
            for k_pool, v_pool in pools:
                k_pool = _pool_write(k_pool, page_ids, None, pos % 256, new,
                                     16, False)
                v_pool = _pool_write(v_pool, page_ids, None, pos % 256, new,
                                     16, False)
                acc = acc + flash_decode_paged(
                    q, k_pool, v_pool, table, pos, active=active,
                    scale=128 ** -0.5, interpret=False)
                out.append((k_pool, v_pool))
            return (tuple(out), jnp.where(active, pos + 1, pos), acc), None

        acc = jnp.zeros(q.shape, jnp.float32)
        (pools, _, acc), _ = lax.scan(step, (pools, pos, acc), None,
                                      length=8)
        return pools, acc

    shape = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        s, dt, sharding=one_chip)
    pools = tuple((shape(*pool), shape(*pool)) for _ in range(2))
    compiled = jax.jit(chunk, donate_argnums=(0,)).lower(
        pools, shape(slots, q_heads, 128), shape(slots, pool[1], 128),
        shape(slots, 16, dt=jnp.int32), shape(slots, dt=jnp.int32),
        shape(slots, dt=jnp.int32)).compile()
    whole = r"= bf16\[{},{},{},{}\]\S* copy\(".format(*pool)
    text = compiled.as_text()
    assert len(re.findall(r"%flash_decode_paged[.\d]* = ", text)) == 2
    assert not [line for line in text.splitlines() if re.search(whole, line)]
    # the list of live rows is made once a step, not once a layer: the two
    # layers' ``nonzero`` (a running sum and two scatters) became one
    assert len(re.findall(r" reduce-window\(", text)) == LIVE_LIST_SUMS
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 64 << 20
    assert memory.alias_size_in_bytes >= 4 * 2 * pool[0] * pool[1] * 256 * 128


# sdar-30b-a3b-stage: 32 query heads on 4 K/V heads of 128; a prefill rung
# under the block mask of 4, and a block step of 64 slots: 4 positions x a
# group of 8 = 32 query rows over each K/V head, 1025 pages of 256
@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_decode_paged"])
def test_attention_kernels_compile_under_the_block_mask(
        one_chip, no_compile_cache, kernel):
    shape = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        s, dt, sharding=one_chip)
    if kernel == "flash_fwd":
        fn = lambda q, k, v: flash_attention(q, k, v, mask_block=4,
                                             interpret=False)
        args = [shape(1, 2048, 32, 128), shape(1, 2048, 4, 128),
                shape(1, 2048, 4, 128)]
    else:
        fn = lambda q, kp, vp, table, pos: flash_decode_paged_block(
            q, kp, vp, table, pos, scale=128 ** -0.5, interpret=False)
        args = [shape(64, 4, 32, 128), shape(1025, 4, 256, 128),
                shape(1025, 4, 256, 128), shape(64, 16, dt=jnp.int32),
                shape(64, dt=jnp.int32)]
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 1 and re.search(rf"%{kernel}[.\d]* = ", calls[0])


# sdar-30b-a3b-stage: 128 experts of 2048 -> 768 -> 2048, 8 picks a token;
# a block forward of 64 slots x 4 positions, prefills of 512 and 4096
@pytest.mark.parametrize("rows", [64 * 4 * 8, 512 * 8, 4096 * 8])
@pytest.mark.parametrize("product", ["gate", "down"])
def test_grouped_matmul_compiles_at_the_gated_experts_widths(
        one_chip, no_compile_cache, rows, product):
    k, n = (2048, 768) if product == "gate" else (768, 2048)
    kw = ({"activation": silu} if product == "gate"
          else {"preferred_element_type": jnp.float32})
    shape = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    text = jax.jit(
        lambda a, b, s: grouped_matmul(a, b, s, interpret=False, **kw)
    ).lower(shape((rows, k), jnp.bfloat16), shape((128, k, n), jnp.bfloat16),
            shape((128,), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in text and "grouped_matmul" in text


def test_a_block_steps_kv_rows_land_in_their_pool_in_place(
        one_chip, no_compile_cache):
    """A block chunk's loop of write-then-attend over donated pools, 64
    slots x 4 positions a forward, idle rows' page ids past the pool: the
    scatter must stay in the kernel's layout (no whole-pool copy), as a
    decode step's does."""
    pool = (1025, 4, 256, 128)

    def chunk(pools, q, new, table, pos, active):
        def step(carry, _):
            pools, pos, acc = carry
            at = pos[:, None] + jnp.arange(4, dtype=jnp.int32)
            ids = jnp.take_along_axis(table, at // 256, axis=1)
            ids = jnp.where(active[:, None], ids, pool[0]).reshape(-1)
            out = []
            for k_pool, v_pool in pools:
                k_pool = _pool_write(k_pool, ids, None, (at % 256).reshape(-1),
                                     new, 16, False)
                v_pool = _pool_write(v_pool, ids, None, (at % 256).reshape(-1),
                                     new, 16, False)
                acc = acc + flash_decode_paged_block(
                    q, k_pool, v_pool, table, pos, active=active,
                    scale=128 ** -0.5, interpret=False)
                out.append((k_pool, v_pool))
            return (tuple(out), pos + 4, acc), None

        acc = jnp.zeros(q.shape, jnp.float32)
        (pools, _, acc), _ = lax.scan(step, (pools, pos, acc), None,
                                      length=6)
        return pools, acc

    shape = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        s, dt, sharding=one_chip)
    pools = tuple((shape(*pool), shape(*pool)) for _ in range(2))
    compiled = jax.jit(chunk, donate_argnums=(0,)).lower(
        pools, shape(64, 4, 32, 128), shape(256, 4, 128),
        shape(64, 16, dt=jnp.int32), shape(64, dt=jnp.int32),
        shape(64, dt=jnp.bool_)).compile()
    whole = r"= bf16\[{},{},{},{}\]\S* copy\(".format(*pool)
    text = compiled.as_text()
    assert len(re.findall(r"%flash_decode_paged[.\d]* = ", text)) == 2
    assert not [line for line in text.splitlines() if re.search(whole, line)]
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 64 << 20
    assert memory.alias_size_in_bytes >= 4 * 2 * pool[0] * pool[1] * 256 * 128
