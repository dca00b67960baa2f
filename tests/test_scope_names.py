"""Kernel names and program phases (ops/*, models/*): ``name=`` on every
``pallas_call`` and ``jax.named_scope`` at the phase boundaries of the
three programs the benchmark's cells run show in the lowered text's
locations, and nowhere else: the text without locations is that of the
same function lowered with the scopes taken out."""

import contextlib
import dataclasses
import re

import pytest

import jax
import jax.numpy as jnp

from hpc_patterns_tpu.models import TransformerConfig, init_params
from hpc_patterns_tpu.models import decode, serving
from hpc_patterns_tpu.models import train as trainlib

T, PAGE, SLOTS, PAGES = 128, 128, 2, 2
CFG = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
                        n_kv_heads=2, max_seq=2 * T, dtype="float32",
                        attention="flash", pos_embed="rope",
                        decode_attn="flash")


def _shapes(tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        tree)


def _params():
    return _shapes(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), CFG)))


def _cache(batch):
    return _shapes(jax.eval_shape(lambda: decode.init_paged_cache(
        CFG, batch, pages_per_seq=PAGES, page_size=PAGE,
        pool_pages=SLOTS * PAGES)))


def i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def lower_prefill():
    one = dict(_cache(SLOTS), table=i32(1, PAGES))
    return serving._prefill_one.lower(
        _params(), i32(1, T), i32(), one, cfg=CFG, page_size=PAGE, mesh=None)


def lower_chunk():
    return serving._chunk_step.lower(
        _params(), _cache(SLOTS), i32(SLOTS), i32(SLOTS), i32(SLOTS),
        jax.ShapeDtypeStruct((SLOTS, 2), jnp.uint32),
        jax.ShapeDtypeStruct((SLOTS,), jnp.float32),
        cfg=CFG, chunk=2, eos_id=-1, greedy=True, top_k=0, mesh=None)


def lower_train():
    cfg = dataclasses.replace(CFG, remat=True, remat_policy="split",
                              loss_chunk=32, max_seq=T)
    optimizer = trainlib.make_optimizer(3e-4, 0.01, 1.0)
    step = trainlib.make_train_step(cfg, optimizer=optimizer)
    params = _params()
    return step.__wrapped__.lower(
        params, _shapes(jax.eval_shape(optimizer.init, params)), i32(2, T))


PROGRAMS = {
    "prefill": (lower_prefill, ["flash_fwd"],
                ["embed", "attn", "mlp", "head", "kv_write"]),
    "chunk": (lower_chunk, ["flash_decode_paged"],
              ["embed", "attn", "mlp", "head", "sample", "kv_write"]),
    "train": (lower_train, ["flash_fwd", "flash_bwd_fused"],
              ["embed", "attn", "mlp", "head", "loss", "update"]),
}


@pytest.fixture(scope="module")
def lowered():
    return {k: fn() for k, (fn, _, _) in PROGRAMS.items()}


def _located(text: str, name: str) -> bool:
    """``name`` is a part of some operation's framework path, bare or
    inside a transformation's wrapper (``transpose(jvp(attn))``)."""
    return any(name == re.sub(r"^(?:\w+\()+|\)+$", "", part)
               for path in re.findall(r'loc\("([^"]*)"', text)
               for part in path.split("/"))


@pytest.mark.parametrize("program,name", [
    (p, n) for p, (_, kernels, scopes) in PROGRAMS.items()
    for n in kernels + scopes])
def test_name_shows_in_the_lowered_metadata(lowered, program, name):
    text = lowered[program].as_text(debug_info=True)
    assert _located(text, name), (program, name)
    assert not _located(lowered[program].as_text(), name)


@pytest.mark.parametrize("program", list(PROGRAMS))
def test_scopes_are_metadata_not_program(lowered, program, monkeypatch):
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()   # or the traced jaxpr comes back, scopes and all
    bare = PROGRAMS[program][0]()
    scopes = PROGRAMS[program][2]
    assert not any(_located(bare.as_text(debug_info=True), s)
                   for s in scopes)
    assert bare.as_text() == lowered[program].as_text()
