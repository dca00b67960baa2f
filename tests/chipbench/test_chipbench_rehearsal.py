"""Each driver end to end at a tiny configuration on the CPU (Pallas in
interpret mode, four virtual devices for the allreduce), the comparison
included: the rest of a run after the look for a chip. Then the same with
the timed path broken underneath, and with the control in the program's
place: ``correct`` has to come out false.
"""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import run as runlib  # noqa: E402

FIX = "tests/chipbench/fixtures"
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
         "ici_bytes_per_s": 2e11}
E2E = [("ttft_p95_ms", "ms", ["tiny-serve"]), ("tpot_p95_ms", "ms", ["tiny-serve"]),
       ("train_tok_s", "tokens/s", ["tiny-train"]),
       ("allreduce_busbw", "GB/s", ["tiny-allreduce"]), ("setup_s", "s", None)]
BENCH = {
    "workloads": [
        {"name": n, "config": c, "traffic": "x", "chips": k,
         "file": f"{FIX}/{n}.json"}
        for n, c, k in (("tiny-serve", "tiny-lm", 1),
                        ("tiny-train", "tiny-lm", 1),
                        ("tiny-allreduce", "tiny-allreduce-cfg", 4))],
    "configs": [{"name": c, "file": f"{FIX}/{c}.json"}
                for c in ("tiny-lm", "tiny-allreduce-cfg")],
    "end_to_end": [dict({"name": n, "unit": u},
                        **({"workloads": w} if w else {}))
                   for n, u, w in E2E],
    "per_layer": [],
}


def drive(cell, seed=2**31 + 7, control=None, seconds=0.5):
    n = next(w["chips"] for w in BENCH["workloads"] if w["name"] == cell)
    return runlib.run_cell(BENCH, cell, seed, seconds, False,
                           jax.devices()[:n], PEAKS, control=control)


@pytest.mark.parametrize("cell,metrics", [
    ("tiny-serve", {"ttft_p95_ms", "tpot_p95_ms", "setup_s"}),
    ("tiny-train", {"train_tok_s", "setup_s"}),
    ("tiny-allreduce", {"allreduce_busbw", "setup_s"}),
])
def test_driver_runs_end_to_end_and_proves_correct(cell, metrics):
    r = drive(cell)
    assert r["correct"] is True, r["checks"]
    assert set(r["metrics"]) == metrics
    assert r["attempted"] > 0 and r["failed"] == 0
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert list(r)[-1] == "checks"   # each number beside its limit, last
    json.dumps(r)


@pytest.mark.parametrize("cell,control", [
    ("tiny-train", "half-batch"),
    ("tiny-allreduce", "bfloat16"),
])
def test_control_in_the_programs_place_is_not_correct(cell, control):
    assert drive(cell, control=control)["correct"] is False


def test_serve_token_altered_where_it_is_produced(monkeypatch):
    from hpc_patterns_tpu.models import serving
    real = serving._chunk_step

    def altered(*a, **kw):
        *state, out = real(*a, **kw)
        return (*state, (out + 1) % kw["cfg"].vocab)

    monkeypatch.setattr(serving, "_chunk_step", altered)
    r = drive("tiny-serve")
    assert r["correct"] is False
    assert r["checks"]["served_logit_gap"]["value"] > \
        r["checks"]["served_logit_gap"]["limit"]


def test_serve_request_that_never_comes_is_not_correct(monkeypatch):
    from chipbench.drivers import serve
    real = serve.serve_window

    def lossy(engine, requests, tracer=None):
        finished, t0, t1 = real(engine, requests, tracer)
        finished.pop(next(r.index for r in requests if r.measured))
        return finished, t0, t1

    monkeypatch.setattr(serve, "serve_window", lossy)
    r = drive("tiny-serve")
    assert r["correct"] is False and r["failed"] == 1


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_train_faults_are_not_correct(monkeypatch, fault):
    from chipbench.drivers import train
    real = train.make_train_step

    def broken(cfg, optimizer=None):
        step = real(cfg, optimizer=optimizer)

        def unchanged(params, opt_state, tokens):
            # the loss of a real step, the state handed back as it came
            keep = jax.tree.map(jnp.copy, (params, opt_state))
            loss, _, _ = step(params, opt_state, tokens)
            return (loss, *keep)

        def half(params, opt_state, tokens):
            return step(params, opt_state, tokens[:tokens.shape[0] // 2])

        return unchanged if fault == "state_unchanged" else half

    monkeypatch.setattr(train, "make_train_step", broken)
    r = drive("tiny-train")
    assert r["correct"] is False
    over = [n for n, c in r["checks"].items() if c["value"] > c["limit"]]
    assert over, r["checks"]


@pytest.mark.parametrize("fault", ["no_exchange", "answer_altered"])
def test_allreduce_faults_are_not_correct(monkeypatch, fault):
    from hpc_patterns_tpu.comm.communicator import Communicator
    real = Communicator.jit_allreduce

    def broken(self, x, algorithm="collective"):
        fn = real(self, x, algorithm)
        if fault == "no_exchange":   # every rank keeps its own buffer
            return lambda v: v + 0
        return lambda v: fn(v).at[0, 0].add(1.0)

    monkeypatch.setattr(Communicator, "jit_allreduce", broken)
    r = drive("tiny-allreduce")
    assert r["correct"] is False
    assert r["checks"]["wrong_elements"]["value"] > 0


def test_reference_block_agrees_with_the_programs_forward():
    # the plain reference and the program's float32 "full" forward are
    # the same equations: logits agree to float32 rounding on the CPU
    import numpy as np
    from chipbench import weights
    from chipbench.reference import transformer as ref
    from hpc_patterns_tpu.models import transformer as prog
    config = json.loads((ROOT / FIX / "tiny-lm.json").read_text())
    m = weights.model_dims(config)
    cfg = prog.TransformerConfig(
        vocab=m["V"], d_model=m["D"], n_heads=m["H"], n_layers=m["L"],
        d_ff=m["F"], n_kv_heads=m["Hkv"], max_seq=64, dtype="float32",
        attention="full", pos_embed="rope", rope_theta=m["theta"])
    params = weights.build(weights.seed_key(5), m)
    tokens = np.arange(64, dtype=np.int32) * 7 % m["V"]
    with jax.default_matmul_precision("highest"):
        want = prog.forward(params, jnp.asarray(tokens)[None], cfg)[0]
    got = ref.logits_at(5, m, [tokens], [np.arange(64)], pad_to=64)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)
