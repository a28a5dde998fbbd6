"""Ties the plain float32 reference (``bench/reference``) to the program on
the CPU, at a tiny width, and checks that its control, the same reference
with float8 matrix products, fails limits the program meets.  The reference,
the program's configuration and its parameter layout are the tiny
configuration's architecture module's (``bench/lib/arch.py``).

The program runs in float32 here, so any gap is summation order: the
tolerances are float32 round-off grown over a few thousand-term sums.
"""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import arch, serve, train, weights
from bench.reference import checks
from bench.tests.roots import SERVE, TINY_LIMITS, TRAIN
from bench.traffic import lm_tokens

DATA = pathlib.Path(__file__).resolve().parent / "data"
SEED = 5


@pytest.fixture(scope="module")
def tiny():
    c = json.loads((DATA / "tiny-moe.json").read_text())
    c32 = dict(c, torch_dtype="float32")
    return c, c32


def test_loss_and_gradients_match_the_program(tiny):
    from repro.parallel.sharding import make_plan
    from repro.train.train_step import loss_fn

    _, c = tiny
    model, ref = arch.of(c), arch.reference(c)
    w = weights.make(SEED, c)
    batch = next(lm_tokens.stream({"seq_len": 32, "batch": 2}, c, SEED))
    tokens, labels = jnp.asarray(batch.tokens), jnp.asarray(batch.labels)
    mcfg = model.model_config(c)

    def prog_loss(p):
        return loss_fn(p, {"tokens": tokens, "labels": labels}, mcfg, make_plan(None))[0]

    got_loss, got_grads = jax.jit(jax.value_and_grad(prog_loss))(model.to_program(w))
    want_loss, want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, tokens, labels, model.reference_view(c))))(w)
    # float32 on both sides; summation order differs (flash vs plain
    # softmax, grouped vs dense experts): 1e-5 of a loss near 5.
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-5)
    got = model.canonical(dict(zip(checks.leaf_paths(got_grads), jax.tree.leaves(got_grads))))
    for path, want in zip(checks.leaf_paths(want_grads), jax.tree.leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(want))) + 1e-12
        err = float(jnp.max(jnp.abs(got[path] - want))) / scale
        # Each gradient element sums over 64 positions and 2 layers of
        # products: 1e-4 of the leaf's largest element.
        assert err < 1e-4, (path, err)


def test_prefill_then_decode_logits_match_a_full_forward(tiny):
    from repro.models import transformer as tfm
    from repro.parallel.sharding import make_plan

    _, c = tiny
    model, ref = arch.of(c), arch.reference(c)
    w = weights.make(SEED, c)
    mcfg, plan = model.model_config(c), make_plan(None)
    params = model.to_program(w)
    rng = np.random.default_rng(SEED)
    prompt = rng.integers(0, c["vocab_size"], 12, dtype=np.int32)
    follow = rng.integers(0, c["vocab_size"], 6, dtype=np.int32)

    feats, _, caches = tfm.model_apply(params, {"tokens": jnp.asarray(prompt[None])},
                                       mcfg, plan, mode="prefill")
    got = [tfm.logits_from_features(params, feats, mcfg)[0, -1]]
    caches = tfm.pad_caches(caches, 32)
    for i, tok in enumerate(follow):
        feats, _, caches = tfm.model_apply(
            params, {"tokens": jnp.asarray([[tok]])}, mcfg, plan, mode="decode",
            caches=caches, t=jnp.asarray([len(prompt) + i], jnp.int32))
        got.append(tfm.logits_from_features(params, feats, mcfg)[0, -1])
    got = jnp.stack(got)

    seq = jnp.asarray(np.concatenate([prompt, follow])[None])
    with jax.default_matmul_precision("highest"):
        full = ref.logits(w, ref.features(w, seq, model.reference_view(c)))[0]
    want = full[len(prompt) - 1:]
    # float32 through the K/V cache and the dense-decode experts against
    # one full-sequence pass: 1e-4 of the largest logit.
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert err < 1e-4


def test_the_fp8_control_fails_the_training_limits(tiny):
    c, _ = tiny
    traffic = json.loads((DATA / "tiny-lm.json").read_text())
    view, ref = arch.of(c).reference_view(c), arch.reference(c)

    def readings(precision, against=None):
        r = checks.train_readings(ref, weights.make(SEED, c), lm_tokens.stream(traffic, c, SEED),
                                  view, c["optimizer"], steps=3, precision=precision,
                                  against=against, keep_first_moments=against is None)
        r["change_norms"] = checks.diff_norms(r.pop("params"), weights.make(SEED, c))
        return r

    control = readings("fp8")
    got = train.compare(control, readings("f32", against=control.pop("first_moments")))
    assert any(got[k] > limit for k, limit in TINY_LIMITS[TRAIN]["checks"].items()), got


@pytest.mark.parametrize("fault", sorted(checks.OPTIMIZER_FAULTS))
def test_an_optimizer_fault_in_the_reference_fails_the_training_limits(tiny, fault):
    c, _ = tiny
    traffic = json.loads((DATA / "tiny-lm.json").read_text())
    view, ref = arch.of(c).reference_view(c), arch.reference(c)

    def readings(fault=None):
        r = checks.train_readings(ref, weights.make(SEED, c), lm_tokens.stream(traffic, c, SEED),
                                  view, c["optimizer"], steps=3, fault=fault,
                                  keep_first_moments=True)
        r["change_norms"] = checks.diff_norms(r.pop("params"), weights.make(SEED, c))
        b1 = c["optimizer"]["b1"]
        r["grad_norms"] = {k: float(np.linalg.norm(m.astype(np.float32))) / (1 - b1)
                           for k, m in r.pop("first_moments").items()}
        return r

    got = train.compare(readings(fault), readings())
    limits = TINY_LIMITS[TRAIN]["checks"]
    assert any(got[k] is not None and got[k] > limit for k, limit in limits.items()), got


def test_the_fp8_control_fails_the_serving_limit(tiny):
    c, _ = tiny
    view, ref = arch.of(c).reference_view(c), arch.reference(c)
    frozen = tuple(sorted(view.items()))
    w = weights.make(SEED, c)
    rng = np.random.default_rng(SEED)
    sample = []
    for _ in range(4):
        seq = np.zeros((1, 64), np.int32)
        seq[0, :16] = rng.integers(0, c["vocab_size"], 16, dtype=np.int32)
        for n in range(16, 40):  # the reference's own greedy continuation
            top = checks._top(w, jnp.asarray(seq), ref=ref, cfg=frozen, precision="f32")
            seq[0, n] = int(top[n - 1])
        sample.append((seq[0, :16], list(seq[0, 16:40])))
    assert max(checks.serve_gaps(ref, w, sample, view, pad_to=64)) == 0.0
    limit = TINY_LIMITS[SERVE]
    fp8 = checks.serve_gaps(ref, w, sample, view, pad_to=64, precision="fp8")
    assert serve.far_share(fp8, limit["far_gap"]) > limit["checks"]["far_token_share"]
