"""The readings that decide ``correct``, on the reference's side.

Training: the reference takes the program's place for the first steps of
the same batches from the same weights, with AdamW as the configuration
states it (linear warm-up then cosine, global-norm clipping, decoupled
weight decay on every leaf).  All arithmetic is float32; the parameters and
moments are held in the dtypes the configuration stores them in, as the
program's state is.  They are rounded to those dtypes as each update's
output, and never as a float32 -> bfloat16 -> float32 round trip inside one
compiled program: the TPU's compiler may drop such a round trip and keep
the wider value.

Serving: the reference runs once over each sampled prompt followed by the
tokens the program served, and reads how far below its own best logit each
served token's logit lies.

The reference itself is the configuration's (``bench/lib/arch.py``
``reference``), passed in as ``ref``: a module with ``features``, ``logits``
and ``loss``.  Nothing here imports the system under test.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def leaf_paths(tree) -> list[str]:
    paths = []
    for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]:
        paths.append("/".join(str(getattr(k, "key", k)) for k in path))
    return paths


@jax.jit
def _norms(leaves):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(F32)))) for x in leaves]


@jax.jit
def _diff_norms(a, b):
    return [jnp.sqrt(jnp.sum(jnp.square(p.astype(F32) - q.astype(F32)))) for p, q in zip(a, b)]


def leaf_norms(tree) -> dict[str, float]:
    """Float32 2-norm of every leaf, by path."""
    return dict(zip(leaf_paths(tree), (float(n) for n in _norms(jax.tree.leaves(tree)))))


def diff_norms(a, b) -> dict[str, float]:
    """Float32 2-norm of ``a - b`` for every leaf, by path."""
    norms = _diff_norms(jax.tree.leaves(a), jax.tree.leaves(b))
    return dict(zip(leaf_paths(a), (float(n) for n in norms)))


def lr_at(opt: dict, step: int) -> float:
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    span = max(opt["total_steps"] - opt["warmup_steps"], 1)
    prog = min(max((step - opt["warmup_steps"]) / span, 0.0), 1.0)
    return opt["lr"] * warm * 0.5 * (1.0 + np.cos(np.pi * prog))


def _loss_mask(labels, fault):
    """All ones; the ``half_batch`` fault keeps the first half of the rows
    (of the positions, where the batch is one row)."""
    mask = jnp.ones(labels.shape, F32)
    if fault == "half_batch":
        b, s = labels.shape
        if b > 1:
            mask = mask.at[b // 2:].set(0.0)
        else:
            mask = mask.at[:, s // 2:].set(0.0)
    return mask


@functools.partial(jax.jit, static_argnames=("ref", "cfg", "precision", "fault"))
def _grads(params, tokens, labels, *, ref, cfg, precision, fault):
    """Loss, float32 gradient of every leaf (whatever dtype it is stored
    in), and their global norm."""
    mask = _loss_mask(labels, fault)
    loss, grads = jax.value_and_grad(ref.loss)(
        jax.tree.map(lambda a: a.astype(F32), params), tokens, labels, dict(cfg), precision,
        mask=mask)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    return loss, grads, gnorm


@functools.partial(jax.jit, static_argnames=("opt",), donate_argnums=(2, 3))
def _update(p, g, m, v, scale, count, lr, *, opt):
    """One AdamW update of one leaf, computed in float32 and returned in the
    leaf's storage dtype; the moments stay in the moment dtype.  Also the
    2-norm of the clipped gradient."""
    opt = dict(opt)
    g = g * scale
    p32 = p.astype(F32)
    m32 = opt["b1"] * m.astype(F32) + (1 - opt["b1"]) * g
    v32 = opt["b2"] * v.astype(F32) + (1 - opt["b2"]) * g * g
    mhat = m32 / (1.0 - opt["b1"] ** count)
    vhat = v32 / (1.0 - opt["b2"] ** count)
    delta = mhat / (jnp.sqrt(vhat) + opt["eps"]) + opt["weight_decay"] * p32
    new_p = (p32 - lr * delta).astype(p.dtype)
    return new_p, m32.astype(m.dtype), v32.astype(v.dtype), jnp.sqrt(jnp.sum(jnp.square(g)))


# Faults planted in the optimizer of the reference put in the program's
# place (``bench/controls.py``): a learning rate 20% low, and each moment's
# decay off the configuration's.
OPTIMIZER_FAULTS = {
    "lr_low": lambda o: dict(o, lr=0.8 * o["lr"]),
    "b1_low": lambda o: dict(o, b1=0.8),
    "b2_high": lambda o: dict(o, b2=0.99),
}


@jax.jit
def _gap_norm(first_moment, g, scale, b1):
    """2-norm of (a first gradient read back from Adam's first moment) minus
    the clipped gradient ``g * scale``."""
    return jnp.sqrt(jnp.sum(jnp.square(first_moment.astype(F32) / (1.0 - b1) - g * scale)))


def train_readings(ref, weights, batches, cfg: dict, opt: dict, *, steps: int,
                   precision: str = "f32", fault: str | None = None,
                   against: dict | None = None, keep_first_moments: bool = False) -> dict:
    """The reference ``ref``'s readings over its first ``steps`` updates.

    ``weights`` are the stored initial weights (consumed); ``batches``
    yields objects with ``tokens``/``labels``.  Returns ``losses`` (one per
    step), ``grad_norms`` (2-norm per leaf of the first step's clipped
    gradient), ``first_change_norms`` (2-norm per leaf of the first
    update's change), ``mu_norms``/``nu_norms`` (2-norm per leaf of Adam's
    moments after the last step), with ``keep_first_moments``
    ``first_moments`` (host copies, by leaf, of Adam's first moment after
    one step), and ``params`` (the stored parameters after ``steps``
    updates); with ``against`` (first moments by leaf from another run of
    the same step) also ``grad_diff_norms``, per leaf the 2-norm of that
    run's first gradient minus this one's.  ``fault`` is ``half_batch`` or
    one of ``OPTIMIZER_FAULTS``.  Leaves are held in their storage dtype,
    and the update runs one leaf at a time, so the gradient, the parameters
    and the moments fit one chip."""
    if fault in OPTIMIZER_FAULTS:
        opt, fault = OPTIMIZER_FAULTS[fault](opt), None
    paths = leaf_paths(weights)
    mdt = jnp.dtype(opt["moment_dtype"])
    flat, tree = jax.tree.flatten(weights)
    del weights
    mu = [jnp.zeros(a.shape, mdt) for a in flat]
    nu = [jnp.zeros(a.shape, mdt) for a in flat]
    frozen_cfg = tuple(sorted(cfg.items()))
    frozen_opt = tuple(sorted(opt.items()))
    losses = []
    for i in range(1, steps + 1):
        b = next(batches)
        loss, grads, gnorm = _grads(
            jax.tree.unflatten(tree, flat), jnp.asarray(b.tokens), jnp.asarray(b.labels),
            ref=ref, cfg=frozen_cfg, precision=precision, fault=fault,
        )
        scale = jnp.minimum(1.0, opt["clip_norm"] / (gnorm + 1e-9))
        grads = jax.tree.leaves(grads)
        count, lr = jnp.asarray(i, F32), jnp.asarray(lr_at(opt, i), F32)
        norms, changes, diffs = [], [], []
        for j in range(len(flat)):
            g, grads[j] = grads[j], None
            if i == 1 and against is not None:
                diffs.append(_gap_norm(jnp.asarray(against[paths[j]]), g, scale, opt["b1"]))
            old = flat[j]
            flat[j], mu[j], nu[j], n = _update(old, g, mu[j], nu[j], scale, count, lr,
                                               opt=frozen_opt)
            if i == 1:
                changes.append(_diff_norms([flat[j]], [old])[0])
            del g, old
            norms.append(n)
        losses.append(float(loss))
        if i == 1:
            out = {"grad_norms": dict(zip(paths, (float(x) for x in norms))),
                   "first_change_norms": dict(zip(paths, (float(x) for x in changes)))}
            if keep_first_moments:
                out["first_moments"] = {p: np.asarray(m) for p, m in zip(paths, mu)}
            if diffs:
                out["grad_diff_norms"] = dict(zip(paths, (float(x) for x in diffs)))
    out["mu_norms"] = dict(zip(paths, (float(x) for x in _norms(mu))))
    out["nu_norms"] = dict(zip(paths, (float(x) for x in _norms(nu))))
    del mu, nu
    out["losses"] = losses
    out["params"] = jax.tree.unflatten(tree, flat)
    return out


@functools.partial(jax.jit, static_argnames=("ref", "cfg", "precision"))
def _position_logits(weights, tokens, *, ref, cfg, precision):
    feats = ref.features(weights, tokens, dict(cfg), precision)
    return ref.logits(weights, feats, precision)[0]  # [S, V]


@functools.partial(jax.jit, static_argnames=("ref", "cfg"))
def _gaps(weights, tokens, targets, *, ref, cfg):
    lg = _position_logits(weights, tokens, ref=ref, cfg=cfg, precision="f32")
    best = jnp.max(lg, axis=-1)
    got = jnp.take_along_axis(lg, jnp.maximum(targets, 0)[:, None], axis=-1)[:, 0]
    return jnp.where(targets >= 0, best - got, 0.0)


@functools.partial(jax.jit, static_argnames=("ref", "cfg", "precision"))
def _top(weights, tokens, *, ref, cfg, precision):
    return jnp.argmax(_position_logits(weights, tokens, ref=ref, cfg=cfg, precision=precision),
                      -1)


def _padded(prompt, served, pad_to: int):
    seq = np.concatenate([np.asarray(prompt, np.int32), np.asarray(served[:-1], np.int32)])
    n = int(-(-len(seq) // pad_to) * pad_to)
    tokens = np.zeros((1, n), np.int32)
    tokens[0, : len(seq)] = seq
    targets = np.full((n,), -1, np.int32)
    targets[len(prompt) - 1 : len(seq)] = np.asarray(served, np.int32)
    return tokens, targets


def serve_gaps(ref, weights, sample, cfg: dict, *, pad_to: int,
               precision: str = "f32") -> list[float]:
    """Per served token: the reference ``ref``'s best logit minus its logit
    of the token.  ``sample`` is ``[(prompt, served tokens)]``.  With
    ``precision="fp8"`` the token read is the one the fp8 forward puts
    first at that position (the control), not the served one."""
    frozen = tuple(sorted(cfg.items()))
    out: list[float] = []
    for prompt, served in sample:
        tokens, targets = _padded(prompt, served, pad_to)
        if precision != "f32":
            top = np.asarray(_top(weights, jnp.asarray(tokens), ref=ref, cfg=frozen,
                                  precision=precision))
            # The token the control would emit at each served position: its
            # own first choice there, given the served prefix.
            targets = np.where(targets >= 0, top, -1).astype(np.int32)
        gaps = np.asarray(_gaps(weights, jnp.asarray(tokens), jnp.asarray(targets), ref=ref,
                                cfg=frozen))
        out.extend(float(g) for g in gaps[targets >= 0])
    return out
