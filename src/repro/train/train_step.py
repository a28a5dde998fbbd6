"""jit-able training / serving step factories.

These are the functions the launcher pjits and the dry-run lowers: pure
(params, opt_state, batch) -> (params, opt_state, metrics) with all
distribution expressed through param/activation shardings (plus the MoE
``mixnet`` shard_map region inside the model).

Gradient reduction has two modes (``dp_comm``): ``"auto"`` leaves the DP
reduction to XLA's sharding propagation (the pjit baseline), while
``"runtime"`` computes per-shard gradients inside an explicit ``shard_map``
over the batch axes and reduces them through the CommRuntime's hierarchical
:class:`~repro.core.commruntime.AllReduce` (reduce-scatter inside the
region, ring across regions, all-gather back — paper §5.3).  The runtime
mode requires a DP-only mesh (no model axis) with FSDP disabled
(``make_plan(mesh, fsdp=False)`` — params ride the shard_map replicated)
and evaluates the MoE aux losses per shard (averaged), the standard
per-group GShard semantics.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.commruntime import AllReduce, CommSpec
from repro.models import transformer as tfm
from repro.optim.adamw import AdamWConfig, adamw_update, init_adamw
from repro.parallel.sharding import ShardingPlan, constrain

__all__ = [
    "make_train_step",
    "make_prefill_step",
    "make_prefill_chunk_step",
    "make_serve_step",
    "make_verify_step",
    "make_draft_loop_step",
    "init_ef_residual",
    "loss_fn",
    "step_shardings",
]


def _sample_rows(logits, keys):
    """Per-row Gumbel-argmax sample: ``[B, V]`` logits × ``[B, 2]`` keys.

    Equivalent to ``categorical`` per row, but each row consumes its OWN key
    — the serving engine's sampling-key discipline (DESIGN.md §11): one key
    per (slot, emitted-token index), so speculative and serial decode draw
    the same token from the same logits, and draft passes can reuse the
    verify keys (common random numbers) for free extra acceptance.
    """
    l32 = logits.astype(jnp.float32)
    g = jax.vmap(lambda k: jax.random.gumbel(k, l32.shape[-1:]))(keys)
    return jnp.argmax(l32 + g, axis=-1)


def loss_fn(params, batch, cfg, plan, mesh=None, expert_perm=None, wire_perm=None):
    """``expert_perm``: ``[repeats, E_virtual]`` per-layer expert->slot maps
    from the control plane (distinct rows per layer after regional
    reconfiguration); the transformer scan slices one row per repeat.
    ``wire_perm``: optional ``[repeats, P]`` device maps for layers whose
    plan was installed as a wire re-address instead of a weight gather."""
    feats, aux, _ = tfm.model_apply(
        params, batch, cfg, plan, mesh=mesh, mode="train", expert_perm=expert_perm,
        wire_perm=wire_perm,
    )
    feats = constrain(feats, mesh, plan.activation_spec())
    ce = tfm.chunked_cross_entropy(params, feats, batch["labels"], cfg)
    loss = ce
    if cfg.is_moe:
        loss = loss + cfg.moe.balance_loss * aux.balance_loss
        loss = loss + cfg.moe.router_z_loss * aux.z_loss
    return loss, (ce, aux)


def init_ef_residual(params, plan: ShardingPlan):
    """Per-shard error-feedback residuals for ``dp_compress=True``: one f32
    copy of every gradient leaf per DP shard, leading dim = the DP degree
    (sharded over the batch axes inside the runtime shard_map)."""
    d = max(plan.data_size, 1)
    return jax.tree.map(
        lambda p: jnp.zeros((d, *p.shape), jnp.float32), params
    )


def _make_runtime_grad_fn(cfg, plan: ShardingPlan, mesh, compress: bool = False):
    """Per-shard gradients inside shard_map over the batch axes, reduced with
    the CommRuntime hierarchical AllReduce (``dp_comm="runtime"``).

    ``compress=True`` routes the reduction through the int8 codec
    (:mod:`repro.optim.compress`) riding the op's reduce-scatter stage, with
    per-shard error-feedback residuals threaded by the caller — quantization
    noise does not accumulate across steps, and the wire bytes drop by the
    gradient dtype's width (the same ``compress_ratio`` netsim prices)."""
    if mesh is None or not plan.batch_axes or plan.model_size > 1:
        raise ValueError(
            "dp_comm='runtime' requires a data-parallel mesh without a model "
            f"axis (got mesh={mesh is not None}, plan={plan})"
        )
    if plan.fsdp_axis is not None:
        # Params enter the shard_map replicated (in_specs P()) and the full
        # gradient tree leaves it replicated — ZeRO-3 sharding would be
        # silently gathered away.  Fail loudly instead of OOMing at scale.
        raise ValueError(
            "dp_comm='runtime' replicates parameters inside the shard_map and "
            "is incompatible with FSDP sharding; build the plan with "
            "make_plan(mesh, fsdp=False)"
        )
    local_plan = ShardingPlan((), None, 1, None, 1)
    reduce_op = AllReduce(CommSpec.for_grad_reduce(plan, mesh))
    tok_spec = P(plan.batch_axes, None)

    def body(params, tokens, labels, expert_perm, residual):
        (loss, (ce, aux)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, {"tokens": tokens, "labels": labels}, cfg, local_plan,
            None, expert_perm,
        )
        new_residual = None
        if compress:
            # Error feedback (Seide et al.): compress (grad + residual), keep
            # this shard's own quantization error for the next step.  The
            # int32 sum through the RS/ring/AG stages is exact, so the only
            # noise is the shared quantization the residual absorbs.
            flat_g, treedef = jax.tree.flatten(grads)
            flat_r = treedef.flatten_up_to(residual)
            red, res = [], []
            for g, r in zip(flat_g, flat_r):
                target = g.astype(jnp.float32) + r[0]
                total, local = reduce_op.compressed(target, mean=True)
                red.append(total.astype(g.dtype))
                res.append((target - local)[None])
            grads = jax.tree.unflatten(treedef, red)
            new_residual = jax.tree.unflatten(treedef, res)
        else:
            grads = jax.tree.map(lambda g: reduce_op(g, mean=True), grads)
        stats = aux.moe_stats
        aux = dataclasses.replace(
            aux,
            # Expert-load telemetry is a count -> SUM over shards; the aux
            # losses are per-shard means -> averaged.
            moe_stats=None if stats is None else reduce_op(stats),
            balance_loss=reduce_op(aux.balance_loss, mean=True),
            z_loss=reduce_op(aux.z_loss, mean=True),
        )
        out = (reduce_op(loss, mean=True), reduce_op(ce, mean=True), aux, grads)
        return out + ((new_residual,) if compress else ())

    def grad_fn(params, batch, expert_perm, residual=None):
        args = [params, batch["tokens"], batch["labels"]]
        in_specs = [P(), tok_spec, tok_spec]
        if expert_perm is not None:
            args.append(expert_perm)
            in_specs.append(P())
        if compress:
            args.append(residual)
            in_specs.append(P(plan.batch_axes))
        out_specs = (P(), P(), P(), P()) + (
            (P(plan.batch_axes),) if compress else ()
        )
        has_perm = expert_perm is not None

        def wrapped(*a):
            pm = a[3] if has_perm else None
            res = a[-1] if compress else None
            return body(a[0], a[1], a[2], pm, res)

        f = jax.shard_map(
            wrapped, mesh=mesh, in_specs=tuple(in_specs), out_specs=out_specs,
            check_vma=False,
        )
        return f(*args)

    return grad_fn


def make_train_step(
    cfg,
    plan: ShardingPlan,
    opt_cfg: AdamWConfig,
    mesh=None,
    microbatches: int = 1,
    dp_comm: str = "auto",
    dp_compress: bool = False,
):
    """jit-able train step; ``microbatches > 1`` scans gradient accumulation
    over batch slices — activation live-set (and its reshard collectives per
    slice) shrink by the microbatch factor at the cost of re-gathering FSDP
    weights per slice (the classic trade; see EXPERIMENTS.md §Perf).

    ``dp_comm="runtime"`` routes the DP gradient reduction through the
    CommRuntime's hierarchical all-reduce (see module docstring);
    ``dp_compress=True`` additionally runs it through the int8 +
    error-feedback codec (``repro.optim.compress``) — the step then takes
    an extra ``ef_residual`` pytree (:func:`init_ef_residual`) and returns
    the updated one as a 4th output."""
    if dp_comm not in ("auto", "runtime"):
        raise ValueError(f"unknown dp_comm mode {dp_comm!r}")
    if dp_compress and dp_comm != "runtime":
        raise ValueError("dp_compress=True requires dp_comm='runtime'")
    if dp_compress and microbatches > 1:
        raise ValueError(
            "dp_compress=True supports microbatches=1 only (the error-feedback "
            "residual is a per-step state, not a per-slice one)"
        )
    runtime_grads = (
        _make_runtime_grad_fn(cfg, plan, mesh, compress=dp_compress)
        if dp_comm == "runtime"
        else None
    )

    def grad_once(params, batch, expert_perm, wire_perm, residual=None):
        if runtime_grads is not None:
            if wire_perm is not None:
                raise ValueError(
                    "wire_perm needs a model axis; dp_comm='runtime' runs on a "
                    "DP-only mesh"
                )
            out = runtime_grads(params, batch, expert_perm, residual)
            return out if dp_compress else (*out, None)
        (loss, (ce, aux)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch, cfg, plan, mesh, expert_perm, wire_perm
        )
        return loss, ce, aux, grads, None

    def train_step(
        params, opt_state, batch, expert_perm=None, wire_perm=None,
        ef_residual=None,
    ):
        if microbatches <= 1:
            loss, ce, aux, grads, new_residual = grad_once(
                params, batch, expert_perm, wire_perm, ef_residual
            )
        else:
            b = batch["tokens"].shape[0]
            m = microbatches
            assert b % m == 0, (b, m)

            def mb_body(acc, xs):
                tok, lab = xs
                l, c, a, g, _ = grad_once(
                    params, {"tokens": tok, "labels": lab}, expert_perm, wire_perm
                )
                acc = (
                    acc[0] + l / m,
                    acc[1] + c / m,
                    jax.tree.map(lambda p, q: p + q / m, acc[2], a),
                    jax.tree.map(lambda p, q: p + q / m, acc[3], g),
                )
                return acc, ()

            toks = batch["tokens"].reshape(m, b // m, -1)
            labs = batch["labels"].reshape(m, b // m, -1)
            zero_aux = jax.tree.map(
                jnp.zeros_like,
                jax.eval_shape(
                    lambda: grad_once(
                        params, {"tokens": toks[0], "labels": labs[0]},
                        expert_perm, wire_perm,
                    )[2]
                ),
            )
            zeros = (
                jnp.zeros((), jnp.float32),
                jnp.zeros((), jnp.float32),
                zero_aux,
                jax.tree.map(lambda p: jnp.zeros(p.shape, p.dtype), params),
            )
            (loss, ce, aux, grads), _ = jax.lax.scan(mb_body, zeros, (toks, labs))
            new_residual = None
        params, opt_state, opt_metrics = adamw_update(params, grads, opt_state, opt_cfg)
        metrics = {
            "loss": loss,
            "ce": ce,
            "balance_loss": aux.balance_loss,
            "z_loss": aux.z_loss,
            **opt_metrics,
        }
        if cfg.is_moe:
            metrics["expert_load"] = aux.moe_stats  # [repeats, E]
        if dp_compress:
            return params, opt_state, metrics, new_residual
        return params, opt_state, metrics

    return train_step


def make_prefill_step(cfg, plan: ShardingPlan, mesh=None, *, with_stats: bool = False):
    """``expert_perm``/``wire_perm`` thread the serving engine's runtime
    placement state into prefill (DESIGN.md §9); ``with_stats`` additionally
    returns the per-layer gate-load telemetry the control plane observes."""

    def prefill_step(params, batch, expert_perm=None, wire_perm=None):
        feats, aux, caches = tfm.model_apply(
            params, batch, cfg, plan, mesh=mesh, mode="prefill",
            expert_perm=expert_perm, wire_perm=wire_perm,
        )
        logits = tfm.logits_from_features(params, feats[:, -1:], cfg)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if with_stats:
            return next_tok, caches, aux.moe_stats
        return next_tok, caches

    return prefill_step


def make_prefill_chunk_step(cfg, plan: ShardingPlan, mesh=None, *, with_stats: bool = False):
    """Chunked-prefill continuation step (DESIGN.md §9).

    Runs a ``[B, C]`` slice of prompt tokens against EXISTING caches in
    decode mode: attention writes positions ``t .. t+C-1`` and attends
    causally over the cached prefix plus the chunk, so a long prompt streams
    through the decode tick loop ``C`` tokens at a time instead of stalling
    every live slot behind one monolithic prefill.  Returns the next-token
    prediction after the chunk's last token (the request's first output when
    the chunk completes the prompt) and the updated caches.

    Only attention block kinds support the multi-token continuation; the
    recurrent kinds (rglru/ssm) advance their state token-by-token.
    """
    bad = [
        k for k in (*cfg.block_pattern, *cfg.tail_pattern)
        if k not in ("global", "local")
    ]
    if bad:
        raise ValueError(
            f"chunked prefill needs attention-only block patterns, got {bad}"
        )

    def chunk_step(
        params, caches, tokens, t, expert_perm=None, wire_perm=None,
        gate_weights=None, page_table=None,
    ):
        feats, aux, caches = tfm.model_apply(
            params, {"tokens": tokens}, cfg, plan, mesh=mesh, mode="decode",
            caches=caches, t=t, expert_perm=expert_perm, wire_perm=wire_perm,
            gate_weights=gate_weights, page_table=page_table,
        )
        logits = tfm.logits_from_features(params, feats[:, -1:], cfg)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if with_stats:
            return next_tok, caches, aux.moe_stats
        return next_tok, caches

    return chunk_step


def make_serve_step(
    cfg, plan: ShardingPlan, mesh=None, *, sample: bool = False,
    with_stats: bool = False,
):
    def serve_step(
        params, caches, tokens, t, rng=None, expert_perm=None, wire_perm=None,
        gate_weights=None, page_table=None,
    ):
        """One decode step: tokens [B,1] + caches -> next token [B,1].

        ``expert_perm``/``wire_perm`` are the runtime placement state the
        serving engine threads per tick; ``gate_weights`` its live-slot mask
        for the exported gate-load telemetry (``with_stats``);
        ``page_table`` switches the caches onto the paged KV pool
        (DESIGN.md §10)."""
        feats, aux, caches = tfm.model_apply(
            params, {"tokens": tokens}, cfg, plan, mesh=mesh, mode="decode",
            caches=caches, t=t, expert_perm=expert_perm, wire_perm=wire_perm,
            gate_weights=gate_weights, page_table=page_table,
        )
        logits = tfm.logits_from_features(params, feats, cfg)[:, -1]
        if sample and rng is not None:
            if rng.ndim == 2:  # [B, 2] per-slot keys (DESIGN.md §11)
                next_tok = _sample_rows(logits, rng)
            else:  # legacy single key for the whole batch (decode.generate)
                next_tok = jax.random.categorical(rng, logits.astype(jnp.float32))
        else:
            next_tok = jnp.argmax(logits, axis=-1)
        next_tok = next_tok.astype(jnp.int32)[:, None]
        if with_stats:
            return next_tok, caches, aux.moe_stats
        return next_tok, caches

    return serve_step


def make_verify_step(
    cfg, plan: ShardingPlan, mesh=None, *, sample: bool = False,
    with_stats: bool = False,
):
    """Speculative VERIFY step (DESIGN.md §11): score a ``[B, C]`` causal
    continuation in one pass and emit the target model's token at EVERY
    position.

    Generalizes ``make_prefill_chunk_step`` from last-position-only to
    all-position outputs: position ``j`` of the chunk consumes token ``j``
    (the previous accepted token at ``j=0``, draft token ``j`` otherwise),
    attention overwrites cache positions ``t .. t+C-1`` with FULL-model K/V
    (the draft's approximate K/V at those positions is never read again),
    and ``tokens[:, j]`` of the result is what serial decode would emit
    after consuming the chunk prefix ``.. j`` — so the longest prefix where
    draft and verify agree, plus verify's first disagreeing token, is
    bit-exact serial decode.  ``rng``: ``[B, C, 2]`` per-(slot, position)
    sample keys under the per-verified-token key discipline.
    """
    bad = [
        k for k in (*cfg.block_pattern, *cfg.tail_pattern)
        if k not in ("global", "local")
    ]
    if bad:
        raise ValueError(
            f"speculative verify needs attention-only block patterns, got {bad}"
        )

    def verify_step(
        params, caches, tokens, t, rng=None, expert_perm=None, wire_perm=None,
        gate_weights=None, page_table=None,
    ):
        feats, aux, caches = tfm.model_apply(
            params, {"tokens": tokens}, cfg, plan, mesh=mesh, mode="decode",
            caches=caches, t=t, expert_perm=expert_perm, wire_perm=wire_perm,
            gate_weights=gate_weights, page_table=page_table,
        )
        logits = tfm.logits_from_features(params, feats, cfg)  # [B, C, V]
        if sample and rng is not None:
            b, c, _ = logits.shape
            flat = _sample_rows(logits.reshape(b * c, -1), rng.reshape(b * c, 2))
            toks = flat.reshape(b, c)
        else:
            toks = jnp.argmax(logits, axis=-1)
        toks = toks.astype(jnp.int32)
        if with_stats:
            return toks, caches, aux.moe_stats
        return toks, caches

    return verify_step


def make_draft_loop_step(
    cfg, plan: ShardingPlan, mesh=None, *, k: int, sample: bool = False,
):
    """Speculative DRAFT loop (DESIGN.md §11): ``k`` greedy/sampled decode
    steps of the (cheap) draft config FUSED into one jitted ``lax.scan``.

    Fusion is the perf point: a serial host loop pays one dispatch per draft
    token, which on launch-overhead-bound decode erases the speculative win;
    the scan makes the whole k-token draft ONE program launch, so a
    draft+verify round is 2 launches for up to k+1 emitted tokens.  The
    draft writes its approximate K/V into the SAME paged pool at positions
    ``t .. t+k-1`` (read only by its own later iterations); verify then
    overwrites those positions with full-model K/V.  ``cfg`` here is the
    DRAFT config (``models.moe.draft_config``).  ``rng``: ``[B, k, 2]``
    keys — the same per-verified-token keys verify uses, so draft samples
    are coupled to verify samples (common random numbers).
    """
    bad = [
        k_ for k_ in (*cfg.block_pattern, *cfg.tail_pattern)
        if k_ not in ("global", "local")
    ]
    if bad:
        raise ValueError(
            f"speculative drafting needs attention-only block patterns, got {bad}"
        )

    def draft_loop(
        params, caches, tokens, t, rng=None, expert_perm=None, wire_perm=None,
        gate_weights=None, page_table=None,
    ):
        b = tokens.shape[0]
        if rng is None:
            keys = jnp.zeros((k, b, 2), jnp.uint32)
        else:
            keys = jnp.swapaxes(rng, 0, 1)  # [k, B, 2]

        def body(carry, xs):
            caches, tok = carry
            i, kk = xs
            feats, _, caches = tfm.model_apply(
                params, {"tokens": tok}, cfg, plan, mesh=mesh, mode="decode",
                caches=caches, t=t + i, expert_perm=expert_perm,
                wire_perm=wire_perm, gate_weights=gate_weights,
                page_table=page_table,
            )
            logits = tfm.logits_from_features(params, feats, cfg)[:, -1]
            if sample:
                nxt = _sample_rows(logits, kk)
            else:
                nxt = jnp.argmax(logits, axis=-1)
            nxt = nxt.astype(jnp.int32)[:, None]
            return (caches, nxt), nxt[:, 0]

        (caches, _), drafts = jax.lax.scan(
            body, (caches, tokens), (jnp.arange(k, dtype=jnp.int32), keys)
        )
        return jnp.swapaxes(drafts, 0, 1), caches  # [B, k]

    return draft_loop


# ---------------------------------------------------------------------------
# shardings for pjit
# ---------------------------------------------------------------------------


def step_shardings(cfg, plan: ShardingPlan, mesh, param_specs):
    """NamedShardings for (params, opt_state, batch) under the given mesh."""
    ns = lambda spec: NamedSharding(mesh, spec)
    p_sh = jax.tree.map(ns, param_specs, is_leaf=lambda s: isinstance(s, P))
    opt_sh = {
        "mu": p_sh,
        "nu": p_sh,
        "step": ns(P()),
    }
    batch_sh = {
        "tokens": ns(plan.tokens_spec()),
        "labels": ns(plan.tokens_spec()),
    }
    return p_sh, opt_sh, batch_sh


def init_all(key, cfg, plan, opt_cfg, mesh=None):
    """(params, specs, opt_state) convenience initializer.

    With a ``mesh`` the state is created already sharded by its specs (one
    jitted init with ``out_shardings``): no device ever holds the whole
    model, which at published widths would not fit one chip."""
    from repro.models.transformer import init_model

    if mesh is None:
        params, specs = init_model(key, cfg, plan)
        return params, specs, init_adamw(params, opt_cfg)
    specs = {}

    def init(k):
        params, s = init_model(k, cfg, plan)
        specs.update(s)
        return params, init_adamw(params, opt_cfg)

    jax.eval_shape(init, key)
    p_sh, opt_sh, _ = step_shardings(cfg, plan, mesh, specs)
    params, opt_state = jax.jit(init, out_shardings=(p_sh, opt_sh))(key)
    return params, specs, opt_state
