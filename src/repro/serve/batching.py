"""Continuous batching: a fixed-slot decode batch where every slot runs at
its own position, finished sequences are evicted between steps and queued
prompts are admitted into the freed slots (vLLM-style scheduling on static
shapes — slot caches are scattered in place, never reshaped).

Decode attention supports per-slot ``t`` vectors natively
(:mod:`repro.models.layers`), so one jitted ``serve_step`` serves the whole
heterogeneous batch.  Two admission paths (DESIGN.md §9):

* **whole-prompt prefill** (default) — the prompt runs through a batch-1
  prefill and the resulting caches scatter into the freed slot;
* **chunked prefill** (``prefill_chunk > 0``) — the prompt streams through
  the decode tick loop ``prefill_chunk`` tokens at a time
  (:func:`repro.train.train_step.make_prefill_chunk_step`), so a long prompt
  never stalls the live decode slots behind one monolithic prefill — the
  chunk rides the same tick the decode step does, which is also what lets
  the netsim serving scenario hide the tick's all-to-all under the combined
  decode + prefill compute window.

Slot lifecycle hardening (regression-tested in ``tests/test_batching.py``):
prompts longer than the slot cache are rejected at admission (``req.error``)
instead of corrupting the ring buffer; a prompt that exactly fills the cache
emits its prefill token and finishes (no decode room); EOS on the final
allowed token finishes the request exactly like an early EOS; and an evicted
slot's dirty cache may be re-admitted into without clearing — every decode
read is masked to ``pos <= t``, so stale tail entries are never attended.

The serving engine (:mod:`repro.serve.engine`) threads runtime placement
state through the ``expert_perm`` / ``wire_perm`` attributes and reads
per-tick gate loads from :class:`TickStats` — the decode-time control-plane
contract.

**Paged KV cache** (DESIGN.md §10, auto-on when the model supports it): the
per-slot ring buffer is replaced by flat page pools plus a
``[slots, max_pages]`` table managed by :class:`repro.serve.paged.PageAllocator`.
Admission reserves pages up front (so a live slot never deadlocks on an
exhausted pool), prompt prefill scatters K/V into freshly allocated pages,
full prompt pages are published to a prefix registry for copy-on-write reuse
by later requests with the same system-prompt prefix, and slot retirement
returns pages to the free list.  HBM residency follows the *live token*
footprint instead of ``slots x max_len``, which is what lets the same pool
bytes serve more concurrent slots — the page-table indirection itself is
priced in the serving scenario of :mod:`repro.core.netsim`.
"""

from __future__ import annotations

import dataclasses
import functools
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import moe as moe_mod
from repro.models import transformer as tfm
from repro.obs import trace
from repro.serve.paged import PageAllocator
from repro.train.train_step import (
    make_draft_loop_step,
    make_prefill_chunk_step,
    make_prefill_step,
    make_serve_step,
    make_verify_step,
)

__all__ = ["ContinuousBatcher", "Request", "TickStats"]


@jax.jit
def _fold_slot_keys(base, rids, counts):
    """Per-slot sample keys: ``fold_in(fold_in(base, rid), emitted_index)``.

    The per-VERIFIED-token key discipline (DESIGN.md §11): the key stream is
    a pure function of (request, output position), so speculative and serial
    decode consume identical keys regardless of how many draft attempts were
    burned getting there.
    """
    k1 = jax.vmap(jax.random.fold_in, (None, 0))(base, rids)
    return jax.vmap(jax.random.fold_in)(k1, counts)  # [B, 2]


@jax.jit
def _fold_span_keys(base, rids, starts, offsets):
    """[B, C, 2] keys for a C-token span starting at each slot's next
    emitted-token index (one jit specialization per span length C)."""
    k1 = jax.vmap(jax.random.fold_in, (None, 0))(base, rids)

    def row(k, s):
        return jax.vmap(lambda o: jax.random.fold_in(k, s + o))(offsets)

    return jax.vmap(row)(k1, starts)


@functools.partial(jax.jit, donate_argnums=(0,))
@jax.named_scope("kv.write")
def _scatter_slot_caches(caches, one, slot):
    """Write ONE slot's column of every dense cache leaf.

    The donated input is the fix for the admission-path copy bug: an undonated
    ``full.at[:, slot].set(...)`` outside jit materializes a fresh
    ``slots x max_len`` copy of every leaf per admitted request; donated under
    jit it lowers to an aliased dynamic-update-slice that touches only the
    target column.  ``slot`` is traced, so all slots share one compile."""

    def sc(full, new):
        return jax.lax.dynamic_update_slice_in_dim(
            full, new.astype(full.dtype), slot, axis=1
        )

    return jax.tree.map(sc, caches, one)


@functools.partial(jax.jit, donate_argnums=(0,))
@jax.named_scope("kv.write")
def _scatter_prompt_pages(caches, one, page_ids):
    """Scatter a batch-1 prefill's K/V into the page pool.

    ``one`` leaves are ``[reps, 1, P*page, Hkv, dh]`` (padded to the table's
    span); ``page_ids [P]`` maps logical page j to its pool slot, -1 entries
    (past the prompt, or reused prefix pages that must not be overwritten)
    scatter out of bounds and drop."""

    def sc(pool, new):
        reps, n_pages, page = pool.shape[0], pool.shape[1], pool.shape[2]
        maxp = page_ids.shape[0]
        r = new[:, 0].reshape(reps, maxp, page, *new.shape[3:])
        pid = jnp.where(page_ids >= 0, page_ids, n_pages)
        return pool.at[:, pid].set(r.astype(pool.dtype), mode="drop")

    return jax.tree.map(sc, caches, one)


@functools.partial(jax.jit, donate_argnums=(0,))
@jax.named_scope("kv.write")
def _copy_pages(caches, src, dst):
    """Copy-on-write fork: duplicate pages ``src -> dst`` in every pool."""
    return jax.tree.map(lambda pool: pool.at[:, dst].set(pool[:, src]), caches)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # [S_prompt] int32
    max_new_tokens: int = 16
    eos_id: int | None = None
    # Traffic-region tag (workload regions; None = untagged).  The engine
    # attributes each tick's gate load to its live requests' regions — the
    # region-conditioned statistics fleet steering scores replicas with.
    region: int | None = None
    out: list = dataclasses.field(default_factory=list)
    error: str | None = None
    submit_tick: int = -1  # tick the request entered the queue
    first_token_tick: int = -1  # tick its first output token was emitted
    finish_tick: int = -1


@dataclasses.dataclass
class _Prefill:
    """Chunked-prefill progress of one admitted-but-not-yet-live request."""

    req: Request
    slot: int
    pos: int = 0


@dataclasses.dataclass
class TickStats:
    """What one tick did — the serving engine's observation surface."""

    live: int  # decode slots served
    prefill_tokens: int  # chunked-prefill tokens advanced this tick
    admitted: int
    finished: int
    gate_load: np.ndarray | None  # [repeats, E] live-slot expert loads
    # Speculative round telemetry (DESIGN.md §11) — all zero on plain ticks.
    spec_drafted: int = 0  # draft tokens proposed (live slots x span k)
    spec_accepted: int = 0  # draft tokens accepted AND emitted
    spec_verified: int = 0  # positions the FULL model scored (live x (k+1))


class ContinuousBatcher:
    """Slot-based continuous batching over a jitted decode step."""

    def __init__(
        self,
        params,
        cfg,
        plan,
        *,
        slots: int = 4,
        max_len: int = 128,
        mesh=None,
        prefill_chunk: int = 0,
        sample: bool = False,
        paged: bool | None = None,
        page_size: int = 16,
        num_pages: int = 0,
        prefix_cache: bool = True,
        spec_k: int = 0,
        spec_draft_mode: str = "auto",
        sample_seed: int = 0,
    ):
        self.params = params
        self.cfg = cfg
        self.plan = plan
        self.mesh = mesh
        self.slots = slots
        self.max_len = max_len
        self.prefill_chunk = int(prefill_chunk)
        self.sample = bool(sample)
        self.sample_seed = int(sample_seed)
        self._base_key = jax.random.PRNGKey(self.sample_seed) if self.sample else None
        self.spec_k = int(spec_k)
        # Measurement plane (DESIGN.md §14): the owning engine points
        # ``trace_tid`` at its viewer track so batcher spans (prefill chunks,
        # decode, spec draft/verify) land on the same timeline row.
        self._tr = trace.default()
        self.trace_tid: int | None = None
        self.queue: deque[Request] = deque()
        self.active: list[Request | None] = [None] * slots
        self.t = np.zeros(slots, np.int32)  # next write position per slot
        self.tokens = np.zeros((slots, 1), np.int32)
        # Paged KV cache (DESIGN.md §10): auto-on for attention-only models;
        # `paged=False` keeps the dense ring buffer (the bit-parity reference
        # and the fallback for MLA / recurrent / audio cache layouts).
        self.paged = tfm.paged_supported(cfg) if paged is None else bool(paged)
        self.alloc: PageAllocator | None = None
        if self.paged:
            self.page_size = int(page_size)
            self.max_pages = -(-max_len // self.page_size)
            self.num_pages = int(num_pages) or slots * self.max_pages
            self.caches = tfm.init_paged_caches(
                cfg, self.num_pages, self.page_size
            )
            self.alloc = PageAllocator(
                slots=slots,
                page_size=self.page_size,
                max_pages=self.max_pages,
                num_pages=self.num_pages,
                prefix_cache=prefix_cache,
            )
        else:
            self.caches = tfm.init_caches(cfg, slots, max_len)
        # Caches are donated into the decode/chunk steps so the slot (or page
        # pool) updates lower to in-place dynamic-update-slices instead of a
        # full-cache copy per tick.
        self._step = jax.jit(
            make_serve_step(cfg, plan, mesh=mesh, sample=sample, with_stats=True),
            donate_argnums=(1,),
        )
        self._prefill_fn = jax.jit(
            make_prefill_step(cfg, plan, mesh=mesh, with_stats=True)
        )
        # Paged mode always builds the chunk step: a prefix-cache hit resumes
        # the prompt mid-way as a decode-mode continuation even when chunked
        # prefill is off.
        self._chunk_fn = (
            jax.jit(
                make_prefill_chunk_step(cfg, plan, mesh=mesh, with_stats=True),
                donate_argnums=(1,),
            )
            if self.prefill_chunk > 0 or self.paged
            else None
        )
        # Speculative decoding (DESIGN.md §11): draft k tokens with the cheap
        # same-weights config, verify all k+1 positions in ONE chunked step.
        # Drafts append into the SAME paged pool, so rejection is a length
        # truncation — the dense ring buffer has no such invariant, hence the
        # paged requirement.
        self.draft_mode = "off"
        self._verify_fn = None
        self._draft_cfg = None
        self._draft_fns: dict[int, object] = {}  # span k -> jitted draft loop
        if self.spec_k > 0:
            if not self.paged:
                raise ValueError(
                    "speculative decoding requires the paged KV cache "
                    "(pass paged=True / a paged-capable model)"
                )
            self.draft_mode = moe_mod.resolve_draft_mode(cfg, spec_draft_mode)
            self._draft_cfg = moe_mod.draft_config(cfg, spec_draft_mode)
            self._verify_fn = jax.jit(
                make_verify_step(
                    cfg, plan, mesh=mesh, sample=sample, with_stats=True
                ),
                donate_argnums=(1,),
            )
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.spec_rounds = 0
        self.prefilling: deque[_Prefill] = deque()
        self.finished: list[Request] = []
        self.tick = 0
        self.kv_resident_pages_peak = 0
        # Runtime placement state, threaded by the serving engine (identity
        # when no control plane drives this batcher).  Stored as numpy; the
        # jitted steps receive them as traced values, so a reconfiguration
        # never recompiles.
        self.expert_perm: np.ndarray | None = None
        self.wire_perm: np.ndarray | None = None

    # -- admission -----------------------------------------------------------
    def submit(self, req: Request) -> None:
        req.submit_tick = self.tick
        self.queue.append(req)

    def _perm_args(self):
        perm = (
            jnp.asarray(self.expert_perm, jnp.int32)
            if self.expert_perm is not None
            else None
        )
        wire = (
            jnp.asarray(self.wire_perm, jnp.int32)
            if self.wire_perm is not None
            else None
        )
        return perm, wire

    def _finish(self, req: Request) -> None:
        req.finish_tick = self.tick
        self.finished.append(req)

    def _emit_first(self, req: Request, first: int) -> bool:
        """Record the prefill's next-token; True if the request is done."""
        req.out.append(first)
        req.first_token_tick = self.tick
        prompt_full = len(req.prompt) + 1 > self.max_len
        done = (
            len(req.out) >= req.max_new_tokens
            or (req.eos_id is not None and first == req.eos_id)
            or prompt_full  # no cache room to decode further
        )
        if done:
            self._finish(req)
        return done

    def _admit(self) -> tuple[int, np.ndarray | None]:
        admitted = 0
        load = None
        for slot in range(self.slots):
            if self.active[slot] is not None or not self.queue:
                continue
            if any(p.slot == slot for p in self.prefilling):
                continue  # slot reserved by an in-flight chunked prefill
            req = self.queue.popleft()
            if len(req.prompt) > self.max_len:
                # Reject instead of writing past the ring buffer: a prompt
                # longer than the cache would wrap and overwrite itself.
                req.error = "prompt_too_long"
                self._finish(req)
                continue
            plan_a = None
            if self.paged:
                # Speculative spans may overshoot max_new by up to spec_k
                # draft positions before the rejected tail is truncated, so
                # admission reserves that headroom too (usually 0-1 pages).
                plan_a = self.alloc.admit(
                    slot, req.prompt, req.max_new_tokens + self.spec_k,
                    self.max_len,
                )
                if plan_a is None:
                    # Pool cannot cover the request yet; keep FIFO order and
                    # wait for retiring slots to release pages.
                    self.queue.appendleft(req)
                    break
            admitted += 1
            start = plan_a.start if plan_a is not None else 0
            if self.prefill_chunk > 0:
                # Chunked prefill: reserve the slot, stream the prompt
                # through the tick loop (see _advance_prefill) — starting
                # past any prefix-cache hit.
                self.prefilling.append(_Prefill(req, slot, pos=start))
                continue
            # One span per admission: page allocation, prefill dispatch,
            # page scatter, and the host read of the first token.
            with self._tr.span("serve.admit", tid=self.trace_tid, rid=req.rid,
                               tokens=len(req.prompt) - start):
                if self.paged:
                    load = self._admit_paged(req, slot, plan_a, load)
                else:
                    load = self._admit_whole(req, slot, load)
        return admitted, load

    def _admit_whole(self, req: Request, slot: int, load):
        """Per-slot prefill: run the prompt through a batch-1 prefill, emit
        the prefill's next-token (the request's first output) and scatter the
        resulting caches into this slot."""
        perm, wire = self._perm_args()
        batch = {"tokens": jnp.asarray(req.prompt[None, :])}
        next_tok, one, stats = self._prefill_fn(self.params, batch, perm, wire)
        first = int(next_tok[0, 0])
        one = tfm.pad_caches(one, self.max_len)
        self.caches = _scatter_slot_caches(
            self.caches, one, jnp.asarray(slot, jnp.int32)
        )
        if stats is not None:
            s = np.asarray(stats)
            load = s if load is None else load + s
        if self._emit_first(req, first):
            return load
        self.active[slot] = req
        self.t[slot] = len(req.prompt)
        self.tokens[slot, 0] = first
        return load

    def _admit_paged(self, req: Request, slot: int, plan_a, load):
        """Paged admission: whole-prompt prefill scatters K/V into freshly
        allocated pages; a prefix-cache hit skips the reused pages and runs
        only the remainder as a decode-mode continuation chunk."""
        prompt = np.asarray(req.prompt)
        n = len(prompt)
        perm, wire = self._perm_args()
        if plan_a.start == 0:
            self._apply_forks(self.alloc.ensure(slot, 0, n))
            batch = {"tokens": jnp.asarray(prompt[None, :])}
            next_tok, one, stats = self._prefill_fn(self.params, batch, perm, wire)
            one = tfm.pad_caches(one, self.max_pages * self.page_size)
            self.caches = _scatter_prompt_pages(
                self.caches, one, jnp.asarray(self.alloc.table[slot])
            )
        else:
            self._apply_forks(self.alloc.ensure(slot, plan_a.start, n))
            next_tok, stats = self._run_chunk(
                slot, prompt[plan_a.start :], plan_a.start
            )
        first = int(next_tok[0, 0])
        self.alloc.register_prefix(slot, prompt)
        if stats is not None:
            s = np.asarray(stats)
            load = s if load is None else load + s
        if self._emit_first(req, first):
            self.alloc.release(slot)
            return load
        self.active[slot] = req
        self.t[slot] = n
        self.tokens[slot, 0] = first
        return load

    def _apply_forks(self, forks) -> None:
        if forks:
            src = jnp.asarray([f[0] for f in forks], jnp.int32)
            dst = jnp.asarray([f[1] for f in forks], jnp.int32)
            self.caches = _copy_pages(self.caches, src, dst)

    def _run_chunk(self, slot: int, chunk: np.ndarray, pos: int):
        """Run a decode-mode continuation chunk for one slot.  Paged mode
        runs batch-1 against the shared pools through the slot's table row;
        dense mode gathers/scatters the slot column."""
        perm, wire = self._perm_args()
        if self.paged:
            next_tok, self.caches, stats = self._chunk_fn(
                self.params,
                self.caches,
                jnp.asarray(chunk[None, :]),
                jnp.asarray(pos, jnp.int32),
                perm,
                wire,
                None,
                jnp.asarray(self.alloc.table[slot : slot + 1]),
            )
            return next_tok, stats
        next_tok, new, stats = self._chunk_fn(
            self.params,
            self._slot_caches(slot),
            jnp.asarray(chunk[None, :]),
            jnp.asarray(pos, jnp.int32),
            perm,
            wire,
        )
        self._scatter_slot(slot, new)
        return next_tok, stats

    def _slot_caches(self, slot: int):
        return jax.tree.map(lambda c: c[:, slot : slot + 1], self.caches)

    def _scatter_slot(self, slot: int, new) -> None:
        self.caches = _scatter_slot_caches(
            self.caches, new, jnp.asarray(slot, jnp.int32)
        )

    def _advance_prefill(self) -> tuple[int, np.ndarray | None]:
        """Advance ONE pending prompt by up to ``prefill_chunk`` tokens —
        the chunk rides the same tick the decode step does."""
        if not self.prefilling:
            return 0, None
        pf = self.prefilling[0]
        chunk = pf.req.prompt[pf.pos : pf.pos + self.prefill_chunk]
        with self._tr.span("serve.prefill_chunk", tid=self.trace_tid,
                           rid=pf.req.rid, tokens=len(chunk)):
            if self.paged:
                self._apply_forks(
                    self.alloc.ensure(pf.slot, pf.pos, pf.pos + len(chunk))
                )
            next_tok, stats = self._run_chunk(pf.slot, np.asarray(chunk), pf.pos)
        pf.pos += len(chunk)
        load = None if stats is None else np.asarray(stats)
        if pf.pos >= len(pf.req.prompt):
            self.prefilling.popleft()
            if self.paged:
                self.alloc.register_prefix(pf.slot, np.asarray(pf.req.prompt))
            first = int(next_tok[0, 0])
            if self._emit_first(pf.req, first):
                if self.paged:
                    self.alloc.release(pf.slot)
            else:
                self.active[pf.slot] = pf.req
                self.t[pf.slot] = len(pf.req.prompt)
                self.tokens[pf.slot, 0] = first
        return len(chunk), load

    def _span_keys(self, c: int):
        """[slots, c, 2] sample keys for a c-token span: slot s's key j is
        ``fold(fold(base, rid), len(out) + j)`` — the per-verified-token
        discipline that makes speculative and serial sampling identical."""
        rids = np.zeros(self.slots, np.int32)
        starts = np.zeros(self.slots, np.int32)
        for s in range(self.slots):
            req = self.active[s]
            if req is not None:
                rids[s] = req.rid
                starts[s] = len(req.out)
        return _fold_span_keys(
            self._base_key,
            jnp.asarray(rids),
            jnp.asarray(starts),
            jnp.arange(c, dtype=jnp.int32),
        )

    # -- one decode tick -------------------------------------------------------
    def step(self) -> TickStats:
        """Admit, advance one prefill chunk, decode one token (or one
        speculative draft/verify round) for every active slot, evict
        finished.  Returns the tick's observations."""
        admitted, pre_load = self._admit()
        prefill_tokens, chunk_load = self._advance_prefill()
        live = [s for s in range(self.slots) if self.active[s] is not None]
        finished = 0
        gate_load = None
        spec_drafted = spec_accepted = spec_verified = 0
        if live and self.spec_k > 0:
            finished, gate_load, spec_drafted, spec_accepted, spec_verified = (
                self._spec_tick(live)
            )
        elif live:
            # The decode span runs from the page bookkeeping to the tick's
            # tokens and gate loads reaching the host.
            with self._tr.span("serve.decode", tid=self.trace_tid,
                               live=len(live)):
                perm, wire = self._perm_args()
                live_mask = np.zeros((self.slots, 1), np.float32)
                live_mask[live] = 1.0
                page_table = None
                if self.paged:
                    # Every live slot writes position t[s] this tick; fork
                    # any shared page in range and allocate fresh pages on
                    # demand.
                    for s in live:
                        self._apply_forks(
                            self.alloc.ensure(s, int(self.t[s]), int(self.t[s]) + 1)
                        )
                    page_table = jnp.asarray(self.alloc.table)
                rng = self._span_keys(1)[:, 0] if self.sample else None
                # The live mask serves two jobs (DESIGN.md §9): it weights the
                # exported MoE gate telemetry, and it suppresses K/V writes
                # for dead slots — without it the decode step would stomp a
                # stale position of a slot that is empty or still
                # mid-chunked-prefill.
                next_tok, self.caches, stats = self._step(
                    self.params,
                    self.caches,
                    jnp.asarray(self.tokens),
                    jnp.asarray(self.t),
                    rng,
                    perm,
                    wire,
                    jnp.asarray(live_mask),
                    page_table,
                )
                if stats is not None:
                    gate_load = np.asarray(stats)
                next_np = np.asarray(next_tok)
            with self._tr.span("serve.emit", tid=self.trace_tid):
                for s in live:
                    req = self.active[s]
                    tok = int(next_np[s, 0])
                    req.out.append(tok)
                    self.t[s] += 1
                    self.tokens[s, 0] = tok
                    done = (
                        len(req.out) >= req.max_new_tokens
                        or (req.eos_id is not None and tok == req.eos_id)
                        or self.t[s] >= self.max_len
                    )
                    if done:
                        finished += 1
                        self._finish(req)
                        self.active[s] = None
                        if self.paged:
                            self.alloc.release(s)
        if self.paged:
            self.kv_resident_pages_peak = max(
                self.kv_resident_pages_peak, self.alloc.resident_pages()
            )
        for extra in (pre_load, chunk_load):
            if extra is not None:
                gate_load = extra if gate_load is None else gate_load + extra
        self.tick += 1
        return TickStats(
            live=len(live),
            prefill_tokens=prefill_tokens,
            admitted=admitted,
            finished=finished,
            gate_load=gate_load,
            spec_drafted=spec_drafted,
            spec_accepted=spec_accepted,
            spec_verified=spec_verified,
        )

    def _spec_tick(self, live):
        """One speculative draft/verify round (DESIGN.md §11).

        Draft the next k tokens per live slot with the cheap config (one
        fused ``lax.scan`` launch), then score all k+1 continuation
        positions with the FULL model in one chunked verify launch.  The
        accepted prefix — the longest run where draft and verify agree,
        plus verify's token at the first disagreement (serial decode's
        correction; a bonus token when everything matched) — is bit-exact
        what non-speculative decode would have emitted, greedy or sampled
        (verify samples with the same per-verified-token keys serial decode
        would have used).  Rejected tail positions hold orphaned K/V: the
        slot's length simply doesn't advance over them, and whole now-unused
        pages go straight back to the allocator's free list.
        """
        perm, wire = self._perm_args()
        # Uniform span: clamp k so every live slot's k+1 writes stay inside
        # the page table.  One compiled program per span length (Kossmann et
        # al.: bucket specializations); steady-state ticks all use k=spec_k.
        k = self.spec_k
        for s in live:
            k = min(k, self.max_len - 1 - int(self.t[s]))
        k = max(k, 0)
        c = k + 1
        # Draft writes t..t+k-1, verify rewrites t..t+k: make the whole span
        # privately writable up front (CoW forks + fresh pages, drawing on
        # the spec_k admission headroom).
        for s in live:
            self._apply_forks(
                self.alloc.ensure(s, int(self.t[s]), int(self.t[s]) + c)
            )
        page_table = jnp.asarray(self.alloc.table)
        t_vec = jnp.asarray(self.t)
        live_mask = np.zeros((self.slots, c), np.float32)
        live_mask[live] = 1.0
        span_keys = self._span_keys(c) if self.sample else None
        tokens = np.zeros((self.slots, c), np.int32)
        tokens[:, 0] = self.tokens[:, 0]
        draft_np = None
        if k > 0:
            draft_fn = self._draft_fns.get(k)
            if draft_fn is None:
                draft_fn = jax.jit(
                    make_draft_loop_step(
                        self._draft_cfg, self.plan, mesh=self.mesh, k=k,
                        sample=self.sample,
                    ),
                    donate_argnums=(1,),
                )
                self._draft_fns[k] = draft_fn
            with self._tr.span("serve.spec_draft", tid=self.trace_tid,
                               k=k, live=len(live)):
                drafts, self.caches = draft_fn(
                    self.params,
                    self.caches,
                    jnp.asarray(self.tokens),
                    t_vec,
                    None if span_keys is None else span_keys[:, :k],
                    perm,
                    wire,
                    jnp.asarray(live_mask[:, :1]),
                    page_table,
                )
            draft_np = np.asarray(drafts)
            tokens[:, 1:] = draft_np
        with self._tr.span("serve.spec_verify", tid=self.trace_tid,
                           span=c, live=len(live)):
            toks, self.caches, stats = self._verify_fn(
                self.params,
                self.caches,
                jnp.asarray(tokens),
                t_vec,
                span_keys,
                perm,
                wire,
                jnp.asarray(live_mask),
                page_table,
            )
        gate_load = None if stats is None else np.asarray(stats)
        v = np.asarray(toks)
        finished = 0
        drafted = k * len(live)
        accepted = 0
        for s in live:
            req = self.active[s]
            a = 0
            while a < k and draft_np[s, a] == v[s, a]:
                a += 1
            emit = [int(x) for x in v[s, : a + 1]]
            # EOS inside the accepted span: stop AT the EOS and discard the
            # tail — post-EOS positions were verified but must not be
            # emitted (they'd never exist in serial decode).
            if req.eos_id is not None and req.eos_id in emit:
                emit = emit[: emit.index(req.eos_id) + 1]
            emit = emit[: req.max_new_tokens - len(req.out)]
            accepted += min(len(emit), a)
            req.out.extend(emit)
            self.t[s] += len(emit)
            self.tokens[s, 0] = emit[-1]
            done = (
                len(req.out) >= req.max_new_tokens
                or (req.eos_id is not None and emit[-1] == req.eos_id)
                or self.t[s] >= self.max_len
            )
            if done:
                finished += 1
                self._finish(req)
                self.active[s] = None
                self.alloc.release(s)
            elif len(emit) < c:
                # Rejected/cut tail: whole pages past the accepted length go
                # straight back to the free list and the slot's reservation
                # is restored (PageAllocator.truncate).
                self.alloc.truncate(s, int(self.t[s]))
        self.spec_drafted += drafted
        self.spec_accepted += accepted
        self.spec_rounds += 1
        return finished, gate_load, drafted, accepted, c * len(live)

    @property
    def busy(self) -> bool:
        return bool(
            self.queue or self.prefilling or any(a is not None for a in self.active)
        )

    def run(self, max_ticks: int = 1000) -> list[Request]:
        for _ in range(max_ticks):
            if not self.busy:
                break
            self.step()
        return self.finished
