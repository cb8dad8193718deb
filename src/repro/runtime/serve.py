"""Batched serving runtime: fixed-slot continuous batching with
chunked prefill.

``Server`` keeps ``batch`` decode slots alive; requests are admitted
into free slots, finished requests retire and free their slot.  Each
slot has a *phase*: **prefill** (stream tokens still unconsumed) or
**decode** (generating).  An engine tick advances prefilling slots by
one ``prefill_chunk``-token jitted ``prefill_step`` and decoding slots
by the one-token jitted ``decode_step`` — a long prompt costs
``ceil(len/chunk)`` ticks instead of ``len``, amortizing the per-tick
weight stream chunk-wide.  This is continuous batching in its
TPU-friendly form: static shapes (slot count, chunk size and cache
length fixed), per-slot state packed in the same pytree the dry-run's
serve_step lowers.

**This module is mechanism only.**  Every discretionary decision —
which queued request to admit, which slot to sacrifice when the page
pool runs dry, when to preempt a low-SLO slot for a waiting high-SLO
arrival — is delegated to a :class:`~repro.runtime.scheduler.Scheduler`
(``Server(scheduler="fcfs" | "priority" | "prefix" | instance)``).  The
serving tunables and their measurement harnesses live in
:mod:`repro.runtime.tunables` (re-exported here for compatibility).

Greedy sampling; per-slot absolute positions drive RoPE/ring caches, so
mixed-progress (and mixed-phase) slots coexist in one batch.  Both
steps gate their state writes per slot, so a prefill tick cannot
corrupt a decoding neighbour and vice versa.

``paged=True`` swaps the per-slot KV rings for a shared page pool
(:mod:`repro.runtime.kv`): admission no longer pre-reserves a full
``context`` per slot — a request is admitted when its pages fit the
currently free pool, pages are allocated on demand as prefill chunks
and decode steps advance, and a tick that runs out of pages
**preempts** a policy-chosen victim: its pages are released
(refcounts decremented — shared pages survive for their sharers) and
the request is requeued with prompt AND generated tokens intact, to be
re-prefilled through the chunked path on resume.  Chunked prefill is
tokenwise-exact, so a preempted request's final output is byte-identical
to an undisturbed run.  The page size is a tunable
(:class:`~repro.runtime.tunables.KVPageTunable`, ``serve.kv_page``).
The pool is indexed by page, not slot, so where it is the whole decode
state the prefill step runs over the prefilling slots' rows alone,
padded to the next of :func:`prefill_rungs` (every rung compiled at the
first prefill), not over every slot.

``share_prefix=True`` (paged only) adds **copy-on-write prefix
sharing**: at placement the server looks for a live slot whose written
stream shares a page-aligned-or-longer prefix with the new request and
maps those pages into the new slot's table
(:meth:`~repro.runtime.kv.PagedKVAllocator.share`) — N requests with
one system prompt prefill it once.  The first write into a still-shared
page triggers a device-side page copy
(:meth:`~repro.runtime.kv.PagedKVAllocator.cow_pages`); only the
partial last shared page can ever need this, so sharing costs at most
one page copy per sharer.  Sharing is exact because attention masks
every key position ≥ the query's own validity: a sharer never attends
positions it has not itself written (or inherited below the shared
length), so a mid-prefill source writing beyond the shared length
cannot leak into a sharer's output.  SSM/hybrid and enc-dec state is
per-slot recurrence with no position index — sharing is refused there.

``speculate=`` adds a third per-tick slot population: decoding slots
with a draft from a :class:`~repro.runtime.speculate.Drafter` verify
``depth+1`` candidate tokens in ONE chunk forward
(:meth:`~repro.models.api.ModelAPI.verify_step` — the chunked-prefill
machinery as a verifier), accept the longest greedy-matching prefix
plus the verifier's bonus token, commit exactly the accepted tokens
with a second gated ``prefill_step``, and in paged mode ``rewind`` the
pages grabbed for rejected draft positions — so speculating,
prefilling, and plain-decoding neighbours coexist in one tick and the
page table stays byte-identical to a never-speculated drain.  Output
is token-for-token the baseline greedy stream; only the tick schedule
changes.  (The guarantee is exact up to floating-point argmax ties:
commit chunks reduce in a different order than one-token decodes, so
two logits that quantize to the same value — routine for random
reduced models at bfloat16 — can flip.  The KV cache follows the
params' dtype, so running float32 params restores real logit gaps and
with them stable parity.  Parity also requires comparing through the
same *compiled* steps: every Server for one api shares one set of
jitted steps — see the cache note in ``__init__`` — because XLA:CPU
codegen is not bit-reproducible across separate compiles.  And it
requires that no dispatch ever alias a persistent host buffer the
engine mutates between ticks — see :func:`_snapshot`.)  Depth ×
drafter is the ``serve.spec_depth`` tunable
(:class:`~repro.runtime.speculate.SpecDepthTunable`).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..models.api import ModelAPI
from ..obs.trace import span
from .kv import NO_PAGE, PagedKVAllocator, PagedKVSpec
from .scheduler import Scheduler, make_scheduler


def _snapshot(a: np.ndarray) -> jax.Array:
    """Device copy of a host array that is immune to later host writes.

    ``jnp.asarray`` on a small aligned numpy array is ZERO-COPY on the
    CPU backend: the jax Array aliases the numpy buffer.  Engine
    dispatches are asynchronous, so handing a step the live
    ``slot_pos`` / ``page_table`` buffer lets an in-flight executable
    observe increments the host makes a few lines later — e.g. the
    speculation commit (whose logits nothing syncs on) reading
    ``slot_pos`` after ``slot_pos[s] += e`` and scattering the
    committed tokens one chunk too far, leaving the true rows holding
    the slot's previous occupant's KV.  The window only opens when the
    runtime threads are preempted, so the corruption is rare and
    load-dependent.  Every dispatch that takes a persistent,
    host-mutated array must go through this copy; per-tick temporaries
    (``tokens``, ``lengths``, ``commit``, ``mask``) are never written
    after dispatch and may alias freely."""
    return jnp.asarray(np.array(a))


def prefill_rungs(batch: int) -> tuple[int, ...]:
    """The row counts a compacted paged prefill step is dispatched at:
    the powers of four below ``batch``, then ``batch`` — at most
    ⌈log₄ batch⌉ + 1 compiled programs."""

    rungs, r = [], 1
    while r < batch:
        rungs.append(r)
        r *= 4
    return (*rungs, batch)


def prefill_rung(n: int, batch: int) -> int:
    """The smallest rung of :func:`prefill_rungs` that holds ``n`` rows."""

    return next(r for r in prefill_rungs(batch) if r >= n)


def server_steps(api: ModelAPI, paged: bool) -> tuple:
    """The jitted ``(decode, prefill, verify)`` steps a ``Server`` of
    ``api`` dispatches, built once per ``(api, paged)`` and cached on
    ``api``.  Their arguments: ``(params, state, tokens, positions,
    active | lengths[, page_table])``; each returns ``(logits, new
    state)``."""

    # jitted one-token step over the whole slot batch; positions is
    # the (batch,) per-slot position vector — decode_step threads it
    # through RoPE, the ring-cache slot, and the validity mask, so
    # mixed-progress slots coexist correctly in one batch.  ``active``
    # gates the state merge per slot: slots mid-prefill (or idle)
    # must not have a garbage token scattered into their KV ring or
    # folded into their SSM recurrence.
    def gate(active, new, old):
        # per-slot leaves are (L, batch, ...): keep old rows of
        # inactive slots
        return jax.tree.map(
            lambda n, o: jnp.where(active.reshape(
                (1, active.shape[0]) + (1,) * (n.ndim - 2)), n, o),
            new, old)

    def step(params, state, tokens, positions, active):
        logits, new_state = api.decode_step(params, state, tokens,
                                            positions)
        return logits, gate(active, new_state, state)

    # paged sibling: the KV pool is SHARED, so its writes are gated
    # per slot inside the paged attention (``active``); only the
    # per-slot leaves (SSM recurrence, cross K/V) are merge-gated
    # here — a blanket tree-map of ``sel`` would slice the pool on
    # its page dim as if it were a slot dim
    def step_paged(params, state, tokens, positions, active,
                   page_table):
        logits, new_state = api.decode_step(params, state, tokens,
                                            positions, page_table,
                                            active)
        blocks = {}
        for key, entry in new_state["blocks"].items():
            old = state["blocks"][key]
            blocks[key] = {
                k2: (v2 if k2 == "kv" else gate(active, v2, old[k2]))
                for k2, v2 in entry.items()}
        return logits, {**new_state, "blocks": blocks}

    # jitted chunked-prefill step: per-slot chunk lengths gate every
    # state write inside the model (KV scatter, SSM scan, paged
    # pool), so one static-shape call serves any mix of
    # prefilling/other slots
    def pstep(params, state, tokens, positions, lengths):
        return api.prefill_step(params, state, tokens, positions,
                                lengths)

    def pstep_paged(params, state, tokens, positions, lengths,
                    page_table):
        return api.prefill_step(params, state, tokens, positions,
                                lengths, page_table)

    # speculation verifier: one chunk forward scoring all depth+1
    # candidate positions.  Its returned STATE is always discarded
    # (it holds rejected tokens' cache writes); the accepted prefix
    # is committed by a second, length-gated ``_prefill_step`` call
    # — the only uniform way to keep SSM/hybrid recurrence exact
    # under partial acceptance.
    def vstep(params, state, tokens, positions, lengths):
        return api.verify_step(params, state, tokens, positions,
                               lengths)

    def vstep_paged(params, state, tokens, positions, lengths,
                    page_table):
        return api.verify_step(params, state, tokens, positions,
                               lengths, page_table)

    # The jitted steps are built once per (api, paged) and SHARED by
    # every Server in the process (cached on the api object).  This
    # is a correctness requirement, not a compile-time nicety:
    # XLA:CPU native codegen is not bit-reproducible across separate
    # compiles of the same HLO — under CPU contention two jax.jit
    # calls on identical code can yield executables whose float
    # rounding differs enough to flip a near-tie argmax — so a
    # speculative server and its plain-decode baseline must argmax
    # through the SAME compiled step to be token-for-token
    # comparable.  jax.jit retraces per batch/context/dtype, so one
    # cache entry serves all server shapes.
    #
    # The paged decode and prefill steps DONATE the state (argument
    # 1): the pool rides through the layer scan's carry and each
    # step rewrites only the pages its new rows land in, so with the
    # input buffer donated XLA writes the pool in place instead of
    # copying it.
    # ``Server`` rebinds its ``state`` to the step's output at every
    # such call, so nothing reads a donated buffer.  The verifier
    # does not donate: its state is discarded and the server's state
    # feeds the commit prefill right after.
    cache = getattr(api, "_server_steps", None)
    if cache is None:
        cache = {}
        api._server_steps = cache
    if paged not in cache:
        cache[paged] = (
            jax.jit(step_paged, donate_argnums=1) if paged
            else jax.jit(step),
            jax.jit(pstep_paged, donate_argnums=1) if paged
            else jax.jit(pstep),
            jax.jit(vstep_paged if paged else vstep))
    return cache[paged]


@dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new: int
    out: list[int] = field(default_factory=list)
    done: bool = False
    slo: str = "interactive"    # SLO class (scheduler.PriorityScheduler)
    deadline: float | None = None   # absolute driver-clock deadline
    skips: int = 0              # admissions that bypassed this request
    preempted: int = 0          # times evicted mid-flight (progress kept)
    shared_prefix: int = 0      # tokens admitted via COW page sharing
    spec_proposed: int = 0      # draft tokens verified for this request
    spec_accepted: int = 0      # of those, accepted into the output
    # lifecycle stamps on time.perf_counter(): submission, and the first
    # placement into a slot (a preempted request keeps its first)
    t_submitted: float | None = None
    t_admitted: float | None = None


class Server:
    def __init__(self, api: ModelAPI, params, *, batch: int, context: int,
                 prefill_chunk: int = 32, paged: bool = False,
                 page_size: int = 16, kv_pages: int | None = None,
                 speculate: Any = None, spec_depth: int = 4,
                 scheduler: str | Scheduler | None = None,
                 share_prefix: bool = False, obs: Any = None):
        self.api = api
        self.params = params
        self.batch = batch
        self.context = context
        self.prefill_chunk = max(1, min(prefill_chunk, context))
        self.paged = paged
        self.scheduler = make_scheduler(scheduler)
        self.share_prefix = bool(share_prefix)
        if self.share_prefix and not paged:
            raise ValueError(
                "share_prefix=True needs paged=True: prefix sharing maps "
                "KV pages between slot page tables, contiguous rings have "
                "none")
        if self.share_prefix and api.cfg.is_encdec:
            raise ValueError(
                "share_prefix=True is unsupported for encoder-decoder "
                "models: per-slot cross-K/V is not positionally sharable")
        self.drafter = None
        self.spec_depth = max(1, min(spec_depth, context - 1))
        if speculate is not None:
            from .speculate import make_drafter
            self.drafter = make_drafter(speculate, api=api, params=params)
        self.alloc: PagedKVAllocator | None = None
        if paged:
            spec = PagedKVSpec.for_server(context=context,
                                          page_size=page_size,
                                          n_pages=kv_pages, batch=batch)
            self.alloc = PagedKVAllocator(spec, batch)
        # KV caches follow the params' dtype: a float32 model keeps a
        # float32 cache (greedy parity under speculation needs the real
        # logit gaps, not bfloat16-quantized ties), a bfloat16 model
        # keeps the compact default.
        pdt = next((leaf.dtype for leaf in jax.tree_util.tree_leaves(params)
                    if hasattr(leaf, "dtype")
                    and jnp.issubdtype(leaf.dtype, jnp.floating)), None)
        self.state = api.init_decode_state(
            batch, context, self.alloc.spec if paged else None, dtype=pdt)
        if self.share_prefix and any(
                "ssm" in entry or "enc_kv" in entry
                for entry in self.state["blocks"].values()):
            raise ValueError(
                "share_prefix=True needs pure-attention decode state: "
                "SSM/recurrent state at the share point is per-slot and "
                "has no position index to share through")
        # the paged pool is indexed by page, not slot, so where it is
        # the whole state a prefill step need only carry the prefilling
        # slots' rows (padded to a rung); a per-slot leaf (SSM
        # recurrence, cross K/V) keeps the full-batch step
        self._compact_prefill = paged and all(
            any(getattr(k, "key", None) == "kv" for k in path)
            for path, _ in jax.tree_util.tree_leaves_with_path(self.state))
        self._rungs_warm = False
        self.slot_req: list[Request | None] = [None] * batch
        self.slot_pos = np.zeros(batch, np.int32)   # per-slot token count
        self._slot_dirty = np.zeros(batch, bool)    # retired -> stale state
        self._slot_seq = np.zeros(batch, np.int64)  # admission order
        self._seq = 0
        self.deferrals = 0          # paged: evictions forced by page OOM
        self.preemptions = 0        # policy-initiated evictions (SLO)
        self.peak_active = 0
        self.peak_used_pages = 0
        # per-drain counters behind stats()
        self.ticks = 0
        self.slot_ticks = 0         # sum of active slots over ticks
        self.tokens_generated = 0
        self.prefill_chunks = 0
        self.prefill_rows_valid = 0       # chunk tokens prefilled
        self.prefill_rows_dispatched = 0  # rows × chunk the steps computed
        self.spec_ticks = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.share_hits = 0         # placements that mapped a prefix
        self.shared_tokens = 0      # prompt tokens admitted without prefill
        self.cow_copies = 0         # pages copied by write-triggered COW
        self.queue: list[Request] = []
        self.completed: list[Request] = []

        self._step, self._prefill_step, self._verify_step = \
            server_steps(api, paged)

        # observability is strictly additive: every hook below is
        # guarded by `if self.obs is not None` and records host state
        # the engine already materialized, so obs=None drains are
        # untouched and obs-attached drains are output-identical.
        self.obs = obs
        if obs is not None:
            obs.attach(self)

    # -- API ----------------------------------------------------------------
    def submit(self, prompt: list[int], max_new: int,
               frames: Any = None, *, slo: str = "interactive",
               deadline: float | None = None) -> Request:
        """``frames``: enc-dec audio frontend output (enc_seq, d_model)
        for this request; the encoder runs at admission and its cross-K/V
        fills the request's slot (serving-side prefill).  ``slo`` names
        the request's service class and ``deadline`` its absolute
        driver-clock deadline — both are policy inputs for the
        scheduler, the engine itself never reads them."""

        prompt = list(prompt)
        if not prompt:
            raise ValueError("empty prompt: a request needs at least one "
                             "prompt token")
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        limit = self.context - max_new
        if len(prompt) > limit:
            raise ValueError(
                f"prompt of {len(prompt)} tokens + max_new={max_new} "
                f"exceeds context={self.context}; prompts may be at most "
                f"context - max_new = {limit} tokens")
        req = Request(rid=len(self.completed) + len(self.queue) +
                      sum(r is not None for r in self.slot_req),
                      prompt=prompt, max_new=max_new, slo=slo,
                      deadline=deadline)
        req._frames = frames  # type: ignore[attr-defined]
        req.t_submitted = time.perf_counter()
        self.queue.append(req)
        if self.obs is not None:
            self.obs.on_submit(self, req)
        return req

    # -- scheduler-facing queries (the policy contract) ---------------------

    def live_slots(self) -> list[int]:
        return [s for s in range(self.batch)
                if self.slot_req[s] is not None]

    def has_free_slot(self) -> bool:
        return any(r is None for r in self.slot_req)

    def slot_seq(self, slot: int) -> int:
        """Admission order of the slot's occupant (higher = younger)."""

        return int(self._slot_seq[slot])

    def slot_request(self, slot: int) -> Request | None:
        return self.slot_req[slot]

    def admit_fits(self, req: Request) -> bool:
        """Would ``req``'s pages fit right now?  Contiguous mode always
        fits (a free slot has a full ring reserved); paged mode needs
        the full stream's pages minus any full pages a live shared
        prefix would map in for free."""

        if not self.paged:
            return True
        total = len(req.prompt) + len(req.out)
        need = self.alloc.pages_needed(total)
        if self.share_prefix:
            _, shared = self._find_share_source(req)
            need -= shared // self.alloc.spec.page_size
        return (need <= self.alloc.spec.pages_per_slot
                and need <= self.alloc.free_pages)

    def shared_prefix_len(self, req: Request) -> int:
        """Tokens a placement of ``req`` would map in via COW sharing
        right now (0 when sharing is off or nothing matches)."""

        if not self.share_prefix:
            return 0
        _, shared = self._find_share_source(req)
        return shared

    def is_share_source(self, slot: int) -> bool:
        """Does ``slot`` map at least one refcount>1 page?"""

        if self.alloc is None:
            return False
        return any(int(self.alloc.refcount[p]) > 1
                   for p in self.alloc.slot_pages(slot))

    # -- admission / placement / preemption ---------------------------------

    def _admit(self) -> None:
        # proactive SLO preemption first: the policy may evict live
        # low-class slots so waiting high-class arrivals run this tick
        # (bounded by batch — each eviction frees a slot, and a policy
        # only volunteers strictly-lower-class victims, so this cannot
        # churn)
        for _ in range(self.batch):
            if not self.queue:
                break
            victim = self.scheduler.preempt_for(self)
            if victim is None:
                break
            self._preempt(victim)
            self.preemptions += 1
        for slot in range(self.batch):
            if self.slot_req[slot] is None and self.queue:
                idx = self.scheduler.pick(self)
                if idx is None:
                    return
                self._place(slot, self.queue.pop(idx))

    def _place(self, slot: int, req: Request) -> None:
        """Bind ``req`` to ``slot``: recurrent-state hygiene, the COW
        prefix share (paged + ``share_prefix``), and the prefill target
        — ``len(prompt) + len(out)``, so a preempted request re-prefills
        its generated tokens too and resumes exactly where it left
        off."""

        self.slot_req[slot] = req
        if req.t_admitted is None:
            req.t_admitted = time.perf_counter()
        self._slot_seq[slot] = self._seq
        self._seq += 1
        if self._slot_dirty[slot]:
            self._reset_recurrent_state(slot)
            self._slot_dirty[slot] = False
        req._prefill_target = (len(req.prompt)  # type: ignore[attr-defined]
                               + len(req.out))
        start = 0
        if self.share_prefix:
            src, shared = self._find_share_source(req)
            if src is not None and self.alloc.share(src, slot, shared):
                start = shared
                req.shared_prefix = max(req.shared_prefix, shared)
                self.share_hits += 1
                self.shared_tokens += shared
        self.slot_pos[slot] = start
        req._cursor = start  # type: ignore[attr-defined]
        frames = getattr(req, "_frames", None)
        if self.api.cfg.is_encdec and frames is not None:
            kv = self.api.encode_cross_kv(
                # verify: waive(alias-dispatch) -- request audio frames
                # are request-immutable after submit; nothing writes
                # them between here and the dispatch
                self.params, jnp.asarray(frames)[None])
            xk, xv = self.state["xattn"]["k"], self.state["xattn"]["v"]
            self.state["xattn"]["k"] = xk.at[:, slot].set(
                kv["k"][:, 0].astype(xk.dtype))
            self.state["xattn"]["v"] = xv.at[:, slot].set(
                kv["v"][:, 0].astype(xv.dtype))
        if self.obs is not None:
            self.obs.on_admit(self, req, slot, start)

    def _backed_prefix(self, slot: int) -> int:
        """Tokens from position 0 whose pages ``slot`` still maps (SWA
        trim can have freed low pages — those positions cannot be
        shared from)."""

        n = 0
        for p in self.alloc.page_table[slot]:
            if p == NO_PAGE:
                break
            n += 1
        return n * self.alloc.spec.page_size

    def _find_share_source(self, req: Request) -> tuple[int | None, int]:
        """The live slot with the longest written, still-backed common
        prefix against ``req``'s stream, capped one short of the stream
        (at least one token must prefill to emit the next).  Sub-page
        matches return (None, 0): they would save no whole page and
        immediately pay a COW copy."""

        stream = req.prompt + req.out
        cap = len(stream) - 1
        best, best_len = None, 0
        for s in range(self.batch):
            src = self.slot_req[s]
            if src is None:
                continue
            written = (src.prompt + src.out)[:int(self.slot_pos[s])]
            m = min(len(written), cap, self._backed_prefix(s))
            n = 0
            while n < m and stream[n] == written[n]:
                n += 1
            if n > best_len:
                best, best_len = s, n
        if best_len < self.alloc.spec.page_size:
            return None, 0
        return best, best_len

    def _preempt(self, slot: int, reason: str = "slo-preempt") -> None:
        """Evict ``slot`` mid-flight: pages released (refcounts
        decremented — pages shared with other slots survive), request
        requeued at the FRONT with prompt and generated tokens intact.
        On re-admission the whole stream re-prefills through the
        chunked path, which emits the same next token the undisturbed
        slot would have — chunked prefill is tokenwise-exact — so
        preemption never changes a request's output.  ``reason``
        (policy preemption vs page-OOM defer) is observability-only."""

        req = self.slot_req[slot]
        req._cursor = 0  # type: ignore[attr-defined]
        req.preempted += 1
        self.queue.insert(0, req)
        if self.paged:
            self.alloc.release(slot)
        self.slot_req[slot] = None
        self.slot_pos[slot] = 0
        self._slot_dirty[slot] = True
        if self.obs is not None:
            self.obs.on_preempt(self, req, slot, reason)

    def _evict_for(self, slot: int) -> int | None:
        """Page-OOM backpressure: the policy names a victim, the engine
        preempts it.  Returns the victim (None = nothing live)."""

        victim = self.scheduler.victim(self)
        if victim is not None:
            self._preempt(victim, reason="oom-defer")
            self.deferrals += 1
        return victim

    def _ensure_pages(self, slot: int, n_tokens: int) -> bool:
        """Back ``slot`` through ``n_tokens`` positions, evicting
        policy-chosen victims until the allocation fits; False when
        ``slot`` itself was evicted (skip it this tick)."""

        while not self.alloc.ensure(slot, n_tokens):
            victim = self._evict_for(slot)
            if victim is None or victim == slot:
                return False
        return True

    def _cow_range(self, slot: int, start: int,
                   end: int) -> list[tuple[int, int]]:
        """Break page sharing before ``slot`` writes positions
        ``[start, end)``; same eviction backpressure as
        :meth:`_ensure_pages` when the copy needs pages the free list
        lacks.  Returns the (src, dst) pairs for :meth:`_copy_pages`
        (empty when nothing was shared or ``slot`` itself was
        evicted)."""

        while True:
            pairs = self.alloc.cow_pages(slot, start, end)
            if pairs is not None:
                return pairs
            victim = self._evict_for(slot)
            if victim is None or victim == slot:
                return []

    def _copy_pages(self, pairs: list[tuple[int, int]]) -> None:
        """Device half of COW: clone src pages' K/V into the fresh dst
        pages across every block's pool (page dim = axis 1 of the
        stacked kv leaves).  The table already points at dst; positions
        beyond the writer's own validity hold the source's garbage,
        which the position mask keeps unattended until overwritten."""

        src = jnp.asarray(np.array([p[0] for p in pairs], np.int32))
        dst = jnp.asarray(np.array([p[1] for p in pairs], np.int32))
        blocks = dict(self.state["blocks"])
        for key, entry in blocks.items():
            if "kv" not in entry:
                continue
            entry = dict(entry)
            # verify: waive(pool-write) -- 'entry' is a fresh dict copy
            # two lines up; the shared pool only sees it via the
            # blocks[key] swap below, never a mutated shared leaf
            entry["kv"] = jax.tree.map(
                lambda a: a.at[:, dst].set(a[:, src]), entry["kv"])
            blocks[key] = entry
        self.state = {**self.state, "blocks": blocks}
        self.cow_copies += len(pairs)

    def _reset_recurrent_state(self, slot: int) -> None:
        """Zero a reused slot's SSM/conv state: position masking hides
        stale KV-ring entries, but the recurrence has no position — a
        new request must not start from the previous one's hidden
        state.  Only the recurrent leaves are touched (dense archs pay
        nothing; KV rings stay as they are)."""

        blocks = dict(self.state["blocks"])
        for key, entry in blocks.items():
            if "ssm" in entry:
                entry = dict(entry)
                entry["ssm"] = jax.tree.map(
                    lambda a: a.at[:, slot].set(0), entry["ssm"])
                blocks[key] = entry
        self.state = {**self.state, "blocks": blocks}

    def _phase(self, slot: int) -> str:
        req = self.slot_req[slot]
        cur = req._cursor  # type: ignore[attr-defined]
        return "prefill" if cur < req._prefill_target else "decode"

    def _retire_if_done(self, slot: int) -> None:
        req = self.slot_req[slot]
        if len(req.out) >= req.max_new or \
                self.slot_pos[slot] >= self.context - 1:
            req.done = True
            self.completed.append(req)
            self.slot_req[slot] = None
            self._slot_dirty[slot] = True
            if self.paged:
                self.alloc.release(slot)
            if self.obs is not None:
                self.obs.on_retire(self, req, slot)

    def kv_stats(self) -> dict[str, float]:
        """Cache occupancy snapshot: live tokens vs reserved capacity
        (plus allocator fragmentation/sharing and eviction counters in
        paged mode) — the quantity ``bench_paged`` tables."""

        live = sum(int(self.slot_pos[s]) for s in range(self.batch)
                   if self.slot_req[s] is not None)
        if not self.paged:
            cap = self.batch * self.context
            return {"live_tokens": float(live), "capacity_tokens": float(cap),
                    "occupancy": live / cap if cap else 0.0,
                    "deferrals": 0.0, "peak_active": float(self.peak_active)}
        st = self.alloc.stats(live_tokens=live)
        st["capacity_tokens"] = float(self.alloc.spec.pool_tokens)
        st["deferrals"] = float(self.deferrals)
        st["peak_active"] = float(self.peak_active)
        st["peak_used_pages"] = float(self.peak_used_pages)
        return st

    def stats(self) -> dict[str, float]:
        """Per-drain engine-counter snapshot: how many ticks the drain
        took, what they produced, how speculation performed, and what
        the policy did (evictions, COW sharing) — surfaced by
        ``timed_server_drain(stats_out=...)`` /
        ``timed_trace_drain(stats_out=...)`` so tunable ``measure()``
        provenance and the serving benchmarks record real counters next
        to wall-clock."""

        g = self.tokens_generated
        return {
            "ticks": float(self.ticks),
            "tokens_generated": float(g),
            "ticks_per_token": (self.ticks / g) if g else 0.0,
            "mean_active": (self.slot_ticks / self.ticks
                            if self.ticks else 0.0),
            "prefill_chunks": float(self.prefill_chunks),
            "prefill_rows_valid": float(self.prefill_rows_valid),
            "prefill_rows_dispatched": float(self.prefill_rows_dispatched),
            "prefill_pad_share": (
                1.0 - self.prefill_rows_valid / self.prefill_rows_dispatched
                if self.prefill_rows_dispatched else 0.0),
            "deferrals": float(self.deferrals),
            "preemptions": float(self.preemptions),
            "peak_active": float(self.peak_active),
            "share_hits": float(self.share_hits),
            "shared_tokens": float(self.shared_tokens),
            "cow_copies": float(self.cow_copies),
            "spec_ticks": float(self.spec_ticks),
            "spec_proposed": float(self.spec_proposed),
            "spec_accepted": float(self.spec_accepted),
            "accept_rate": (self.spec_accepted / self.spec_proposed
                            if self.spec_proposed else 0.0),
        }

    def _propose_drafts(self) -> dict[int, list[int]]:
        """Host-side draft proposals for this tick's decoding slots.
        Depth is capped so emission can never overshoot ``max_new`` or
        the context (cap ``d``: up to ``d+1`` tokens emitted, and the
        verify chunk writes positions ``pos..pos+d``), making the spec
        path retire at exactly the baseline stopping point."""

        drafts: dict[int, list[int]] = {}
        if self.drafter is None:
            return drafts
        for s in range(self.batch):
            req = self.slot_req[s]
            if req is None or self._phase(s) != "decode":
                continue
            pos = int(self.slot_pos[s])
            cap = min(self.spec_depth,
                      req.max_new - len(req.out) - 1,
                      self.context - 2 - pos)
            if cap < 1:
                continue
            d = self.drafter.propose(req.prompt + req.out, cap)[:cap]
            if d:
                drafts[s] = [int(t) for t in d]
        return drafts

    def tick(self) -> int:
        """One engine iteration; returns number of active slots.

        Decoding slots advance one token through ``decode_step``;
        prefilling slots advance up to ``prefill_chunk`` stream tokens
        through ``prefill_step`` — the chunk that consumes a stream's
        last token also yields the request's next generated token,
        exactly as the tokenwise tick that fed that token would have.

        Paged mode first backs every slot's positions for this tick
        (admission order) and breaks COW sharing for every position
        about to be written; a slot the allocator cannot cover — even
        after evicting every policy-offered victim — is itself evicted
        and sits the tick out.

        The host spans (:func:`~repro.obs.trace.span`) have fixed
        names: ``serve.tick`` around the whole body; ``serve.admit``,
        ``serve.draft`` (with a drafter), ``serve.back`` (paged backing,
        evictions, COW copies), ``serve.trim`` (window reclamation); and
        per step run — ``decode``, ``verify``, ``accept`` (the
        speculative commit) or ``prefill`` — ``serve.pack.<step>`` (host
        inputs and their uploads), ``serve.dispatch.<step>`` (the jitted
        call), ``serve.readback.<step>`` (argmax to the host) and
        ``serve.commit.<step>`` (per-slot bookkeeping).  The prefill's
        pack and dispatch carry ``slots`` and the ``rung`` of rows the
        step ran."""

        with span("serve.tick", self.obs, self.ticks):
            return self._tick()

    def _tick(self) -> int:
        ob = self.obs
        with span("serve.admit", ob, self.ticks):
            self._admit()
        drafts: dict[int, list[int]] = {}
        if self.drafter is not None:
            with span("serve.draft", ob, self.ticks):
                drafts = self._propose_drafts()
        if self.paged:
            with span("serve.back", ob, self.ticks):
                self._back(drafts)
        active = [s for s in range(self.batch) if self.slot_req[s] is not None]
        self.peak_active = max(self.peak_active, len(active))
        if not active:
            return 0
        self.ticks += 1
        self.slot_ticks += len(active)
        if ob is not None:
            ob.on_tick_begin(self, self.ticks)
        decode = [s for s in active if self._phase(s) == "decode"]
        spec = [s for s in decode if s in drafts]
        decode = [s for s in decode if s not in drafts]
        prefill = [s for s in active if self._phase(s) == "prefill"]
        # the page table is snapshot once per tick, by the first step's
        # pack; every step of the tick reads that one snapshot
        table = None
        if decode:
            table = self._run_decode(decode, table)
        if spec:
            table = self._run_spec(spec, drafts, table)
        if prefill:
            self._run_prefill(prefill, table)

        # sliding-window reclamation: pages whose positions all fell out
        # of the window are never attended again — hand them back.  The
        # next tick's earliest attended position is slot_pos - window + 1.
        if self.paged and self.api.cfg.window is not None:
            with span("serve.trim", ob, self.ticks):
                w = self.api.cfg.window
                for s in range(self.batch):
                    if self.slot_req[s] is not None:
                        self.alloc.trim(
                            s, max(0, int(self.slot_pos[s]) - w + 1))
        if ob is not None:
            ob.on_tick_end(self, self.ticks, n_decode=len(decode),
                           n_spec=len(spec), n_prefill=len(prefill))
        return len(active)

    def _back(self, drafts: dict[int, list[int]]) -> None:
        """Paged backing for this tick: pages for every position a slot
        is about to write (admission order, evicting policy-chosen
        victims on OOM), draft positions where the free list covers them
        without evicting, and COW breaks copied on the device."""

        cow_pairs: list[tuple[int, int]] = []
        order = sorted((s for s in range(self.batch)
                        if self.slot_req[s] is not None),
                       key=lambda s: self._slot_seq[s])
        for s in order:
            req = self.slot_req[s]
            if req is None:          # evicted as an earlier victim
                continue
            pos = int(self.slot_pos[s])
            if self._phase(s) == "decode":
                end = pos + 1
                if s in drafts:
                    # opportunistic draft backing: shrink the draft to
                    # whatever the free list covers WITHOUT evicting a
                    # neighbour — speculation must never evict a slot a
                    # plain decode wouldn't
                    dr = drafts.pop(s)
                    for dd in range(len(dr), 0, -1):
                        if self.alloc.ensure(s, pos + dd + 1):
                            drafts[s] = dr[:dd]
                            end = pos + dd + 1
                            break
                if s not in drafts and not self._ensure_pages(s, pos + 1):
                    continue
            else:
                cur = req._cursor  # type: ignore[attr-defined]
                n = min(self.prefill_chunk, req._prefill_target - cur)
                end = pos + n
                if not self._ensure_pages(s, end):
                    continue
            if self.share_prefix and self.slot_req[s] is req:
                cow_pairs.extend(self._cow_range(s, pos, end))
        if cow_pairs:
            self._copy_pages(cow_pairs)
        self.peak_used_pages = max(self.peak_used_pages,
                                   self.alloc.used_pages)

    def _table(self, table):
        """The tick's page-table snapshot (taken on first use)."""

        if self.paged and table is None:
            table = _snapshot(self.alloc.page_table)
        return table

    def _run_decode(self, decode: list[int], table):
        ob, t = self.obs, self.ticks
        with span("serve.pack.decode", ob, t):
            tokens = np.zeros((self.batch, 1), np.int32)
            mask = np.zeros(self.batch, bool)
            for s in decode:
                tokens[s, 0] = self.slot_req[s].out[-1]
                mask[s] = True
            table = self._table(table)
            extra = (table,) if self.paged else ()
            tok, pos = jnp.asarray(tokens), _snapshot(self.slot_pos)
            act = jnp.asarray(mask)
        with span("serve.dispatch.decode", ob, t):
            logits, self.state = self._step(self.params, self.state, tok,
                                            pos, act, *extra)
        with span("serve.readback.decode", ob, t):
            nxt = np.asarray(jnp.argmax(logits, axis=-1))
        with span("serve.commit.decode", ob, t, slots=len(decode)):
            for s in decode:
                req = self.slot_req[s]
                req._cursor += 1  # type: ignore[attr-defined]
                self.slot_pos[s] += 1
                req.out.append(int(nxt[s]))
                self.tokens_generated += 1
                self._retire_if_done(s)
        return table

    def _run_spec(self, spec: list[int], drafts: dict[int, list[int]],
                  table):
        # speculation: verify the chunk [pending token, drafts...] at
        # absolute positions pos..pos+d in one forward, accept the
        # longest prefix of drafts matching the verifier's own greedy
        # picks (plus its bonus token), then COMMIT exactly the accepted
        # tokens with a length-gated prefill_step (the verify state,
        # rejected writes included, is discarded)
        ob, t = self.obs, self.ticks
        with span("serve.pack.verify", ob, t):
            D1 = self.spec_depth + 1
            tokens = np.zeros((self.batch, D1), np.int32)
            lengths = np.zeros(self.batch, np.int32)
            for s in spec:
                dr = drafts[s]
                tokens[s, 0] = self.slot_req[s].out[-1]
                tokens[s, 1:1 + len(dr)] = dr
                lengths[s] = len(dr) + 1
            table = self._table(table)
            extra = (table,) if self.paged else ()
            tok, pos = jnp.asarray(tokens), _snapshot(self.slot_pos)
            lens = jnp.asarray(lengths)
        with span("serve.dispatch.verify", ob, t):
            logits, _ = self._verify_step(self.params, self.state, tok, pos,
                                          lens, *extra)
        with span("serve.readback.verify", ob, t):
            greedy = np.asarray(jnp.argmax(logits, axis=-1))  # (B, D1)
        with span("serve.commit.verify", ob, t, slots=len(spec)):
            commit = np.zeros(self.batch, np.int32)
            emitted: dict[int, list[int]] = {}
            for s in spec:
                dr = drafts[s]
                k = 0
                while k < len(dr) and int(greedy[s, k]) == dr[k]:
                    k += 1
                emitted[s] = dr[:k] + [int(greedy[s, k])]
                commit[s] = k + 1
                req = self.slot_req[s]
                req.spec_proposed += len(dr)
                req.spec_accepted += k
                self.spec_proposed += len(dr)
                self.spec_accepted += k
        with span("serve.pack.accept", ob, t):
            pos, lens = _snapshot(self.slot_pos), jnp.asarray(commit)
        with span("serve.dispatch.accept", ob, t):
            _, self.state = self._prefill_step(self.params, self.state, tok,
                                               pos, lens, *extra)
        with span("serve.commit.accept", ob, t, slots=len(spec)):
            self.spec_ticks += 1
            for s in spec:
                req = self.slot_req[s]
                e = int(commit[s])
                req._cursor += e  # type: ignore[attr-defined]
                self.slot_pos[s] += e
                req.out.extend(emitted[s])
                self.tokens_generated += e
                if self.paged:
                    # hand back pages grabbed for rejected positions;
                    # the table must match a never-speculated drain
                    self.alloc.rewind(s, int(self.slot_pos[s]))
                self._retire_if_done(s)
        return table

    def _warm_prefill_rungs(self) -> None:
        """Run every rung of the compacted prefill step once, every
        length 0 (no row is written), and the readback's argmax over its
        logits, so that neither compiles in a later tick."""

        T, M = self.prefill_chunk, self.alloc.spec.pages_per_slot
        for K in prefill_rungs(self.batch):
            zeros = jnp.zeros(K, jnp.int32)
            logits, self.state = self._prefill_step(
                self.params, self.state, jnp.zeros((K, T), jnp.int32),
                zeros, zeros, jnp.full((K, M), NO_PAGE, jnp.int32))
            jnp.argmax(logits, axis=-1)
        self._rungs_warm = True

    def _run_prefill(self, prefill: list[int], table) -> None:
        # compacted: row i of the step is slot prefill[i], padded with
        # length-0 rows (position 0, no pages) to the next rung; else
        # row s is slot s over the whole batch
        ob, t = self.obs, self.ticks
        T = self.prefill_chunk
        if self._compact_prefill:
            K = prefill_rung(len(prefill), self.batch)
            row = {s: i for i, s in enumerate(prefill)}
        else:
            K = self.batch
            row = {s: s for s in prefill}
        with span("serve.pack.prefill", ob, t, slots=len(prefill), rung=K):
            tokens = np.zeros((K, T), np.int32)
            lengths = np.zeros(K, np.int32)
            for s in prefill:
                req = self.slot_req[s]
                cur = req._cursor  # type: ignore[attr-defined]
                # the stream includes generated tokens: a preempted
                # request re-prefills prompt + out and resumes exactly
                stream = req.prompt + req.out
                n = min(T, req._prefill_target - cur)
                tokens[row[s], :n] = stream[cur:cur + n]
                lengths[row[s]] = n
            if self._compact_prefill:
                # the prefilling slots' rows of the host table equal the
                # tick's snapshot: a step before this one only releases
                # or rewinds its own (decoding) slots
                idx = np.asarray(prefill)
                positions = np.zeros(K, np.int32)
                positions[:len(prefill)] = self.slot_pos[idx]
                pages = np.full((K, self.alloc.spec.pages_per_slot), NO_PAGE,
                                np.int32)
                pages[:len(prefill)] = self.alloc.page_table[idx]
                pos, extra = jnp.asarray(positions), (jnp.asarray(pages),)
            else:
                table = self._table(table)
                extra = (table,) if self.paged else ()
                pos = _snapshot(self.slot_pos)
            tok, lens = jnp.asarray(tokens), jnp.asarray(lengths)
        with span("serve.dispatch.prefill", ob, t, slots=len(prefill),
                  rung=K):
            if self._compact_prefill and not self._rungs_warm:
                self._warm_prefill_rungs()
            logits, self.state = self._prefill_step(
                self.params, self.state, tok, pos, lens, *extra)
        with span("serve.readback.prefill", ob, t):
            nxt = np.asarray(jnp.argmax(logits, axis=-1))
        with span("serve.commit.prefill", ob, t, slots=len(prefill)):
            self.prefill_chunks += len(prefill)
            self.prefill_rows_valid += int(lengths.sum())
            self.prefill_rows_dispatched += K * T
            for s in prefill:
                req = self.slot_req[s]
                n = int(lengths[row[s]])
                req._cursor += n  # type: ignore[attr-defined]
                self.slot_pos[s] += n
                if req._cursor >= req._prefill_target:
                    req.out.append(int(nxt[row[s]]))
                    self.tokens_generated += 1
                    self._retire_if_done(s)

    def run_until_drained(self, max_ticks: int = 10_000) -> None:
        for _ in range(max_ticks):
            if self.tick() == 0 and not self.queue:
                return
        raise RuntimeError("serving did not drain")


# ---------------------------------------------------------------------------
# compatibility re-exports: the serving tunables and their harnesses
# moved to repro.runtime.tunables (and the policies to
# repro.runtime.scheduler) when the scheduler subsystem landed; every
# pre-move import path keeps working through these.
# ---------------------------------------------------------------------------

from .scheduler import SCHEDULER_KINDS  # noqa: E402,F401
from .tunables import (K_AND_V, KV_CACHE_BYTES,  # noqa: E402,F401
                       DecodeBatchTunable, KVPageTunable,
                       PrefillChunkTunable, SchedulerTunable,
                       _require_model, choose_batch, choose_kv_page,
                       choose_prefill_chunk, choose_scheduler,
                       decode_batch_tunable, kv_cache_stream_s,
                       kv_page_tunable, prefill_chunk_tunable,
                       scheduler_tunable, timed_server_drain,
                       timed_trace_drain)

__all__ = ["Server", "Request", "Scheduler", "make_scheduler",
           "SCHEDULER_KINDS",
           "DecodeBatchTunable", "PrefillChunkTunable", "KVPageTunable",
           "SchedulerTunable", "decode_batch_tunable",
           "prefill_chunk_tunable", "kv_page_tunable", "scheduler_tunable",
           "choose_batch", "choose_prefill_chunk", "choose_kv_page",
           "choose_scheduler", "kv_cache_stream_s", "timed_server_drain",
           "timed_trace_drain"]
