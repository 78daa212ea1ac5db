"""Serving engine: continuous batching over a paged KV cache.

``Engine`` keeps a fixed pool of B batch rows ("slots") and a global
page pool for attention KV (``serve.kv_pool``).  Requests are admitted
per step into free slots, their prompt KV is scattered into
block-table-indexed pages, and one fused decode step advances every
active slot; finished slots free their pages immediately, so KV memory
tracks *live tokens* rather than ``slots * max_len`` (the vLLM-style
paged-attention dataflow, told in the MPU vocabulary: block tables are
the far-bank address path that picks which near-bank "row buffer" each
sequence streams next).

Design contract — **zero re-traces at steady state**:

* the decode step has ONE signature (pool + fixed-width tables), traced
  once; with ``offload=True`` it runs through the near-bank rewriter and
  ``Engine.offload_stats`` stays at ``plan_misses == traces == 1``;
* admits are shape-bucketed: prompts pad to pow2 buckets (exact under
  the causal mask and the length-aware SWA rolling capture), so the
  jitted admit retraces once per bucket and ``Engine.serve_stats``
  counters freeze after warmup;
* slot bookkeeping (pos/token/budget/temperature/active) lives on
  device and is updated inside the jitted step — one host sync per
  decode step.

Long prompts on dense attention-only models can prefill in fixed-size
chunks interleaved with decode (``prefill_chunk=N``): one chunk per
engine step scatters straight into the request's pages, bounding
per-step latency.  On page exhaustion the engine preempts the youngest
request by recompute (its prompt + emitted tokens re-queue), which is
exact for greedy decoding.

Knobs: ``page_size`` (tokens per KV page), ``num_pages`` (pool size;
default fits ``slots`` full-length requests — smaller values
oversubscribe and exercise preemption), ``prefill_chunk`` (0 = whole
prompts), ``bucket_prompts`` (pow2 admit bucketing).

Spans: ``admit`` runs in a ``jax.profiler.TraceAnnotation``
``engine.admit`` (rid, prompt and bucket tokens, slot) and ``step`` in a
``StepTraceAnnotation`` ``engine.step`` (step number, active rows) whose
children are ``engine.prepare`` (deadlines, resume, guard epoch, prefill
chunk, page growth), ``engine.dispatch`` (table upload, rng split, the
jitted call), ``engine.sync`` (the one host wait on the device) and
``engine.emit`` (per-slot bookkeeping).  They land on the trace clock
of whatever profiler is running; with none, each costs a check.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.configs.base import ModelConfig
from repro.kernels.guard import kernel_guard
from repro.models import build_model
from repro.models.transformer import attention_only_pattern
from repro.serve.kv_pool import PagePool, bucket_length, ceil_pow2


@dataclass
class Request:
    prompt: np.ndarray            # [S] int32
    max_new_tokens: int = 16
    temperature: float = 0.0
    rid: int = 0
    deadline_s: float = 0.0       # relative budget; 0 = no deadline
    deadline_at: float = 0.0      # absolute monotonic; stamped at submit/admit
    preempts: int = 0             # times preempted (bounded by max_preempts)


#: Completion.status values — "ok" is the only one with a full token
#: stream; the others are terminal non-success outcomes.
STATUSES = ("ok", "cancelled", "aborted", "rejected")


@dataclass
class Completion:
    rid: int
    tokens: list[int] = field(default_factory=list)
    status: str = "ok"
    reason: str = ""              # e.g. "deadline", "nan_logits", "queue_full"


class Engine:
    """Continuous-batching engine over a paged KV cache."""

    def __init__(self, cfg: ModelConfig, params: Any, *, slots: int = 8,
                 max_len: int = 512, seed: int = 0, offload: bool = False,
                 offload_policy: "OffloadPolicy | None" = None,
                 offload_bulk_threshold: int | None = None,
                 offload_max_plans: int | None = None,
                 page_size: int = 64, num_pages: int | None = None,
                 prefill_chunk: int = 0, bucket_prompts: bool = True,
                 max_preempts: int = 3, max_queue: int = 0,
                 fault_injector: Any = None):
        self.cfg = cfg
        self.model = build_model(cfg)
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.page_size = page_size
        w = cfg.sliding_window
        # logical per-request cache capacity (rolling window for SWA)
        self.kv_capacity = min(max_len, w) if w > 0 else max_len
        pages_per_req = -(-self.kv_capacity // page_size)
        self.table_width = ceil_pow2(pages_per_req)
        if num_pages is None:
            # page 0 is scratch; default sizes the pool for full residency
            num_pages = 1 + slots * pages_per_req
        self.num_pages = num_pages
        self.pool = PagePool(num_pages, page_size, self.table_width, slots)
        self.cache = self.model.init_paged_cache(slots, num_pages, page_size)

        # device-side slot state, updated inside the jitted step/admit —
        # ONE host sync per decode step (np.asarray of the emit triple)
        self._state = {
            "pos": jnp.zeros((slots,), jnp.int32),
            "tok": jnp.zeros((slots,), jnp.int32),
            "budget": jnp.zeros((slots,), jnp.int32),
            "temp": jnp.zeros((slots,), jnp.float32),
            "active": jnp.zeros((slots,), bool),
        }
        # host mirrors (slot occupancy / page-growth bookkeeping)
        self._host_active = np.zeros((slots,), bool)   # occupied (incl. prefilling)
        self._decode_active = np.zeros((slots,), bool)  # decoding
        self._host_pos = np.zeros((slots,), np.int32)
        self._slot_rid = np.full((slots,), -1, np.int32)
        self._slot_req: list[Request | None] = [None] * slots
        self._slot_emitted: list[list[int]] = [[] for _ in range(slots)]
        self._slot_seq = np.zeros((slots,), np.int64)  # admit order (preempt youngest)
        self._admit_seq = 0
        self._step_num = 0
        self._prefilling: dict[int, dict] = {}  # slot -> {req, prompt, ctx}
        self._requeue: list[Request] = []
        # robustness state: submit() queue (bounded by max_queue),
        # terminal events for pop_finished(), slots paused on transient
        # page-alloc faults, and the kernel-guard epoch the jitted step
        # was last built against
        self.max_preempts = max_preempts
        self.max_queue = max_queue
        self._injector = fault_injector
        self._queue: list[Request] = []
        self._events: list[Completion] = []
        self._paused = np.zeros((slots,), bool)
        self._transient_fault = False
        self._guard_epoch = kernel_guard().epoch

        self.rng = jax.random.PRNGKey(seed)
        self._has_frontend = cfg.frontend != "none"
        # pow2 admit bucketing is exact only when no recurrent state can
        # see the pad tokens; a MoE serves dropless, so pad tokens take
        # no capacity from real ones
        self.bucket_prompts = bucket_prompts and attention_only_pattern(cfg)
        # chunked prefill: dense causal attention scattering straight
        # into pages — no SWA rolling, no frontend prefix, no recurrent
        # state, no MoE capacity coupling across chunks
        if prefill_chunk > 0 and cfg.mla is not None:
            raise ValueError("chunked prefill does not support latent "
                             "attention (prefill_chunk must be 0)")
        self.prefill_chunk = prefill_chunk
        self._chunkable = (prefill_chunk > 0 and cfg.kind == "decoder"
                           and not self._has_frontend and w == 0
                           and cfg.moe is None
                           and attention_only_pattern(cfg))

        self.serve_counters = {"admit_traces": 0, "step_traces": 0,
                               "chunk_traces": 0, "control_traces": 0,
                               "preemptions": 0, "preemption_retries": 0,
                               "preempt_vetoes": 0, "deadline_cancels": 0,
                               "nan_aborts": 0, "page_faults": 0,
                               "alloc_stalls": 0, "kernel_replans": 0,
                               "reject_queue_full": 0, "reject_deadline": 0}
        if fault_injector is not None:
            # make trace-time kernel dispatch and durable-artifact IO see
            # the same injector the step-time fault classes use
            from repro.core.artifacts import set_disk_injector
            from repro.kernels.guard import set_injector
            set_injector(fault_injector)
            set_disk_injector(fault_injector)

        # the hot path: with offload on, the decode step goes through
        # the compile-time near-bank rewriter; the plan is built once
        # for the pool's decode signature and the result still jits +
        # donates.  ``offload_policy`` (an OffloadPolicy; implies
        # offload) selects the decision backend and planner knobs —
        # None leaves the wrapper unpinned, resolving the policy scope
        # active when the decode signature first TRACES.
        offload = offload or offload_policy is not None
        if offload_bulk_threshold is not None or \
                offload_max_plans is not None:
            from repro.core.policy import fold_legacy_kwargs
            offload_policy = fold_legacy_kwargs(
                offload_policy, where="Engine", target="offload_policy",
                bulk_threshold=offload_bulk_threshold,
                max_plans=offload_max_plans)
        self.offload = offload
        self.offload_policy = offload_policy
        self._decode_offload = None
        self._build_fns()

    # -- jitted functions ---------------------------------------------------
    def _build_step_fn(self):
        """(Re)build the jitted decode step.  Called once at init and
        again on kernel-guard epoch changes (``kernel_replans``): the
        fresh ``jax.jit`` re-enters the offload wrapper at trace time,
        which drops quarantine-stale plans and re-plans under the
        degraded (all_far) policy — the only way a quarantine can reach
        an already-compiled hot path.  The wrapper object itself is
        preserved so its stats/cache accumulate across rebuilds."""
        model, max_len = self.model, self.max_len
        counters = self.serve_counters

        def paged_decode(params, cache, tok, pos, tables, active):
            return model.decode_step_paged(params, cache, tok, pos,
                                           tables, active, max_len=max_len)

        if self.offload:
            if self._decode_offload is None:
                from repro.core.offload import mpu_offload
                self._decode_offload = mpu_offload(
                    paged_decode, policy=self.offload_policy)
            decode_fn = self._decode_offload
        else:
            decode_fn = paged_decode

        def step_impl(params, cache, state, tables, sub, poison):
            counters["step_traces"] += 1   # fires at trace time only
            logits, cache = decode_fn(params, cache, state["tok"],
                                      state["pos"], tables, state["active"])
            # chaos: poisoned rows get non-finite logits (no-op select
            # when poison is all-False, so fault-free runs stay exact)
            logits = jnp.where(poison[:, None], jnp.nan, logits)
            # a poisoned row must not kill the batch: detect non-finite
            # logits per row, sample that row from neutral logits, and
            # report the mask so the host aborts just that request
            bad = state["active"] & ~jnp.isfinite(logits).all(-1)
            safe = jnp.where(bad[:, None], 0.0, logits)
            greedy = jnp.argmax(safe, -1).astype(jnp.int32)
            temps = state["temp"]
            sampled = jax.random.categorical(
                sub, safe / jnp.maximum(temps[:, None], 1e-3)
            ).astype(jnp.int32)
            nxt = jnp.where(temps > 0, sampled, greedy)
            emitted, was_active = state["tok"], state["active"]
            pos = jnp.where(was_active, state["pos"] + 1, state["pos"])
            budget = jnp.where(was_active, state["budget"] - 1,
                               state["budget"])
            done = was_active & ((budget < 0) | (pos >= max_len - 1))
            new_state = {
                "pos": pos,
                "tok": jnp.where(was_active, nxt, state["tok"]),
                "budget": budget,
                "temp": state["temp"],
                "active": was_active & ~done,
            }
            return emitted, was_active, done, bad, new_state, cache

        self._step_fn = jax.jit(step_impl, donate_argnums=(1, 2))

    def _build_fns(self):
        model, cfg = self.model, self.cfg
        max_len, cap = self.max_len, self.kv_capacity
        page, counters = self.page_size, self.serve_counters
        w, has_frontend = cfg.sliding_window, self._has_frontend
        pool = self.pool

        self._build_step_fn()

        def admit_impl(params, cache, state, tokens, frontend, length,
                       slot, table_row, budget, temp):
            counters["admit_traces"] += 1  # once per prompt shape bucket
            batch = {"tokens": tokens}
            if has_frontend:
                batch["frontend"] = frontend
            logits, cache1 = model.prefill(params, batch, max_len, length)
            n_pr = (pool.pages_for(cap) if w > 0
                    else pool.pages_for(min(tokens.shape[1], cap)))
            cache = _scatter_admit(cache, cache1, table_row, slot,
                                   page=page, n_pr=n_pr)
            tok0 = jnp.argmax(logits[0]).astype(jnp.int32)
            state = {
                "pos": state["pos"].at[slot].set(length),
                "tok": state["tok"].at[slot].set(tok0),
                "budget": state["budget"].at[slot].set(budget),
                "temp": state["temp"].at[slot].set(temp),
                "active": state["active"].at[slot].set(True),
            }
            return cache, state

        self._admit_fn = jax.jit(admit_impl, donate_argnums=(1, 2))

        def chunk_impl(params, cache, tokens, table_row, ctx, n_valid):
            counters["chunk_traces"] += 1
            return model.prefill_chunk(params, cache, tokens, table_row,
                                       ctx, n_valid)

        self._chunk_fn = jax.jit(chunk_impl, donate_argnums=(1,))

        def activate_impl(state, logits, slot, pos0, budget, temp):
            counters["control_traces"] += 1
            tok0 = jnp.argmax(logits[0]).astype(jnp.int32)
            return {
                "pos": state["pos"].at[slot].set(pos0),
                "tok": state["tok"].at[slot].set(tok0),
                "budget": state["budget"].at[slot].set(budget),
                "temp": state["temp"].at[slot].set(temp),
                "active": state["active"].at[slot].set(True),
            }

        self._activate_fn = jax.jit(activate_impl, donate_argnums=(0,))

        def deactivate_impl(state, slot):
            counters["control_traces"] += 1
            return {**state, "active": state["active"].at[slot].set(False)}

        self._deactivate_fn = jax.jit(deactivate_impl, donate_argnums=(0,))

        def reactivate_impl(state, slot):
            counters["control_traces"] += 1
            return {**state, "active": state["active"].at[slot].set(True)}

        # resume a slot paused on a transient page-alloc fault: pos/tok/
        # budget were never touched, so flipping active back is exact
        self._reactivate_fn = jax.jit(reactivate_impl, donate_argnums=(0,))

    # -- introspection ------------------------------------------------------
    @property
    def offload_stats(self) -> dict | None:
        """Compile-time counters of the offloaded decode step (None when
        offload is off).  The wrapper sits under the engine's ``jax.jit``,
        so the counters tick at trace/compile time, not per decode step:
        the zero-retrace steady state is ``plan_misses == traces == 1``
        and ``plan_hits == 0`` — the paged decode has a single signature
        (fixed pool + fixed-width tables), so churning admissions and
        evictions never re-enter Python.  Growing ``traces`` /
        ``plan_misses`` would mean the decode signature is unstable;
        growing ``evictions`` means signature churn exceeds the policy's
        ``max_plans`` LRU bound.

        Kernel-guard health (``kernel_failures`` / ``kernel_fallbacks``
        / ``quarantines``, process-wide) is merged in, plus this
        wrapper's ``plan_invalidations``: under faults the bounded form
        of the zero-retrace contract is ``plan_misses <= 1 +
        plan_invalidations`` — re-plans happen only on quarantine
        events, never per step."""
        if self._decode_offload is None:
            return None
        return {**self._decode_offload.stats.as_dict(),
                **kernel_guard().stats()}

    @property
    def serve_stats(self) -> dict:
        """Serving-side counters: jit trace counts per entry point (each
        should freeze after one warmup per shape bucket — the serving
        analogue of ``offload_stats``'s zero-retrace contract), plus
        preemptions and live page-pool occupancy.  With a MoE,
        ``moe_routed``: the (token, expert) pairs routed to each held
        expert by the decode steps' active rows, summed over layers —
        counted on the device inside the step and read here."""
        stats = {
            **self.serve_counters,
            "pages_used": self.pool.used_pages,
            "pages_free": self.pool.free_pages,
            "page_size": self.page_size,
            "table_width": self.table_width,
        }
        if self.cache is not None and "moe_routed" in self.cache:
            stats["moe_routed"] = np.asarray(
                self.cache["moe_routed"]).tolist()
        return stats

    def explain_decode(self):
        """Per-segment offload DecisionReport of the paged decode step
        for the pool's current signature (None when offload is off):
        which chains fused, which candidates the policy declined, and
        the modeled near/far times behind each verdict."""
        if self._decode_offload is None:
            return None
        return self._decode_offload.explain(
            self.params, self.cache, self._state["tok"], self._state["pos"],
            jnp.asarray(self.pool.tables), self._state["active"])

    def verify_paged_tables(self):
        """Static bounds proof for the paged decode kernel's
        scalar-prefetched gathers: every block-table entry — padding
        slots included, because the K/V index map runs on masked grid
        steps too — must name a real page, and no slot's position may
        exceed what its table row addresses.  Returns the (possibly
        empty) list of ``repro.analysis`` findings."""
        from repro.analysis import verify_paged_decode
        return verify_paged_decode(
            self.pool.tables, np.asarray(self._state["pos"]),
            num_pages=self.num_pages, page_size=self.page_size)

    # -- slot management ----------------------------------------------------
    def _free_slot(self) -> int | None:
        idx = np.where(~self._host_active)[0]
        return int(idx[0]) if idx.size else None

    def _occupy(self, slot: int, req: Request, pos0: int):
        self._host_active[slot] = True
        self._host_pos[slot] = pos0
        self._slot_rid[slot] = req.rid
        self._slot_req[slot] = req
        self._slot_emitted[slot] = []
        self._slot_seq[slot] = self._admit_seq
        self._admit_seq += 1

    def _release(self, slot: int):
        self.pool.free_slot(slot)
        self._host_active[slot] = False
        self._decode_active[slot] = False
        self._paused[slot] = False
        self._slot_req[slot] = None
        self._slot_rid[slot] = -1
        self._prefilling.pop(slot, None)

    def _finish(self, slot: int, status: str = "ok", reason: str = ""):
        """Terminal transition: record the completion event (drained by
        ``pop_finished``) and free the slot + its pages immediately."""
        self._events.append(Completion(
            int(self._slot_rid[slot]), list(self._slot_emitted[slot]),
            status, reason))
        self._release(slot)

    def _preempt(self, slot: int):
        """Evict by recompute: requeue the request's prompt + emitted
        tokens (exact for greedy; sampled requests resample the tail).
        The requeued request carries its preemption count (victim
        eligibility bound) and its absolute deadline."""
        req = self._slot_req[slot]
        req.preempts += 1
        if slot in self._prefilling:
            self._requeue.append(req)   # nothing emitted yet
        else:
            emitted = self._slot_emitted[slot]
            remaining = req.max_new_tokens - len(emitted)
            if remaining > 0:
                prompt = np.concatenate([
                    np.asarray(req.prompt, np.int32),
                    np.asarray(emitted, np.int32)])
                self._requeue.append(Request(
                    prompt, remaining, req.temperature, req.rid,
                    deadline_s=req.deadline_s, deadline_at=req.deadline_at,
                    preempts=req.preempts))
                self.serve_counters["preemption_retries"] += 1
            self._state = self._deactivate_fn(self._state, slot)
        self._release(slot)
        self.serve_counters["preemptions"] += 1

    def _preempt_for_pages(self, protect: int) -> bool:
        """Free pages by preempting the youngest *eligible* decoding
        slot other than ``protect``.  Eligibility is the anti-starvation
        bound: a request preempted ``max_preempts`` times is exempt from
        further eviction, so two oversized requests can no longer
        preempt each other forever — the aged one keeps its pages and
        the other waits for completions.  Returns True if a victim was
        evicted."""
        candidates = [s for s in range(self.slots)
                      if self._decode_active[s] and s != protect]
        victims = [s for s in candidates
                   if self._slot_req[s].preempts < self.max_preempts]
        if not victims:
            if candidates:
                self.serve_counters["preempt_vetoes"] += 1
            return False
        self._preempt(max(victims, key=lambda s: self._slot_seq[s]))
        return True

    # -- admission ----------------------------------------------------------
    def _pool_ensure(self, slot: int, need: int) -> tuple[bool, bool]:
        """``pool.ensure`` with fault injection: returns (ok, injected).
        The injector is only consulted when the call would actually
        allocate (growth), so already-satisfied ensures never fault; an
        injected failure is transient — the caller stalls/pauses and
        retries instead of preempting."""
        if need > self.pool.allocated(slot) and self._injector is not None \
                and self._injector.page_alloc():
            self.serve_counters["page_faults"] += 1
            self._transient_fault = True
            return False, True
        return self.pool.ensure(slot, need), False

    def _stamp_deadline(self, req: Request):
        if req.deadline_s > 0 and req.deadline_at == 0.0:
            req.deadline_at = time.monotonic() + req.deadline_s

    def admit(self, req: Request) -> bool:
        """Admit a request into a free slot (prefill now, or start a
        chunked prefill).  Returns False when no slot/pages are free."""
        slot = self._free_slot()
        if slot is None:
            return False
        self._stamp_deadline(req)
        toks = np.asarray(req.prompt, np.int32).reshape(-1)
        s = toks.shape[0]
        chunked = self._chunkable and s > self.prefill_chunk
        if chunked:
            s_b = self.prefill_chunk
        else:
            s_b = bucket_length(s, self.max_len) if self.bucket_prompts else s
        with TraceAnnotation("engine.admit", rid=req.rid, prompt_tokens=s,
                             bucket_tokens=s_b, slot=slot):
            if chunked:
                need = self.pool.pages_for(min(self.prefill_chunk, s))
                if not self._pool_ensure(slot, need)[0]:
                    return False
                self._occupy(slot, req, pos0=s)
                self._prefilling[slot] = {"req": req, "prompt": toks,
                                          "ctx": 0}
                return True
            need = (self.pool.pages_for(self.kv_capacity)
                    if self.cfg.sliding_window > 0
                    else self.pool.pages_for(min(s_b, self.kv_capacity)))
            if not self._pool_ensure(slot, need)[0]:
                return False
            tokens = np.zeros((1, s_b), np.int32)
            tokens[0, :s] = toks
            if self._has_frontend:
                from repro.models.frontends import synth_frontend_embeddings
                frontend = synth_frontend_embeddings(
                    jax.random.fold_in(self.rng, req.rid), self.cfg, 1)
            else:
                frontend = np.zeros((1,), np.float32)  # unused traced arg
            self.cache, self._state = self._admit_fn(
                self.params, self.cache, self._state, tokens, frontend,
                int(s), int(slot), jnp.asarray(self.pool.tables[slot]),
                int(req.max_new_tokens - 1), float(req.temperature))
            self._occupy(slot, req, pos0=s)
            self._decode_active[slot] = True
            return True

    def _advance_prefill(self):
        """Run ONE prompt chunk for the oldest prefilling slot —
        interleaved with decode so long prompts don't stall the batch."""
        slot = next(iter(self._prefilling))
        info = self._prefilling[slot]
        prompt, ctx, c = info["prompt"], info["ctx"], self.prefill_chunk
        n_valid = min(c, prompt.shape[0] - ctx)
        need = self.pool.pages_for(ctx + n_valid)
        while True:
            ok, injected = self._pool_ensure(slot, need)
            if ok:
                break
            if injected:
                return  # transient fault: retry this chunk next step
            if not self._preempt_for_pages(protect=slot):
                if not self._decode_active.any():
                    raise RuntimeError(
                        "paged KV pool too small to prefill request "
                        f"{info['req'].rid}: need {need} pages, "
                        f"free {self.pool.free_pages}")
                return  # stall: decode completions will free pages
        tokens = np.zeros((1, c), np.int32)
        tokens[0, :n_valid] = prompt[ctx:ctx + n_valid]
        logits, self.cache = self._chunk_fn(
            self.params, self.cache, tokens,
            jnp.asarray(self.pool.tables[slot]), int(ctx), int(n_valid))
        ctx += n_valid
        if ctx >= prompt.shape[0]:
            req = info["req"]
            self._state = self._activate_fn(
                self._state, logits, int(slot), int(ctx),
                int(req.max_new_tokens - 1), float(req.temperature))
            del self._prefilling[slot]
            self._decode_active[slot] = True
            self._host_pos[slot] = ctx
        else:
            info["ctx"] = ctx

    # -- decode -------------------------------------------------------------
    def _slot_page_need(self, s: int) -> int:
        write_idx = min(int(self._host_pos[s]), self.kv_capacity - 1)
        return write_idx // self.page_size + 1

    def _pause_slot(self, s: int):
        """Transient page-alloc fault mid-decode: park the slot instead
        of preempting.  Its device state freezes (active=False) and its
        pages stay owned, so resuming later continues token-exact."""
        self._state = self._deactivate_fn(self._state, int(s))
        self._decode_active[s] = False
        self._paused[s] = True
        self.serve_counters["alloc_stalls"] += 1

    def _resume_paused(self):
        """Retry the page growth that paused each parked slot; on
        success flip the slot live again."""
        for s in np.flatnonzero(self._paused):
            ok, _ = self._pool_ensure(int(s), self._slot_page_need(int(s)))
            if ok:
                self._paused[s] = False
                self._decode_active[s] = True
                self._state = self._reactivate_fn(self._state, int(s))

    def _check_deadlines(self):
        """Cancel every occupied slot whose absolute deadline has
        passed: pages are reclaimed immediately and the completion
        carries the tokens emitted so far.  Queued/requeued requests
        expire the same way (see ``_pump``)."""
        now = time.monotonic()
        for s in range(self.slots):
            if not self._host_active[s]:
                continue
            req = self._slot_req[s]
            if req.deadline_at > 0 and now > req.deadline_at:
                if self._decode_active[s]:
                    self._state = self._deactivate_fn(self._state, int(s))
                self._finish(int(s), "cancelled", "deadline")
                self.serve_counters["deadline_cancels"] += 1

    def _check_guard_epoch(self):
        """Kernel quarantine (or reset) bumped the guard epoch: rebuild
        the jitted step so the next call re-traces through the offload
        wrapper and picks up the degraded/restored plan."""
        if self._decode_offload is None:
            return
        if kernel_guard().epoch != self._guard_epoch:
            self._guard_epoch = kernel_guard().epoch
            self._build_step_fn()
            self.serve_counters["kernel_replans"] += 1

    def _grow_pages(self):
        """Before a decode step, make sure every active slot owns the
        page its next write lands in (dense caches grow with ``pos``;
        SWA slots are fully allocated at admit).  Injected alloc faults
        pause the slot (transient); real exhaustion preempts a victim
        or — with no eligible victim and nothing running — raises."""
        if self.cfg.sliding_window > 0:
            return
        for s in np.where(self._decode_active)[0]:
            need = self._slot_page_need(int(s))
            while self._decode_active[s]:
                ok, injected = self._pool_ensure(int(s), need)
                if ok:
                    break
                if injected:
                    self._pause_slot(int(s))
                    break
                if not self._preempt_for_pages(protect=int(s)):
                    others = [o for o in range(self.slots)
                              if o != s and self._decode_active[o]]
                    if others or self._prefilling:
                        # every candidate victim is preemption-exempt:
                        # park this slot until their completions free
                        # pages (resumed by _resume_paused)
                        self._pause_slot(int(s))
                        break
                    raise RuntimeError(
                        "paged KV pool too small for a single request: "
                        f"need {need} pages, width {self.table_width}, "
                        f"free {self.pool.free_pages}")

    def step(self) -> list[tuple[int, int]]:
        """One engine step: sweep deadlines, resume paused slots,
        advance at most one prefill chunk, then one fused decode for all
        active slots.  Returns [(rid, token)]."""
        self._step_num += 1
        with StepTraceAnnotation("engine.step", step_num=self._step_num,
                                 active=int(self._decode_active.sum())):
            with TraceAnnotation("engine.prepare"):
                if self._injector is not None:
                    self._injector.slow_step()
                self._check_deadlines()
                self._resume_paused()
                self._check_guard_epoch()
                if self._prefilling:
                    self._advance_prefill()
                if not self._decode_active.any():
                    return []
                self._grow_pages()
                if not self._decode_active.any():
                    return []
                if self._injector is not None:
                    poison = self._injector.poison_slots(self._decode_active)
                else:
                    poison = np.zeros((self.slots,), bool)
            with TraceAnnotation("engine.dispatch"):
                self.rng, sub = jax.random.split(self.rng)
                emitted, was_active, done, bad, self._state, self.cache = \
                    self._step_fn(self.params, self.cache, self._state,
                                  jnp.asarray(self.pool.tables), sub, poison)
            with TraceAnnotation("engine.sync"):
                # the single host sync of the step
                em, wa, dn, bd = (np.asarray(emitted),
                                  np.asarray(was_active),
                                  np.asarray(done), np.asarray(bad))
            with TraceAnnotation("engine.emit"):
                out = []
                for s in range(self.slots):
                    if not wa[s]:
                        continue
                    tok = int(em[s])
                    out.append((int(self._slot_rid[s]), tok))
                    self._slot_emitted[s].append(tok)
                    self._host_pos[s] += 1
                    if bd[s]:
                        # non-finite logits: this step's emit (computed
                        # from the previous step's finite logits) stands,
                        # the NEXT token would be garbage — abort just
                        # this request
                        if not dn[s]:
                            self._state = self._deactivate_fn(self._state,
                                                              int(s))
                        self._finish(s, "aborted", "nan_logits")
                        self.serve_counters["nan_aborts"] += 1
                    elif dn[s]:
                        self._finish(s)
                return out

    # -- submission / lifecycle --------------------------------------------
    def submit(self, req: Request) -> str:
        """Queue a request with admission control.  Returns "queued", or
        a typed rejection reason — "rejected_queue_full" when the
        backlog is at ``max_queue`` (backpressure; 0 = unbounded), or
        "rejected_deadline" when the deadline already passed.  Rejected
        requests also surface as Completion events (``pop_finished``)."""
        self._stamp_deadline(req)
        if self.max_queue > 0 and \
                len(self._queue) + len(self._requeue) >= self.max_queue:
            self.serve_counters["reject_queue_full"] += 1
            self._events.append(Completion(
                req.rid, [], "rejected", "queue_full"))
            return "rejected_queue_full"
        if req.deadline_at > 0 and time.monotonic() > req.deadline_at:
            self.serve_counters["reject_deadline"] += 1
            self._events.append(Completion(
                req.rid, [], "rejected", "deadline"))
            return "rejected_deadline"
        self._queue.append(req)
        return "queued"

    def pop_finished(self) -> list[Completion]:
        """Drain terminal events (ok / cancelled / aborted / rejected)
        accumulated since the last call."""
        out, self._events = self._events, []
        return out

    def _pump(self) -> bool:
        """Admit as many queued requests as slots/pages allow — aged
        (preempted) requests first so re-queueing can never starve them
        behind fresh arrivals.  Expired queue entries are cancelled
        without occupying a slot.  Returns True if anything moved."""
        moved = False
        now = time.monotonic()
        for queue in (self._requeue, self._queue):
            while queue:
                head = queue[0]
                if head.deadline_at > 0 and now > head.deadline_at:
                    queue.pop(0)
                    self._events.append(Completion(
                        head.rid, [], "cancelled", "deadline"))
                    self.serve_counters["deadline_cancels"] += 1
                    moved = True
                    continue
                if not self.admit(head):
                    # a blocked aged head also blocks fresh admissions:
                    # a fresh request must not steal the slot/pages the
                    # aged one is waiting on
                    return moved
                queue.pop(0)
                moved = True
        return moved

    def generate(self, requests: list[Request]) -> dict[int, Completion]:
        """Run a request list to completion with continuous batching
        (per-step admission; preempted requests re-queue internally).
        Completions carry a terminal ``status``: "ok", "cancelled"
        (deadline), "aborted" (non-finite logits), or "rejected"
        (backpressure) — tokens are whatever was emitted before the
        terminal transition."""
        done: dict[int, Completion] = {
            r.rid: Completion(r.rid) for r in requests}
        for r in requests:
            self.submit(r)
        stalls = 0
        while self._queue or self._requeue or self._host_active.any():
            moved = self._pump()
            made = self.step()
            for rid, tok in made:
                done[rid].tokens.append(tok)
            for ev in self.pop_finished():
                done[ev.rid].status = ev.status
                done[ev.rid].reason = ev.reason
            if made or moved:
                stalls = 0
                continue
            # nothing moved this iteration: transient injected faults
            # and pages-in-flight (prefill stall, paused slots) deserve
            # bounded patience; an empty engine that cannot admit its
            # head request is stuck for good
            stalls += 1
            stuck_empty = not (self._prefilling or self._host_active.any()
                               or self._transient_fault)
            self._transient_fault = False
            if stuck_empty or stalls >= 10_000:
                raise RuntimeError(
                    "no progress: request cannot be admitted "
                    f"(free pages {self.pool.free_pages}, "
                    f"page_size {self.page_size})")
        for ev in self.pop_finished():
            done[ev.rid].status = ev.status
            done[ev.rid].reason = ev.reason
        return done


# batch-axis position (from the end) per cache leaf name — mirrors the
# layouts in repro.models.transformer.init_block_cache
_BATCH_AXIS_FROM_END = {"k": 4, "v": 4, "ssm": 4, "wkv": 4, "ckv": 3,
                        "conv": 3, "tshift": 3, "cshift": 3}


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", getattr(path[-1], "name", "")))


def _fit_len(x: jnp.ndarray, length: int, axis: int) -> jnp.ndarray:
    """Slice or zero-pad ``x`` to ``length`` along ``axis``."""
    t = x.shape[axis]
    if t == length:
        return x
    if t > length:
        idx = [slice(None)] * x.ndim
        idx[axis] = slice(0, length)
        return x[tuple(idx)]
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, length - t)
    return jnp.pad(x, pads)


def _scatter_admit(cache, cache1, table_row, slot, *, page: int, n_pr: int):
    """Merge a single-request prefill cache into the paged pools:
    attention K/V leaves and latent rows (``ckv``) scatter their first
    ``n_pr`` pages through the slot's block-table row; recurrent leaves
    write the slot's state row (name-resolved batch axis).  The decode
    counters pass through."""
    counters = {k: cache[k] for k in ("moe_routed",) if k in cache}
    cache = {k: v for k, v in cache.items() if k not in counters}

    def leaf(path, pool_leaf, one):
        name = _leaf_name(path)
        if name == "ckv":
            # rows [(L,) 1, T, C] -> pages [(L,) P, page, C]
            ids = table_row[:n_pr]
            x = _fit_len(one, n_pr * page, axis=one.ndim - 2)
            x = x.reshape(*x.shape[:-3], n_pr, page, x.shape[-1])
            x = x.astype(pool_leaf.dtype)
            if pool_leaf.ndim == 4:        # stacked periods
                return pool_leaf.at[:, ids].set(x)
            return pool_leaf.at[ids].set(x)
        if name in ("k", "v") and pool_leaf.ndim in (4, 5) \
                and one.ndim == pool_leaf.ndim:
            ids = table_row[:n_pr]
            if pool_leaf.ndim == 5:        # stacked periods
                x = _fit_len(one[:, 0], n_pr * page, axis=1)
                n, _, nk, h = x.shape
                x = x.reshape(n, n_pr, page, nk, h).transpose(0, 1, 3, 2, 4)
                return pool_leaf.at[:, ids].set(x.astype(pool_leaf.dtype))
            x = _fit_len(one[0], n_pr * page, axis=0)
            _, nk, h = x.shape
            x = x.reshape(n_pr, page, nk, h).transpose(0, 2, 1, 3)
            return pool_leaf.at[ids].set(x.astype(pool_leaf.dtype))
        from_end = _BATCH_AXIS_FROM_END.get(name)
        if from_end is None or one.ndim != pool_leaf.ndim:
            raise ValueError(
                f"cannot merge cache leaf {name!r} {one.shape} "
                f"-> {pool_leaf.shape}")
        ax = pool_leaf.ndim - from_end
        idx = (slice(None),) * ax + (slot,)
        return pool_leaf.at[idx].set(
            jnp.squeeze(one, ax).astype(pool_leaf.dtype))

    return {**jax.tree_util.tree_map_with_path(leaf, cache, cache1),
            **counters}
