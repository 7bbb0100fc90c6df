"""Continuous-batching generation scheduler for the LM serving path.

`generate_lm_batch` advances B prompts in lockstep: a request arriving
mid-flight waits for the WHOLE batch to drain (p99 TTFT = longest
generation in front of you). This scheduler owns a `models.zoo.
DecodeStepper` — a fixed-width slot batch with per-slot KV-cache cursors —
and admits new sequences at STEP BOUNDARIES: a request waits only for the
next single-token dispatch (+ its own prefill), and a slot is recycled the
moment its sequence hits EOS / its token budget.

Per-request sampling replays `generate_lm`'s exact draw sequence (one
`np.random.RandomState(seed)` per request, `_sample_token` per token), so
a continuously-batched generation is float-close to the sequential
single-sequence path — the acceptance property `tests/test_serving_tier.py`
pins down.

`mode="drain"` disables mid-flight admission (refill only when every slot
is free): the control arm `bench.py serving_slo` compares against.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from deeplearning4j_tpu import observability as _obs
from deeplearning4j_tpu.observability import propagate as _prop
from deeplearning4j_tpu.observability.ledger import NOOP_RECORD
from deeplearning4j_tpu.serving import metrics as _m
from deeplearning4j_tpu.serving.errors import (
    InputValidationError,
    RequestTimeoutError,
    ServerOverloadedError,
)


def prompt_bucket_ladder(capacity: int,
                         buckets: Optional[Sequence[int]] = None):
    """Prompt-length pad ladder: powers of two from 8 up to the decode
    cache capacity (explicit `buckets` override, capped at capacity)."""
    if buckets:
        ladder = sorted({int(b) for b in buckets if 0 < int(b) <= capacity})
        if not ladder:
            raise ValueError(
                f"prompt_buckets must contain a size in [1, {capacity}]")
        if ladder[-1] < capacity:
            ladder.append(capacity)
        return tuple(ladder)
    out, b = [], 8
    while b < capacity:
        out.append(b)
        b *= 2
    out.append(int(capacity))
    return tuple(out)


class GenerationRequest:
    __slots__ = ("prompt", "n_steps", "temperature", "top_k", "top_p",
                 "seed", "eos_id", "ids", "error", "deadline", "cancelled",
                 "event", "t_submit", "rng", "ctx", "t_submit_ns",
                 "adapter", "params", "ledger_rec", "_last_tok_ns",
                 "_rounds", "_decode_t0_ns")

    def __init__(self, prompt, n_steps, *, temperature=1.0, top_k=0,
                 top_p=0.0, seed=0, eos_id=None, deadline=None,
                 adapter=None, ledger_rec=None):
        # Multi-tenant serving: the LoRA adapter name this request decodes
        # through (None = the base model). `params` is filled at submit
        # with the adapter-merged tree; the decode loop groups slots by
        # adapter per round.
        self.adapter = None if adapter is None else str(adapter)
        self.params = None
        self.prompt = [int(t) for t in prompt]
        self.n_steps = int(n_steps)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.seed = int(seed)
        self.eos_id = None if eos_id is None else int(eos_id)
        self.ids: List[int] = list(self.prompt)
        self.error: Optional[str] = None
        self.deadline = deadline
        self.cancelled = False
        self.event = threading.Event()
        self.t_submit = time.monotonic()
        self.rng = np.random.RandomState(self.seed)
        # Trace context rides the request object into the decode-loop
        # thread (the submitter's thread-local binding stops at submit).
        self.ctx = _prop.current()
        self.t_submit_ns = time.perf_counter_ns()
        # Accounting record (observability/ledger.py): the decode loop
        # credits it marks, tokens, speculative accepts and its slot-share
        # of every round's wall time; the SERVER owns open/close. NOOP
        # default keeps direct scheduler users (tests, bench) branch-free.
        self.ledger_rec = NOOP_RECORD if ledger_rec is None else ledger_rec
        self._last_tok_ns: Optional[int] = None  # ITL anchor
        # Decode rounds this request rode, and when the first one began:
        # its one `serving.decode` span is written at retirement.
        self._rounds = 0
        self._decode_t0_ns = 0

    @property
    def done(self) -> bool:
        gen = len(self.ids) - len(self.prompt)
        if gen >= self.n_steps:
            return True
        return (self.eos_id is not None and gen > 0
                and self.ids[-1] == self.eos_id)


class GenerationScheduler:
    """One LM's continuous-batching decode loop (see module docstring)."""

    def __init__(self, cg, model_name: str = "default", slots: int = 4,
                 prompt_buckets: Optional[Sequence[int]] = None,
                 queue_depth: int = 64, mode: str = "continuous",
                 kv: str = "dense", page_size: int = 64,
                 kv_pages: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 prefix_cache_entries: int = 32,
                 draft=None, spec_k: int = 4, context=None):
        from deeplearning4j_tpu.models.zoo import (DecodeStepper,
                                                   PagedDecodeStepper)

        if mode not in ("continuous", "drain"):
            raise ValueError(f"unknown scheduler mode {mode!r}")
        if kv not in ("dense", "paged"):
            raise ValueError(f"unknown kv cache layout {kv!r}; "
                             "want 'dense' or 'paged'")
        if kv == "dense" and prefix_cache:
            raise ValueError(
                "prefix_cache requires kv='paged' (a hit installs pool "
                "pages by reference; the dense stepper has none to share)")
        self.model_name = model_name
        self.mode = mode
        self.kv = kv
        # Tensor-parallel serving: the host sharded `cg` over
        # `context.mesh` at load; the stepper runs every dispatch inside
        # the context so the whole decode loop serves GSPMD programs.
        self.context = context
        if kv == "paged":
            self.stepper = PagedDecodeStepper(cg, slots,
                                              page_size=page_size,
                                              pages=kv_pages,
                                              context=context)
        else:
            self.stepper = DecodeStepper(cg, slots, context=context)
        self.slots = self.stepper.slots
        self.capacity = self.stepper.capacity
        # Draft-model speculative decoding: a second (small) stepper
        # proposes spec_k tokens per round; the target verifies them in
        # ONE step_k dispatch. Both steppers advance in lockstep, so the
        # effective capacity is the smaller of the two caches.
        self._draft_stepper = None
        self._spec_k = int(spec_k)
        if draft is not None:
            if self._spec_k < 1:
                raise ValueError("spec_k must be >= 1 with a draft model")
            self._draft_stepper = DecodeStepper(draft, self.slots)
            self.capacity = min(self.capacity,
                                self._draft_stepper.capacity)
        # Prefix cache rides the page pool (default on for paged): repeat
        # prompts install shared pages + replay the stored first-token
        # distribution instead of prefilling.
        self._prefix_cache = None
        if kv == "paged" and (prefix_cache is None or prefix_cache):
            from deeplearning4j_tpu.models.kv_pool import PrefixCache

            self._prefix_cache = PrefixCache(
                self.stepper.pool, max_entries=prefix_cache_entries)
            self.stepper.pool.reclaim = self._prefix_cache.evict_one
        # Multi-tenant hooks (set by serving/server.py when the hosted
        # model has LoRA adapters loaded): name -> merged params tree, and
        # the list of names to warm per-adapter dispatch for.
        self.adapter_params = None
        self.adapter_names = None
        # Set by `abort_inflight` (a sharded replica group losing a peer):
        # every active and queued generation fails with this reason at the
        # next step boundary — the caller gets a clean error instead of a
        # hang or a silently truncated sequence.
        self._abort: Optional[str] = None
        self.prompt_buckets = prompt_bucket_ladder(self.capacity,
                                                   prompt_buckets)
        self._queue: "queue.Queue[Optional[GenerationRequest]]" = queue.Queue(
            maxsize=int(queue_depth))
        self._thread: Optional[threading.Thread] = None
        _m.MODEL_QUEUE_DEPTH.labels(
            model=model_name, route="generate").set_function(self._queue.qsize)
        self._itl_hist = _m.ITL_SECONDS.labels(model=model_name)
        self._disp_prefill = _m.DISPATCH_SECONDS.labels(model=model_name,
                                                        phase="prefill")
        self._disp_decode = _m.DISPATCH_SECONDS.labels(model=model_name,
                                                       phase="decode")
        self._round_end_ns = 0  # end of the latest decode round
        if kv == "paged":
            pool = self.stepper.pool
            for st in ("free", "used", "shared"):
                _m.KV_PAGES.labels(model=model_name, state=st).set_function(
                    lambda s=st, p=pool: p.counts()[s])

    # ------------------------------------------------------------ control

    def start(self) -> "GenerationScheduler":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, name=f"dl4j-decode-{self.model_name}",
                daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        t = self._thread
        if t is not None:
            self._thread = None
            try:
                self._queue.put_nowait(None)
            except queue.Full:
                pass
            # Bounded join: see ShapeBucketBatcher.stop — a worker left
            # mid-dispatch at interpreter shutdown dies inside native code.
            t.join(timeout=10.0)

    def qsize(self) -> int:
        return self._queue.qsize()

    def abort_inflight(self, reason: str) -> None:
        """Fail every active and queued generation with `reason` at the
        next step boundary, and every later submit on arrival, until
        `clear_abort()`. Used by the sharded-group peer watchdog
        (`serving/fleet.py`): when a shard member dies, the survivors'
        in-flight sequences can never finish coherently — surfacing a
        prompt error beats a hang (the client) or a truncation passed off
        as completion (the caller's training data)."""
        self._abort = str(reason)

    def clear_abort(self) -> None:
        self._abort = None

    # ------------------------------------------------------------- warmup

    def warmup(self) -> None:
        """Compile every prefill bucket + the step program into the AOT
        store before traffic (one short throwaway generation per bucket).
        With a draft model, also warms the draft's programs and every
        speculative verify width (k_round shrinks from spec_k to 0 near
        capacity, and each T is its own traced program). With adapters
        loaded, every bucket is re-driven through ONE adapter-merged tree:
        merged trees all share a pytree structure (distinct from the bare
        base), so one variant warms per-adapter dispatch for every
        tenant."""
        for b in self.prompt_buckets:
            probs, slot_state, n = self.stepper.prefill([0], pad_to=b)
        self.stepper.install(0, slot_state, n)
        self.stepper.step([0] * self.slots)
        self.stepper.warm_page_copies()
        names = self.adapter_names() if callable(self.adapter_names) else ()
        if names and self.adapter_params is not None:
            try:
                self.stepper.set_params(self.adapter_params(names[0]))
                for b in self.prompt_buckets:
                    _, astate, an = self.stepper.prefill([0], pad_to=b)
                self.stepper.install(0, astate, an)
                self.stepper.step([0] * self.slots)
            finally:
                self.stepper.set_params(None)
        if self._draft_stepper is not None:
            for t in range(2, self._spec_k + 2):
                self.stepper.rewind_all([n] + [0] * (self.slots - 1))
                self.stepper.step_k(np.zeros((self.slots, t), np.int64))
            for b in self.prompt_buckets:
                _, dstate, dn = self._draft_stepper.prefill([0], pad_to=b)
            self._draft_stepper.install(0, dstate, dn)
            self._draft_stepper.step([0] * self.slots)
            self._draft_stepper.warm_page_copies()
            self._draft_stepper.clear(0)
        self.stepper.clear(0)

    # ---------------------------------------------------------- admission

    def submit(self, req: GenerationRequest) -> GenerationRequest:
        if not req.prompt:
            raise InputValidationError("prompt_ids must be non-empty")
        if req.n_steps < 1:
            raise InputValidationError("n_steps must be >= 1")
        if len(req.prompt) + req.n_steps > self.capacity:
            raise InputValidationError(
                f"prompt ({len(req.prompt)}) + n_steps ({req.n_steps}) "
                f"exceeds the decode cache capacity {self.capacity}")
        if req.adapter is not None:
            if self._draft_stepper is not None:
                raise InputValidationError(
                    "adapter selection is not supported with a draft "
                    "(speculative) model configured — the draft has no "
                    "per-tenant delta to propose with")
            if self.adapter_params is None:
                raise InputValidationError(
                    f"model {self.model_name!r} hosts no adapters "
                    f"(requested {req.adapter!r})")
            try:
                # Resolve at admission so an unknown name 400s here and
                # the decode loop only ever sees a ready merged tree.
                req.params = self.adapter_params(req.adapter)
            except KeyError as e:
                raise InputValidationError(str(e.args[0]) if e.args
                                           else str(e))
        try:
            self._queue.put_nowait(req)
        except queue.Full:
            raise ServerOverloadedError(
                f"model {self.model_name!r} generation queue is full "
                f"({self._queue.maxsize} requests); retry later")
        return req

    def generate(self, prompt_ids, n_steps: int, *,
                 timeout_s: Optional[float] = None, adapter=None,
                 ledger_rec=None, **sampling) -> List[int]:
        """Blocking helper: submit + wait; cancels the request (recycled at
        the next step boundary) when the caller's timeout expires."""
        deadline = (None if timeout_s is None
                    else time.monotonic() + timeout_s)
        req = GenerationRequest(prompt_ids, n_steps, deadline=deadline,
                                adapter=adapter, ledger_rec=ledger_rec,
                                **sampling)
        self.submit(req)
        req.event.wait(timeout=timeout_s)
        if not req.event.is_set():
            req.cancelled = True
            raise TimeoutError(
                f"generation timed out after {timeout_s}s; the slot is "
                "recycled at the next step boundary")
        if req.error == "__deadline__":
            raise RequestTimeoutError(
                "generation deadline expired before completion")
        if req.error is not None:
            raise RuntimeError(req.error)
        return req.ids

    # --------------------------------------------------------------- loop

    def _sample(self, req: GenerationRequest, probs) -> int:
        from deeplearning4j_tpu.models.zoo import _sample_token

        tok = _sample_token(probs, req.rng, req.temperature, req.top_k,
                            req.top_p)
        req.ids.append(tok)
        # Per-request inter-token gap: the SLO engine's itl_p99 objective
        # reads this distribution (TTFT covers the first token, so the
        # first sample only anchors the clock).
        now_ns = time.perf_counter_ns()
        if req._last_tok_ns is not None:
            self._itl_hist.observe((now_ns - req._last_tok_ns) / 1e9)
        req._last_tok_ns = now_ns
        req.ledger_rec.add_tokens_out(1)
        _m.GENERATED_TOKENS.labels(model=self.model_name).inc()
        return tok

    def _finish_timeout(self, req: GenerationRequest) -> None:
        _m.REQUESTS.labels(model=self.model_name, route="generate",
                           outcome="timeout").inc()
        if not req.cancelled:
            req.error = "__deadline__"
        req.event.set()

    def _install_prompt(self, slot: int, req: GenerationRequest,
                        pad_to: int):
        """Get `slot` holding `req.prompt`'s KV and return the first-token
        distribution. Prefix-cache hit: point the slot at the resident
        pages and replay the STORED distribution — zero model dispatches,
        so TTFT on a repeat prompt is pure sampling. Miss: prefill,
        install, and admit the fresh pages into the cache."""
        cache = self._prefix_cache
        # Prefix entries are namespaced by adapter: the same prompt
        # prefilled through different merged trees has different KV.
        hit = (cache.get(req.prompt, namespace=req.adapter)
               if cache is not None else None)
        if hit is not None:
            pages, n, probs = hit
            self.stepper.install_shared(slot, pages, n)
            _m.PREFIX_CACHE_HITS.labels(model=self.model_name).inc()
            req.ledger_rec.set_prefix_hit(True)
            req.ledger_rec.mark("prefix_hit")
        else:
            # parent_ctx is explicit: the decode-loop thread has no
            # enclosing span stack to inherit from.
            t_pf = time.perf_counter_ns()
            with _obs.tracer.span("serving.prefill", cat="serving",
                                  parent_ctx=req.ctx,
                                  model=self.model_name, pad_to=pad_to):
                self.stepper.set_params(req.params)
                probs, slot_state, n = self.stepper.prefill(req.prompt,
                                                            pad_to=pad_to)
                self.stepper.install(slot, slot_state, n)
            # Prefill is a single-request dispatch: its wall time is
            # attributed whole (no co-batched requests to split with).
            prefill_s = (time.perf_counter_ns() - t_pf) / 1e9
            self._disp_prefill.inc(prefill_s)
            req.ledger_rec.add_device_seconds(prefill_s)
            req.ledger_rec.mark("prefill")
            if cache is not None:
                _m.PREFIX_CACHE_MISSES.labels(model=self.model_name).inc()
                req.ledger_rec.set_prefix_hit(False)
                cache.admit(req.prompt, self.stepper.pool.pages_of(slot),
                            n, probs, namespace=req.adapter)
        if self._draft_stepper is not None:
            # The draft always prefills (its dense cache has no pages to
            # share) — it is the small model, so a prefix hit still skips
            # the expensive target prefill.
            _, dstate, dn = self._draft_stepper.prefill(req.prompt,
                                                        pad_to=pad_to)
            self._draft_stepper.install(slot, dstate, dn)
        return probs

    def _admit(self, slot: int, req: GenerationRequest) -> bool:
        """Prefill + install + first token. Returns True when the request
        stays active in `slot` (False: finished or failed at admission)."""
        with _obs.tracer.span("serving.admit", cat="serving"):
            return self._admit_inner(slot, req)

    def _admit_inner(self, slot: int, req: GenerationRequest) -> bool:
        pad_to = next(b for b in self.prompt_buckets
                      if len(req.prompt) <= b)
        if req.ctx is not None:
            # Retroactive admission-wait span: submit -> this step
            # boundary, parented to the replica request span.
            _obs.tracer.complete(
                "serving.admission_wait", req.t_submit_ns,
                time.perf_counter_ns() - req.t_submit_ns, cat="serving",
                parent_ctx=req.ctx, model=self.model_name)
        req.ledger_rec.set_queue_wait(
            (time.perf_counter_ns() - req.t_submit_ns) / 1e9)
        req.ledger_rec.mark("admitted")
        try:
            probs = self._install_prompt(slot, req, pad_to)
        except Exception as e:
            req.error = f"{type(e).__name__}: {e}"
            req.event.set()
            return False
        _m.TTFT_SECONDS.labels(model=self.model_name).observe(
            time.monotonic() - req.t_submit)
        with _obs.tracer.span("serving.sample", cat="serving"):
            self._sample(req, probs)
        req.ledger_rec.mark("first_token")
        if req.done:
            self._clear_slot(slot)
            req.event.set()
            return False
        return True

    def _clear_slot(self, slot: int) -> None:
        self.stepper.clear(slot)
        if self._draft_stepper is not None:
            self._draft_stepper.clear(slot)

    def _retire(self, slot: int, req: GenerationRequest,
                timed_out: bool = False) -> None:
        if self.kv == "paged":
            req.ledger_rec.add_cow_copies(
                self.stepper.pool.cow_count(slot))
        if req.ctx is not None and req._rounds:
            # One span a request, first round's start to last round's
            # end: the federated request tree keeps router -> replica ->
            # admission_wait -> prefill -> decode at O(1) spans a request.
            _obs.tracer.complete(
                "serving.decode", req._decode_t0_ns,
                self._round_end_ns - req._decode_t0_ns, cat="serving",
                parent_ctx=req.ctx, model=self.model_name,
                rounds=req._rounds,
                tokens=len(req.ids) - len(req.prompt) - 1)
        self._clear_slot(slot)
        if timed_out:
            self._finish_timeout(req)
        else:
            req.event.set()

    def _credit_round(self, active: Dict[int, GenerationRequest],
                      t0_ns: int, step_hist) -> None:
        """Cost attribution choke point: one round's wall time (begun at
        `t0_ns`, ending now) splits EVENLY across the co-batched slots
        (every slot rides every dispatch of the round, including other
        groups' rewinds)."""
        self._round_end_ns = time.perf_counter_ns()
        round_s = (self._round_end_ns - t0_ns) / 1e9
        step_hist.observe(round_s)
        self._disp_decode.inc(round_s)
        share = round_s / len(active)
        for req in active.values():
            req.ledger_rec.add_device_seconds(share)
            if not req._rounds:
                req._decode_t0_ns = t0_ns
            req._rounds += 1

    def _loop(self) -> None:
        active: Dict[int, GenerationRequest] = {}
        try:
            self._loop_inner(active)
        except Exception as e:
            # Decode-loop death strands every active sequence: dump the
            # flight bundle, fail the callers, then let the thread die.
            _obs.flight.on_crash("serving.decode_loop", e)
            for req in active.values():
                req.error = f"{type(e).__name__}: {e}"
                req.event.set()
            raise

    def _loop_inner(self, active: Dict[int, GenerationRequest]) -> None:
        free = list(reversed(range(self.slots)))
        busy_gauge = _m.DECODE_SLOTS_BUSY.labels(model=self.model_name)
        step_hist = _m.DECODE_STEP_SECONDS.labels(model=self.model_name)
        while True:
            if self._abort is not None and active:
                # Group failure: fail the batch at this step boundary.
                for slot, req in list(active.items()):
                    req.error = self._abort
                    req.event.set()
                    self._clear_slot(slot)
                    free.append(slot)
                active.clear()
            # Admission happens ONLY here — a step boundary. Continuous
            # mode refills any free slot mid-flight; drain mode waits for
            # the whole batch to finish (the control arm for the bench).
            admitting = bool(free) and (self.mode == "continuous"
                                        or not active)
            while admitting and free:
                try:
                    req = self._queue.get(timeout=None if not active
                                          else 0.0)
                except queue.Empty:
                    break
                if req is None:
                    self._shutdown(active)
                    return
                if self._abort is not None:
                    req.error = self._abort
                    req.event.set()
                    continue
                now = time.monotonic()
                if req.cancelled or (req.deadline is not None
                                     and now > req.deadline):
                    self._finish_timeout(req)
                    continue
                slot = free.pop()
                if self._admit(slot, req):
                    active[slot] = req
                else:
                    free.append(slot)
            busy_gauge.set(len(active))
            if not active:
                continue
            if self._draft_stepper is not None:
                self._spec_round(active, free, step_hist)
                continue
            t0_ns = time.perf_counter_ns()
            with _obs.tracer.span("serving.decode_round", cat="serving",
                                  slots=len(active)):
                rows = self._decode_round(active)
            self._credit_round(active, t0_ns, step_hist)
            now = time.monotonic()
            with _obs.tracer.span("serving.sample", cat="serving"):
                for slot, req in list(active.items()):
                    if req.cancelled or (req.deadline is not None
                                         and now > req.deadline):
                        self._retire(slot, req, timed_out=True)
                        del active[slot]
                        free.append(slot)
                        continue
                    self._sample(req, rows[slot])
                    if req.done:
                        self._retire(slot, req)
                        del active[slot]
                        free.append(slot)

    def _decode_round(self, active: Dict[int, GenerationRequest]):
        """One decode step for every active slot, grouped by adapter.
        Returns `{slot: next-token distribution}`.

        All requests on one adapter (the overwhelmingly common round,
        including the no-adapter case) are ONE dispatch — identical to
        the pre-adapter loop. Mixed rounds dispatch once per adapter
        group: each group's `step` advances EVERY slot (the batch is the
        whole slot bank), so after each dispatch the caches rewind —
        slots whose own group has run stay at `L+1` (their position-L KV
        row was just written with the RIGHT params; later groups deposit
        garbage at `L+1`, beyond the cursor and overwritten next round),
        slots still waiting drop back to `L` so their group rewrites
        position L correctly. A slot's returned row always comes from its
        own group's dispatch."""
        tokens = [active[s].ids[-1] if s in active else 0
                  for s in range(self.slots)]
        order: List[Optional[str]] = []
        groups: Dict[Optional[str], List[int]] = {}
        for s in sorted(active):
            a = active[s].adapter
            if a not in groups:
                groups[a] = []
                order.append(a)
            groups[a].append(s)
        if len(order) == 1:
            self.stepper.set_params(active[groups[order[0]][0]].params)
            probs = self.stepper.step(tokens)
            return {s: probs[s] for s in active}
        L = [len(active[s].ids) - 1 if s in active else 0
             for s in range(self.slots)]
        rows: Dict[int, object] = {}
        done: set = set()
        for a in order:
            gslots = groups[a]
            self.stepper.set_params(active[gslots[0]].params)
            probs = self.stepper.step(tokens)
            done.update(gslots)
            for s in gslots:
                rows[s] = probs[s]
            self.stepper.rewind_all([L[s] + 1 if s in done else L[s]
                                     for s in range(self.slots)])
        return rows

    def _spec_round(self, active: Dict[int, GenerationRequest],
                    free: List[int], step_hist) -> None:
        """One speculative decode round (Leviathan et al., ICML 2023,
        greedy acceptance).

        Invariant at entry: BOTH steppers have consumed exactly
        `ids[:-1]` for every active slot (the last sampled token has not
        been fed yet). The round feeds `[x, d1..dk]` — the pending token
        plus k draft proposals — through ONE target `step_k` dispatch;
        row j of the result is the target's distribution after
        `ids + d1..dj`, so a greedy slot emits tokens left to right while
        the target's argmax keeps agreeing with the draft (+1 bonus token
        from the first disagreeing row: that sample is still drawn from a
        correctly-conditioned target distribution). Both steppers are then
        REWOUND to `len(ids) - 1`, restoring the invariant regardless of
        how many rows were accepted — rejected rows stay in the caches
        beyond the cursor, masked until overwritten. Greedy output is
        therefore bit-identical to the non-speculative scheduler; the
        only thing speculation changes is how many target dispatches the
        same token sequence costs.

        Non-greedy slots emit one token per round from row 0 (exactly the
        distribution a plain `step` would have produced), so sampled
        requests stay correct — they just don't accelerate.
        """
        draft = self._draft_stepper
        # Clamp k so target writes (positions len(ids)-1 .. len(ids)+k-1)
        # never cross capacity — a clamped page index would corrupt the
        # last page.
        k = max(0, min(self._spec_k,
                       min(self.capacity - len(r.ids)
                           for r in active.values())))
        x = [active[s].ids[-1] if s in active else 0
             for s in range(self.slots)]
        tok = np.zeros((self.slots, k + 1), np.int64)
        tok[:, 0] = x
        t0_ns = time.perf_counter_ns()
        with _obs.tracer.span("serving.decode_round", cat="serving",
                              slots=len(active), k=k):
            for j in range(k):
                dprobs = draft.step(tok[:, j])
                tok[:, j + 1] = dprobs.argmax(axis=-1)
            if k:
                # Feed the last proposal so the draft has consumed
                # tok[:, :k+1] too; the result is unused (rewound below
                # either way).
                draft.step(tok[:, k])
            probs = self.stepper.step_k(tok)
        self._credit_round(active, t0_ns, step_hist)
        spec_acc = _m.SPECULATIVE_TOKENS.labels(model=self.model_name,
                                                outcome="accepted")
        spec_rej = _m.SPECULATIVE_TOKENS.labels(model=self.model_name,
                                                outcome="rejected")
        now = time.monotonic()
        with _obs.tracer.span("serving.sample", cat="serving"):
            for slot, req in list(active.items()):
                if req.cancelled or (req.deadline is not None
                                     and now > req.deadline):
                    self._retire(slot, req, timed_out=True)
                    del active[slot]
                    free.append(slot)
                    continue
                greedy = req.temperature <= 0
                accepted = 0
                for j in range(k + 1):
                    t = self._sample(req, probs[slot, j])
                    if (req.done or not greedy or j >= k
                            or t != int(tok[slot, j + 1])):
                        break
                    accepted += 1
                if greedy and k:
                    spec_acc.inc(accepted)
                    spec_rej.inc(k - accepted)
                    req.ledger_rec.add_speculative(accepted, k - accepted)
                if req.done:
                    self._retire(slot, req)
                    del active[slot]
                    free.append(slot)
        # Restore the invariant: truncate both caches back to the tokens
        # actually kept (retired slots to 0 — their pool pages are
        # already freed and their table rows zeroed).
        lengths = [len(active[s].ids) - 1 if s in active else 0
                   for s in range(self.slots)]
        self.stepper.rewind_all(lengths)
        draft.rewind_all(lengths)

    def _shutdown(self, active: Dict[int, GenerationRequest]) -> None:
        for slot, req in active.items():
            req.error = "server stopped"
            req.event.set()
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                return
            if req is not None:
                req.error = "server stopped"
                req.event.set()
