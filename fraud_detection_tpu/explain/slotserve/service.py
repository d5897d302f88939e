"""Slotserve: slot-based continuous-batching LLM serving for explanations.

The fixed-batch explain path (``OnPodBackend.generate_batch`` →
``models/llm.py generate_tokens_batch``) decodes a flagged batch behind ONE
barrier: every row pays device steps until the SLOWEST row finishes, the
batch pads up to a power-of-two bucket (dummy rows decode garbage), and a
row flagged while a batch is in flight waits for the whole batch to drain.
At ~18.6 expl/s measured against a classifier doing ~100k rows/s, that
barrier is why explanations were sampled, not guaranteed.

This module is the iteration-level alternative (Orca, OSDI '22; slot/KV
management in the spirit of vLLM, SOSP '23):

* a fixed pool of **decode slots** over ONE persistent pool of fixed-size
  KV pages and per-slot page tables (``PagedSlotDecoder``, models/llm.py
  ``paged_slot_prefill``/``paged_decode_window``): block-granular
  allocation instead of a worst-case reservation per slot, the shared
  explain preamble prefilled ONCE into refcounted read-only pages
  (copy-on-write on the partial page), and pool exhaustion preempts the
  newest admit as an accounted ``kv_pages_exhausted`` drop;
* a bounded **admission queue**: newly flagged rows admit at iteration
  boundaries into every free slot the queue and the page pool can fill —
  prefill interleaves with decode, no fixed-batch barrier, and overload
  drops the OLDEST queued request with honest accounting (``admitted ==
  completed + dropped`` is a pinned invariant);
* per-slot retirement: a row that hits EOS frees its slot THAT iteration
  and the next queued row takes it — wall clock tracks the MEAN emission
  length, not the max, and slots never decode padding rows;
* one host sync per iteration, B tokens wide (the continuous-batching
  amortization).

Surfaces: the ``LLMBackend`` protocol (``chat``/``generate``/
``generate_batch``) so the service drops in anywhere ``OnPodBackend`` does
(incl. behind the PR 1 circuit breaker — explain/circuit.py forwards
``explain_rows`` too), plus :meth:`SlotServeService.submit_rows` /
:meth:`~SlotServeService.explain_rows` (tickets back unresolved / wait for
them all) which also take the rows' PR 10 trace cids so every explained
row's ``chain(cid)`` shows poll→flag→explain→annotate with its slot and
queue wait. :func:`make_slot_explain_hook` adapts them to the engine's
``explain_batch_fn`` shape; the async annotation lane passes cids through
when the hook advertises ``accepts_cids`` and, where it advertises
``submit_rows``, keeps a window of rows' tickets in the service and
delivers each as it resolves (stream/annotations.py).

Degradation contract: a decoder failure fails every in-flight and queued
request with :class:`~fraud_detection_tpu.explain.backends.BackendError`
(the breaker counts it; the hook converts it into an ``[explanation
unavailable: ...]`` marker so flagged rows stay ACCOUNTED in the
annotations topic even mid-outage). ``snapshot()`` is the
``health()["explain"]`` block (schema pinned in tests/test_slotserve.py,
FC301-checked).
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Callable, List, Optional, Sequence

from fraud_detection_tpu.explain.backends import (BackendError, ChatMessage,
                                                  frame_prompt)
from fraud_detection_tpu.explain.onpod import flatten_chat
from fraud_detection_tpu.explain.slotserve.decode import PagedSlotDecoder
from fraud_detection_tpu.obs.trace import (STAGE_SETUP_SERVICE,
                                           STAGE_SETUP_WARM, setup_span)
from fraud_detection_tpu.sched.sketch import LatencySketch
from fraud_detection_tpu.utils import get_logger

log = get_logger("explain.slotserve")

DROPPED_MARKER = "[explanation dropped: {reason}]"
UNAVAILABLE_MARKER = "[explanation unavailable: {reason}]"


def shared_explain_prefix() -> str:
    """The template preamble every slotserve analysis prompt opens with:
    chat framing + system prompt + the analysis template's static first
    line. Derived through the SAME ``flatten_chat``/``frame_prompt``/
    ``ANALYSIS_PREAMBLE`` pieces the serving paths use, so it can never
    drift from what ``explain_rows`` actually renders."""
    from fraud_detection_tpu.explain.prompts import ANALYSIS_PREAMBLE

    framed = flatten_chat(frame_prompt(ANALYSIS_PREAMBLE))
    return framed[: framed.index(ANALYSIS_PREAMBLE) + len(ANALYSIS_PREAMBLE)]


class _SlotRequest:
    """One admitted prompt's lifecycle record. Queue/result fields mutate
    under the service's condition; the ``done`` event is the completion
    latch every waiter blocks on."""

    __slots__ = ("tokens", "max_new", "temperature", "cid", "submitted_at",
                 "submitted_wall", "first_token_at", "out", "text", "dropped",
                 "error", "done", "slot", "_watchers", "_watch_lock")

    def __init__(self, tokens, max_new: int, temperature: float,
                 cid: Optional[str], submitted_at: float,
                 submitted_wall: Optional[float] = None):
        self.tokens = tokens
        self.max_new = max_new
        self.temperature = temperature
        self.cid = cid
        self.submitted_at = submitted_at
        # The same moment on the tracer's clock (traced rows only): where
        # the row's ``slot_wait`` span starts.
        self.submitted_wall = submitted_wall
        self.first_token_at: Optional[float] = None
        self.out: List[int] = []
        self.text: Optional[str] = None
        self.dropped: Optional[str] = None      # drop reason when dropped
        self.error: Optional[BaseException] = None
        self.done = threading.Event()
        self.slot: Optional[int] = None
        self._watchers: List[Callable] = []
        self._watch_lock = threading.Lock()

    def resolve(self) -> None:
        """Latch ``done`` and tell whoever asked to be told. The result
        fields (``text`` / ``dropped`` / ``error``) are set before this."""
        with self._watch_lock:
            self.done.set()
            watchers, self._watchers = self._watchers, []
        for fn in watchers:
            try:
                fn(self)
            except Exception:  # noqa: BLE001 — a watcher must not fail the lane
                log.exception("slot ticket's done callback raised")

    def add_done_callback(self, fn: Callable) -> None:
        """Call ``fn(ticket)`` once the request has resolved: at once on
        the caller's thread if it already has, else on the thread that
        resolves it (the slot lane's; a submitter's or closer's for a
        drop). ``fn`` must be quick: the annotation lane's only notes the
        ticket and wakes its worker."""
        with self._watch_lock:
            if not self.done.is_set():
                self._watchers.append(fn)
                return
        fn(self)

    def wait(self, timeout: Optional[float]) -> str:
        """Block until the request resolves; returns the explanation text
        (a ``DROPPED_MARKER`` string when the queue dropped it), raises
        BackendError on decoder failure or timeout."""
        if not self.done.wait(timeout):
            raise BackendError(
                f"slotserve request timed out after {timeout:.1f}s")
        if self.error is not None:
            raise BackendError(
                f"slotserve decoder failed: {self.error!r}") from self.error
        if self.dropped is not None:
            return DROPPED_MARKER.format(reason=self.dropped)
        return self.text or ""


class SlotServeService:
    """Continuous-batching explanation service over one slot pool.

    ``lm``: a models/llm.py ``LanguageModel`` (pass ``lm.quantized()`` for
    int8 weights — decode is weight-streaming bound, so the PR 7 per-block
    quantizer is the one knob that moves tokens/sec; params already placed
    on a mesh via ``shard_params`` ride along unchanged). One worker
    thread ("slotserve-lane") owns the decoder; every public surface is
    callable from any thread. ``kv_pages`` caps the page pool (default: a
    worst-case row for every slot); ``shared_prefix`` prefills the explain
    preamble once where it fits. ``prefill_per_iter`` caps the rows admitted
    at one boundary (default: none beyond the free slots and the pages).
    ``paged`` is kept for the one caller that still states it
    (benchmark/desk.py): the pool is always paged, and ``paged=False``
    raises.
    """

    def __init__(self, lm, *, slots: int = 8, max_queue: int = 1024,
                 max_new_tokens: int = 128, prompt_width: int = 384,
                 prompt_bucket: int = 64,
                 prefill_per_iter: Optional[int] = None,
                 decode_window: int = 16,
                 temperature: float = 0.0, seed: int = 0,
                 rowtrace=None, wait_timeout: float = 600.0,
                 warm: bool = True, paged: bool = True,
                 page_size: int = 64, kv_pages: Optional[int] = None,
                 shared_prefix: bool = True,
                 clock: Callable[[], float] = time.perf_counter):
        if max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        if prefill_per_iter is not None and prefill_per_iter < 1:
            raise ValueError(
                f"prefill_per_iter must be >= 1, got {prefill_per_iter}")
        if decode_window < 1:
            raise ValueError(
                f"decode_window must be >= 1, got {decode_window}")
        if not paged:
            raise ValueError("the slot lane has one pool, and it is paged: "
                             "drop paged=False")
        with setup_span(STAGE_SETUP_SERVICE) as phase:
            self._decoder = PagedSlotDecoder(lm, slots,
                                             prompt_width=prompt_width,
                                             max_new_tokens=max_new_tokens,
                                             prompt_bucket=prompt_bucket,
                                             page_size=page_size,
                                             total_pages=kv_pages)
            phase.detail = f"slots={slots} pages={self._decoder.total_pages}"
            if shared_prefix:
                prefix = shared_explain_prefix()
                lp = len(lm.tokenizer.encode(prefix))
                n_prefix = -(-lp // self._decoder.page_size)
                fits = (lp < self._decoder.prompt_width
                        and self._decoder.total_pages
                        >= self._decoder.n_view + n_prefix)
                if fits:
                    self._decoder.set_prefix(prefix, span=lambda stage: (
                        setup_span(stage, detail=f"tokens={lp}")))
                else:
                    log.warning(
                        "shared explain prefix (%d tokens, %d pages) does "
                        "not fit prompt_width %d / pool %d; serving WITHOUT "
                        "prefix sharing", lp, n_prefix,
                        self._decoder.prompt_width,
                        self._decoder.total_pages)
            if warm:
                with setup_span(STAGE_SETUP_WARM,
                                detail=f"steps={decode_window}"):
                    self._decoder.warm(decode_window)
        import numpy as np

        self.slots = slots
        self.max_queue = max_queue
        self.max_new_tokens = max_new_tokens
        self.prefill_per_iter = prefill_per_iter
        # Admission granularity: free slots refill every `decode_window`
        # fused steps (rows retiring mid-window cost at most window-1 idle
        # steps) — the knob trading scheduling granularity against
        # per-program dispatch overhead.
        self.decode_window = decode_window
        self.temperature = temperature
        self.wait_timeout = wait_timeout
        self._rowtrace = rowtrace
        self._clock = clock
        self._seed = seed
        # --- worker-only slot state (never read off the lane thread) ---
        self._slot_req: List[Optional[_SlotRequest]] = [None] * slots
        self._lens = np.zeros(slots, np.int32)
        self._last_tok = np.full(slots, lm.cfg.EOS, np.int32)
        self._active_arr = np.zeros(slots, bool)
        self._temps = np.zeros(slots, np.float32)
        self._admit_seq = np.zeros(slots, np.int64)  # preemption order key
        self._retired: List[int] = []       # slots finished this iteration
        self._seq = 0                       # device-call counter (seeds)
        self._admits = 0                    # monotone admission counter
        # --- shared state (everything below lives under _cv) ---
        self._cv = threading.Condition()
        self._q: List[_SlotRequest] = []
        self._free = list(range(slots))
        self._busy = 0
        self._closed = False
        self._admitted = 0
        self._completed = 0
        self._dropped = 0
        self._errors = 0
        self._truncated = 0
        self._iterations = 0
        self._prefills = 0
        # Boundaries that admitted a row, and those that stopped with a
        # slot free and a request queued (the page gate, or a stated cap).
        self._admit_rounds = 0
        self._admit_gated = 0
        self._decode_steps = 0
        self._tokens_out = 0
        self._occ_sum = 0
        # Free slot-steps of every decode window, put down to the queue's
        # state at the window's boundary: nothing queued (starved from
        # upstream) or requests waiting (admission-limited). With
        # ``_occ_sum`` they partition ``decode_steps * slots``.
        self._steps_starved = 0
        self._steps_backlogged = 0
        self._started_at: Optional[float] = None
        self._lat = LatencySketch()         # submit -> complete (sec)
        self._first = LatencySketch()       # submit -> first token (sec)
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="slotserve-lane")
        self._thread.start()

    # ------------------------------------------------------------------
    # submission (any thread)
    # ------------------------------------------------------------------

    def submit(self, prompt: str, *, max_tokens: Optional[int] = None,
               temperature: Optional[float] = None,
               cid: Optional[str] = None) -> _SlotRequest:
        """Enqueue one (already framed) prompt; never blocks. Over
        capacity the OLDEST queued request drops (counted; its ticket
        resolves to a ``DROPPED_MARKER``) — under sustained overload the
        lane serves a sliding recent sample, like the annotation lane."""
        toks, truncated = self._decoder.encode_prompt(prompt)
        max_new = min(max_tokens or self.max_new_tokens, self.max_new_tokens)
        tr = self._rowtrace
        req = _SlotRequest(toks, max(1, max_new),
                           self.temperature if temperature is None
                           else temperature,
                           cid, self._clock(),
                           tr.wall() if tr is not None and cid is not None
                           else None)
        evicted: List[_SlotRequest] = []
        with self._cv:
            self._admitted += 1
            if truncated:
                self._truncated += 1
            if self._started_at is None:
                self._started_at = self._clock()
            if self._closed:
                self._dropped += 1
                evicted.append(req)
                req.dropped = "closed"
            else:
                while len(self._q) >= self.max_queue:
                    old = self._q.pop(0)
                    old.dropped = "queue_overflow"
                    self._dropped += 1
                    evicted.append(old)
                self._q.append(req)
                self._cv.notify()
        for old in evicted:
            if self._rowtrace is not None and old.cid is not None:
                self._rowtrace.record_event(old.cid, "explain", ok=False,
                                            detail=f"dropped:{old.dropped}")
            old.resolve()
        return req

    # ------------------------------------------------------------------
    # LLMBackend surface (+ explain_rows) — any thread, blocking
    # ------------------------------------------------------------------

    def chat(self, messages: Sequence[ChatMessage], *,
             temperature: float = 1.0, max_tokens: int = 1000) -> str:
        return self.submit(flatten_chat(messages), max_tokens=max_tokens,
                           temperature=temperature).wait(self.wait_timeout)

    def generate(self, prompt: str, *, temperature: float = 1.0,
                 max_tokens: int = 1000, system: Optional[str] = None) -> str:
        return self.chat(frame_prompt(prompt, system),
                         temperature=temperature, max_tokens=max_tokens)

    def generate_batch(self, prompts: Sequence[str], *,
                       temperature: float = 0.0,
                       max_tokens: int = 256) -> List[str]:
        """Positional batch interface (framing parity with
        ``OnPodBackend.generate_batch``): all prompts enter the admission
        queue at once and stream through the slots — FIFO admission, but
        completion order is per-row (short replies retire early and their
        slots refill), so the caller's wall is the mean, not the max."""
        reqs = [self.submit(flatten_chat(frame_prompt(p)),
                            max_tokens=max_tokens, temperature=temperature)
                for p in prompts]
        return [r.wait(self.wait_timeout) for r in reqs]

    def submit_rows(self, texts: Sequence[str], labels: Sequence[int],
                    confs: Sequence[float], *,
                    cids: Optional[Sequence[Optional[str]]] = None,
                    temperature: float = 0.0,
                    max_tokens: int = 128) -> List[_SlotRequest]:
        """Enqueue flagged rows WITH their trace identity and hand back
        their tickets unresolved; never blocks. Each row's analysis prompt
        is built here (same ``analysis_prompt`` + chat-template framing as
        every other path) and its cid rides into the slot, so the
        completed row's ``chain(cid)`` carries an "explain" span with slot
        + latency detail. The annotation lane's hook hands rows over
        through this and delivers each ticket as it resolves."""
        from fraud_detection_tpu.explain.prompts import analysis_prompt

        reqs = []
        for i, (text, label, conf) in enumerate(zip(texts, labels, confs)):
            prompt = flatten_chat(frame_prompt(
                analysis_prompt(text, label, conf)))
            reqs.append(self.submit(prompt, max_tokens=max_tokens,
                                    temperature=temperature,
                                    cid=cids[i] if cids else None))
        return reqs

    def explain_rows(self, texts: Sequence[str], labels: Sequence[int],
                     confs: Sequence[float], *,
                     cids: Optional[Sequence[Optional[str]]] = None,
                     temperature: float = 0.0,
                     max_tokens: int = 128) -> List[str]:
        """:meth:`submit_rows`, then wait for every ticket: the blocking
        form (the breaker forwards this one; ``warm`` paths call it)."""
        reqs = self.submit_rows(texts, labels, confs, cids=cids,
                                temperature=temperature,
                                max_tokens=max_tokens)
        return [r.wait(self.wait_timeout) for r in reqs]

    # ------------------------------------------------------------------
    # the slot lane (one worker thread)
    # ------------------------------------------------------------------

    def _run(self) -> None:
        while True:
            with self._cv:
                while not self._q and self._busy == 0 and not self._closed:
                    self._cv.wait(timeout=0.2)
                if self._closed and not self._q and self._busy == 0:
                    return
            try:
                self._iteration()
            except Exception as e:  # noqa: BLE001 — lane must fail honestly
                log.exception("slotserve iteration failed; failing all "
                              "in-flight and queued requests")
                self._fail_all(e)

    def _iteration(self) -> None:
        """One scheduler iteration: admissions land at the boundary FIRST
        (free slots fill before the pool advances), then one decode step
        moves every busy slot, then finished rows retire and free their
        slots for the next boundary."""
        with self._span("slot_iter"):
            with self._span("slot_admit") as span:
                self._admit_pending(span)
            self._decode_step()
            with self._span("slot_retire"):
                self._retire_done()
        with self._cv:
            self._iterations += 1

    def _span(self, stage: str, cid: Optional[str] = None):
        """One span of the loop's own chain (``slot-<iteration>``, or a
        row's ``cid``) when a tracer is attached: in the ring, and as
        ``fraud/<stage>`` on this thread's line of a profiler capture."""
        tr = self._rowtrace
        if tr is None:
            return contextlib.nullcontext()
        return tr.span(cid or f"slot-{self._iterations:x}", stage)

    def _admit_pending(self, span=None) -> None:
        """free → prefill: pop queued requests into every free slot the
        queue and the page pool can fill (or up to ``prefill_per_iter``,
        where a caller states one), prefill each prompt into its slot and
        emit the first sampled token. The free slots bound the round by
        themselves: a prefill only takes a slot that would otherwise spend
        the whole next window empty. ``span``, the boundary's open
        ``slot_admit`` span, gets ``rows= free= queued=``."""
        grabbed: List[tuple] = []
        pages_planned = 0
        cap = self.prefill_per_iter or self.slots
        with self._cv:
            free, queued = len(self._free), len(self._q)
            while self._free and self._q and len(grabbed) < cap:
                # Page-pool gate: stop admitting this boundary once the
                # free pages can't cover every grabbed prompt's table —
                # decode retirements free pages for the next boundary, so
                # nothing deadlocks.
                need = self._decoder.pages_needed(self._q[0].tokens)
                if self._decoder.pages_free < pages_planned + need:
                    break
                pages_planned += need
                req = self._q.pop(0)
                slot = self._free.pop()
                self._busy += 1
                # Claim the slot HERE, before any device call: if a
                # prefill below dies, the failure sweep (_fail_all) must
                # find every grabbed request on its slot — otherwise its
                # waiter would hang to timeout.
                self._slot_req[slot] = req
                req.slot = slot
                self._admits += 1
                self._admit_seq[slot] = self._admits
                grabbed.append((slot, req))
            if grabbed:
                self._admit_rounds += 1
            if self._free and self._q:
                self._admit_gated += 1
        if span is not None:
            span.detail = f"rows={len(grabbed)} free={free} queued={queued}"
        tr = self._rowtrace
        for slot, req in grabbed:
            self._seq += 1
            if tr is not None and req.submitted_wall is not None:
                # Queued for a slot: submit -> this row's own prefill call
                # (not the grant: rows granted together prefill in turn).
                tr.record_span(req.cid, "slot_wait",
                               max(0.0, tr.wall() - req.submitted_wall),
                               start=req.submitted_wall,
                               detail=f"slot={slot}")
            with self._span("prefill", req.cid):
                first = self._decoder.prefill(
                    slot, req.tokens, req.temperature,
                    self._seed + self._seq, span=self._span)
            if first is not None:
                self._first_token(req)
            self._lens[slot] = self._decoder.committed_len(len(req.tokens))
            self._temps[slot] = req.temperature
            self._active_arr[slot] = True
            with self._cv:
                self._prefills += 1
            # A model that diffuses over blocks serves nothing before its
            # first block commits.
            if first is not None:
                self._last_tok[slot] = first
                self._emit(slot, first)

    def _first_token(self, req: _SlotRequest) -> None:
        now = self._clock()
        req.first_token_at = now
        self._first.add(max(0.0, now - req.submitted_at))

    def _decode_step(self) -> None:
        """prefill/decode → decode: one fused decode window for the whole
        pool. The host replays the device's freeze rule column-by-column,
        so each row's emission stream is exactly the single-step one. A
        model that diffuses over blocks serves a row's tokens a committed
        block at a time (``out[slot, :counts[slot]]``), and the window's
        steps are block forwards."""
        import numpy as np

        busy_rows = np.flatnonzero(self._active_arr).tolist()
        if not busy_rows:
            return
        # Host side of the iteration boundary: every busy row's page table
        # must cover this window's writes BEFORE the compiled program runs.
        with self._span("slot_grow"):
            self._ensure_window_pages(busy_rows)
        busy_rows = np.flatnonzero(self._active_arr).tolist()
        if not busy_rows:
            return
        remaining = np.zeros(self.slots, np.int32)
        for slot in busy_rows:
            req = self._slot_req[slot]
            remaining[slot] = max(0, req.max_new - len(req.out))
        self._seq += 1
        counts = None
        if self._decoder.diffusion is not None:
            out, counts, new_lens, steps_run, n_act = self._decoder.block_step(
                self._lens, self._active_arr, remaining, self._temps,
                self._seed + self._seq, self.decode_window, span=self._span)
        else:
            out, new_lens, steps_run, n_act = self._decoder.step(
                self._last_tok, self._lens, self._active_arr, remaining,
                self._temps, self._seed + self._seq, self.decode_window,
                span=self._span)
        self._lens = new_lens
        free = steps_run * self.slots - n_act
        with self._cv:
            self._decode_steps += steps_run
            self._occ_sum += n_act
            # The steps a row leaves over when it finishes mid-window go
            # with the boundary's class too.
            if self._q:
                self._steps_backlogged += free
            else:
                self._steps_starved += free
        eos = self._decoder.cfg.EOS
        with self._span("slot_emit"):
            for slot in busy_rows:
                req = self._slot_req[slot]
                n = out.shape[1] if counts is None else int(counts[slot])
                if n and req.first_token_at is None:
                    self._first_token(req)
                for j in range(n):
                    tok = int(out[slot, j])
                    req.out.append(tok)
                    self._last_tok[slot] = tok
                    if tok == eos or len(req.out) >= req.max_new:
                        self._active_arr[slot] = False
                        self._retired.append(slot)
                        break

    def _ensure_window_pages(self, busy_rows: List[int]) -> None:
        """Grow each busy slot's page table to cover ``lens +
        decode_window``. On pool exhaustion, preempt the NEWEST-admitted
        active slot (its waiter resolves to an accounted
        ``kv_pages_exhausted`` drop — oldest work survives, matching the
        queue's drop-OLDEST-first... inverse: admitted rows beat queued
        ones, and among admitted the most recent yields) and retry; a
        preempted row's pages free immediately, so the pass terminates
        (the pool is validated to hold at least one worst-case row)."""
        for slot in busy_rows:
            while self._active_arr[slot] and not self._decoder.grow_for_window(
                    slot, int(self._lens[slot]), self.decode_window):
                victims = [s for s in busy_rows if self._active_arr[s]]
                victim = max(victims, key=lambda s: self._admit_seq[s])
                self._preempt(victim)

    def _preempt(self, slot: int) -> None:
        """Evict one in-flight row to reclaim its pages: accounted drop
        (``admitted == completed + dropped`` holds), waiter resolved with
        the drop marker, slot + pages released."""
        req = self._slot_req[slot]
        req.dropped = "kv_pages_exhausted"
        with self._cv:
            self._dropped += 1
        if self._rowtrace is not None and req.cid is not None:
            self._rowtrace.record_event(req.cid, "explain", ok=False,
                                        detail="dropped:kv_pages_exhausted")
        log.warning("page pool exhausted: preempting slot %d "
                    "(%d tokens emitted) to free its pages",
                    slot, len(req.out))
        self._release(slot)
        req.resolve()

    def _emit(self, slot: int, tok: int) -> None:
        """Record one prefill-emitted token; a row whose FIRST token is
        already terminal (EOS, or a 1-token budget) never enters the
        decode set — its slot frees at this very boundary."""
        req = self._slot_req[slot]
        req.out.append(tok)
        if tok == self._decoder.cfg.EOS or len(req.out) >= req.max_new:
            self._active_arr[slot] = False
            self._retired.append(slot)

    def _retire_done(self) -> None:
        """decode → drain → free: finalize every finished row (decode the
        text, resolve its waiter, trace it) BEFORE its slot returns to
        the free pool — a reader can never observe a freed slot whose row
        is still unresolved."""
        retired, self._retired = self._retired, []
        for slot in retired:
            req = self._slot_req[slot]
            self._complete(slot, req)
            self._release(slot)

    def _complete(self, slot: int, req: _SlotRequest) -> None:
        req.text = self._decoder.decode_text(req.out)
        dt = max(0.0, self._clock() - req.submitted_at)
        with self._cv:
            self._completed += 1
            self._tokens_out += len(req.out)
            self._lat.add(dt)
        if self._rowtrace is not None and req.cid is not None:
            wait_ms = round(1e3 * max(0.0, (req.first_token_at or dt)
                                      - req.submitted_at), 2)
            # The one END-stamped span (docs/observability.md): the
            # benchmark reads this span's ``start`` as the row's done time.
            self._rowtrace.record_span(
                req.cid, "explain", dt, start=self._rowtrace.wall(),
                detail=f"slot={slot} tokens={len(req.out)} "
                       f"admit_ms={wait_ms}")
        req.resolve()

    def _release(self, slot: int) -> None:
        # Pages first, slot second: a slot on the free list ALWAYS has an
        # empty page table (the page-lifecycle obligation FC503 checks).
        self._decoder.release_slot(slot)
        self._slot_req[slot] = None
        self._lens[slot] = 0
        self._last_tok[slot] = self._decoder.cfg.EOS
        self._active_arr[slot] = False
        with self._cv:
            self._busy -= 1
            self._free.append(slot)

    def _fail_all(self, exc: BaseException) -> None:
        """Decoder failure: resolve EVERY in-flight and queued request with
        the error (waiters raise BackendError — the breaker's food), reset
        the pool. The lane stays up: a later request retries the device."""
        failed: List[_SlotRequest] = []
        for slot in range(self.slots):
            req = self._slot_req[slot]
            if req is not None:
                req.error = exc
                failed.append(req)
            self._slot_req[slot] = None
            self._lens[slot] = 0
            self._last_tok[slot] = self._decoder.cfg.EOS
            self._active_arr[slot] = False
        self._retired = []
        # The failed rows' page tables go with them — the allocator
        # identity must hold across the reset, not leak into the retry.
        self._decoder.reset_slots()
        with self._cv:
            drained, self._q = self._q, []
            for req in drained:
                req.error = exc
                failed.append(req)
            self._errors += len(failed)
            self._dropped += len(failed)
            self._busy = 0
            self._free = list(range(self.slots))
        for req in failed:
            if self._rowtrace is not None and req.cid is not None:
                self._rowtrace.record_event(req.cid, "explain", ok=False,
                                            detail=type(exc).__name__)
            req.resolve()

    # ------------------------------------------------------------------
    # lifecycle + observability (any thread)
    # ------------------------------------------------------------------

    def set_rowtrace(self, rowtrace) -> None:
        """Attach (or replace) the tracer completed rows report into —
        serve.py builds tracers after the service exists. A plain
        reference swap: the lane reads the current value per completion."""
        with self._cv:
            self._rowtrace = rowtrace

    def drain(self, timeout: float = 60.0) -> bool:
        """Block until queue empty and every slot free (or timeout)."""
        deadline = self._clock() + timeout
        while self._clock() < deadline:
            with self._cv:
                if not self._q and self._busy == 0:
                    return True
            time.sleep(0.005)
        return False

    def close(self, timeout: float = 60.0) -> bool:
        """Drain best-effort, then stop the lane. Residual queued requests
        resolve as dropped ("closed", counted); True = clean shutdown."""
        drained = self.drain(timeout)
        with self._cv:
            residual, self._q = self._q, []
            for req in residual:
                req.dropped = "closed"
            self._dropped += len(residual)
            self._closed = True
            self._cv.notify()
        for req in residual:
            req.resolve()
        self._thread.join(timeout=min(10.0, max(0.2, timeout)))
        if not self._thread.is_alive():
            # Quiescence: the lane is down, every slot released — return
            # every page (prefix base refs included). Leaks are recorded
            # by the decoder, not raised here.
            self._decoder.close()
        return drained and not residual and not self._thread.is_alive()

    def snapshot(self) -> dict:
        """The ``health()["explain"]`` block (schema pinned in
        tests/test_slotserve.py SLOTSERVE_BLOCK_SCHEMA, FC301-checked)."""
        with self._cv:
            busy = self._busy
            queue_depth = len(self._q)
            admitted, completed = self._admitted, self._completed
            dropped, errors = self._dropped, self._errors
            truncated = self._truncated
            iterations, prefills = self._iterations, self._prefills
            admit_rounds, admit_gated = self._admit_rounds, self._admit_gated
            decode_steps, tokens_out = self._decode_steps, self._tokens_out
            occ_sum, started = self._occ_sum, self._started_at
            starved, backlogged = (self._steps_starved,
                                   self._steps_backlogged)
            lat_p50 = self._lat.quantile(0.50)
            lat_p99 = self._lat.quantile(0.99)
            adm_p50 = self._first.quantile(0.50)
            adm_p99 = self._first.quantile(0.99)
        elapsed = (None if started is None
                   else max(1e-9, self._clock() - started))
        return {
            "slots": self.slots,
            "busy": busy,
            "free": self.slots - busy,
            "queue_depth": queue_depth,
            "admitted": admitted,
            "completed": completed,
            "dropped": dropped,
            "errors": errors,
            "truncated": truncated,
            "expl_per_s": (None if elapsed is None
                           else round(completed / elapsed, 2)),
            "latency_ms": {
                "p50": None if lat_p50 is None else round(lat_p50 * 1e3, 2),
                "p99": None if lat_p99 is None else round(lat_p99 * 1e3, 2)},
            "admit_to_first_token_ms": {
                "p50": None if adm_p50 is None else round(adm_p50 * 1e3, 2),
                "p99": None if adm_p99 is None else round(adm_p99 * 1e3, 2)},
            "occupancy": (round(occ_sum / (decode_steps * self.slots), 4)
                          if decode_steps else None),
            "iterations": iterations,
            "prefills": prefills,
            # Boundaries that admitted a row (prefills / admit_rounds = rows
            # a round), and those that stopped with a slot free and a
            # request queued: the page gate, or a stated prefill_per_iter.
            "admit_rounds": admit_rounds,
            "admit_gated": admit_gated,
            # ... whose suffix was long enough for the flash kernel
            # (llm.prefill_takes_flash, the rule the program is traced by).
            "prefills_flash": self._decoder.prefills_flash,
            # ... and the prefills and decode windows whose expert tiles ran
            # through the Pallas kernel (llm.experts_take_kernel, likewise).
            "prefills_expert_kernel": self._decoder.prefills_expert_kernel,
            "windows_expert_kernel": self._decoder.windows_expert_kernel,
            "decode_steps": decode_steps,
            # decode_steps * slots, partitioned: a row decoded / the slot
            # was free with nothing queued / free with requests waiting.
            "slot_steps_occupied": occ_sum,
            "slot_steps_starved": starved,
            "slot_steps_backlogged": backlogged,
            "tokens_out": tokens_out,
            "kv_bytes": self._decoder.kv_bytes,
            # The page pool.
            "kv_pages": self._decoder.kv_pages,
            "page_bytes": self._decoder.page_bytes,
            "pages_free": self._decoder.pages_free,
            "prefix_pages": self._decoder.prefix_pages,
            "prefix_hits": self._decoder.prefix_hits,
            "cow_copies": self._decoder.cow_copies,
            "kv_bytes_saved_vs_contiguous":
                self._decoder.kv_bytes_saved_vs_contiguous,
            # What the model's own programs count (all zero for a dense
            # model): expert choices made by real tokens and those that
            # landed on an expert held here; distinct held experts read,
            # summed over decode steps and expert layers; the busiest and
            # the mean held expert's tokens, summed over prefills and expert
            # layers, and how the prefills' grouped product was sized (tile
            # steps, the rows they covered, distinct experts touched, held
            # picks: tiles / touched = reads of an expert's weights per
            # touched expert, picks / rows = tile fill), summed likewise;
            # state snapshots copied into a slot on admission.
            "moe_picks": self._decoder.moe_picks,
            "moe_picks_held": self._decoder.moe_picks_held,
            "moe_picks_zero": self._decoder.moe_picks_zero,
            "moe_experts_touched": self._decoder.moe_experts_touched,
            "moe_expert_slots": self._decoder.moe_expert_slots,
            "moe_prefill_load_max": self._decoder.moe_prefill_load_max,
            "moe_prefill_load_mean": round(
                self._decoder.moe_prefill_load_mean, 4),
            "moe_prefill_tiles": self._decoder.moe_prefill_tiles,
            "moe_prefill_tile_rows": self._decoder.moe_prefill_tile_rows,
            "moe_prefill_experts_touched":
                self._decoder.moe_prefill_experts_touched,
            "moe_prefill_picks_held": self._decoder.moe_prefill_picks_held,
            "state_restores": self._decoder.state_restores,
            # A KDA gate without a lower bound (zeros otherwise): per-channel
            # log-decays of real tokens, prefill and decode, over its layers,
            # and those below -5 (llm.DEEP_DECAY).
            "kda_decays": self._decoder.kda_decays,
            "kda_decays_deep": self._decoder.kda_decays_deep,
            # Diffusion over blocks (zeros otherwise): row-forwards of live
            # rows, commit forwards included (``decode_steps`` counts the
            # pool's forwards), positions committed by denoising, and blocks
            # committed to the pages.
            "diffusion_row_forwards": self._decoder.diffusion_row_forwards,
            "diffusion_tokens_committed":
                self._decoder.diffusion_tokens_committed,
            "diffusion_blocks_committed":
                self._decoder.diffusion_blocks_committed,
        }


class _RowTicket:
    """One row's slot ticket as the annotation lane holds it
    (stream/annotations.py: ``add_done_callback`` + ``result`` +
    ``timeout``). ``result`` is the row's analysis — the served text, the
    drop marker, or the unavailable marker where the decoder failed or
    the ticket outlived ``timeout`` unresolved — so every flagged row
    lands explained or accounted, each on its own."""

    __slots__ = ("_req", "timeout")

    def __init__(self, req: _SlotRequest, timeout: Optional[float]):
        self._req = req
        self.timeout = timeout

    def add_done_callback(self, fn: Callable) -> None:
        self._req.add_done_callback(lambda _req: fn(self))

    def result(self) -> str:
        try:
            return self._req.wait(0)     # resolved (or given up on): no wait
        except Exception as e:  # noqa: BLE001 — annotation only; accounted
            log.warning("slotserve ticket failed: %r (row annotated with "
                        "an unavailable marker)", e)
            return UNAVAILABLE_MARKER.format(reason=type(e).__name__)


def make_slot_explain_hook(backend, *, temperature: float = 0.0,
                           max_tokens: int = 128, only_scams: bool = True):
    """Build a ``StreamingClassifier.explain_batch_fn`` over a slotserve
    backend (the service itself, or a ``CircuitBreakerBackend`` wrapping
    it — the breaker forwards ``explain_rows``).

    Differences from ``make_stream_explain_hook``: (1) the hook advertises
    ``accepts_cids`` so the async annotation lane passes each row's trace
    cid through to the slots, (2) a backend failure (decoder death,
    breaker fast-fail) yields an ``[explanation unavailable: ...]`` MARKER
    per row instead of dropping the batch's annotations — every flagged
    row lands in the annotations topic explained or accounted, the slot
    lane's coverage invariant, even mid-outage — and (3) over a backend
    that hands out tickets (``submit_rows``: the service itself) the hook
    advertises ``submit_rows`` too: the lane then gets each row's ticket
    back unresolved and delivers it when it resolves, so no row waits for
    another's decode. Over the breaker, which forwards the blocking call
    only, every row resolves at the call's return."""
    rows_fn = backend.explain_rows     # AttributeError now beats one later

    def over_picked(fn, texts, labels, confs, cids):
        picked = [i for i, lab in enumerate(labels)
                  if (lab != 0 or not only_scams)]
        out = [None] * len(texts)
        if not picked:
            return out
        try:
            replies = fn(
                [texts[i] for i in picked],
                [labels[i] for i in picked],
                [confs[i] for i in picked],
                cids=([cids[i] for i in picked]
                      if cids is not None else None),
                temperature=temperature, max_tokens=max_tokens)
        except Exception as e:  # noqa: BLE001 — annotation only; accounted
            log.warning("slotserve backend failed for a %d-row batch: %r "
                        "(rows annotated with an unavailable marker)",
                        len(picked), e)
            replies = [UNAVAILABLE_MARKER.format(reason=type(e).__name__)
                       ] * len(picked)
        if len(replies) != len(picked):
            log.warning("slotserve backend returned %d analyses for %d "
                        "prompts; dropping the batch's annotations",
                        len(replies), len(picked))
            return out
        for i, reply in zip(picked, replies):
            out[i] = reply
        return out

    def explain_batch(texts, labels, confs, cids=None):
        return over_picked(rows_fn, texts, labels, confs, cids)

    explain_batch.accepts_cids = True
    if hasattr(backend, "submit_rows"):
        def ticket_rows(*args, **kw):
            # ``submit_rows`` (and the ``submit`` under it) looked up on
            # the instance at call time: a caller may wrap either.
            return [_RowTicket(req, backend.wait_timeout)
                    for req in backend.submit_rows(*args, **kw)]

        def submit_rows(texts, labels, confs, cids=None):
            return over_picked(ticket_rows, texts, labels, confs, cids)

        explain_batch.submit_rows = submit_rows
    return explain_batch
