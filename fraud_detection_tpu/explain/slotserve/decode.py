"""Host-side driver of the slot pool's device programs (models/llm.py).

One :class:`PagedSlotDecoder` owns ONE persistent KV pool — a flat pool of
fixed-size pages per layer, allocated once, and a per-slot page table — and
the two jitted entries that touch it: ``paged_slot_prefill`` (admit one
prompt into a free slot at an iteration boundary) and
``paged_decode_window`` (advance every busy slot up to a window of tokens).
Compile count is bounded by construction: exactly one decode program for
the pool, plus one prefill program per prompt bucket (prompt lengths round
up to ``prompt_bucket`` multiples — the same padding-ladder idea
sched/batcher.py applies to scoring shapes).

Pages come from an exact-accounting refcounted :class:`PageAllocator`
(alloc on admit/growth, free on slot release), which is also what shares
the explain template's preamble: it is prefilled ONCE into refcounted
read-only pages every slot's table points at, with copy-on-write when an
admit would append into a partially-filled shared page.

All slot/queue policy (admission, retirement, accounting) lives in
:mod:`fraud_detection_tpu.explain.slotserve.service`; this class is the
thin device seam so the policy layer never touches jax directly.
"""

from __future__ import annotations

import contextlib
from typing import Callable, Dict, List, Optional

import numpy as np

from fraud_detection_tpu.models import llm


def _no_span(_stage: str):
    return contextlib.nullcontext()


class PagePoolExhausted(RuntimeError):
    """The page pool has no free page for a required alloc. Admission gates
    on :meth:`PagedSlotDecoder.can_admit`, so this surfaces mid-flight only
    on decode-window growth — the service preempts a slot and retries."""


class PageAllocator:
    """Exact-accounting refcounted page allocator (host-side, no locking —
    owned by the slot lane's single worker thread).

    Invariants (pinned by :meth:`check` and the property tests):
      * ``len(free) + pages_with_refs == total`` — every page is either on
        the free list or referenced, never both, never neither;
      * refcounts never go negative (double-free raises instead);
      * at quiescence (every slot released, prefix dropped) all pages are
        free — zero leaks.
    """

    def __init__(self, total: int):
        if total < 1:
            raise ValueError(f"page pool needs >= 1 page, got {total}")
        self.total = total
        # LIFO free list: recently-freed pages are re-used first (warm).
        self._free: List[int] = list(range(total - 1, -1, -1))
        self._refs = [0] * total

    @property
    def free(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.total - len(self._free)

    def refcount(self, pid: int) -> int:
        return self._refs[pid]

    def alloc(self) -> int:
        """Take a free page at refcount 1."""
        if not self._free:
            raise PagePoolExhausted(
                f"page pool exhausted ({self.total} pages, 0 free)")
        pid = self._free.pop()
        self._refs[pid] = 1
        return pid

    def retain(self, pid: int) -> None:
        """Add a reference to an allocated page (prefix sharing)."""
        if self._refs[pid] <= 0:
            raise ValueError(f"retain of unallocated page {pid}")
        self._refs[pid] += 1

    def release(self, pid: int) -> int:
        """Drop one reference; the page returns to the free list at zero.
        Releasing an unreferenced page is a hard error (double free)."""
        if self._refs[pid] <= 0:
            raise ValueError(f"double free of page {pid}")
        self._refs[pid] -= 1
        if self._refs[pid] == 0:
            self._free.append(pid)
        return self._refs[pid]

    def check(self) -> Dict[str, int]:
        """Verify the accounting identity; returns the counters for pinning.
        Raises AssertionError on any violation."""
        refd = sum(1 for r in self._refs if r > 0)
        assert all(r >= 0 for r in self._refs), "negative refcount"
        assert len(self._free) + refd == self.total, (
            f"identity broken: free={len(self._free)} refd={refd} "
            f"total={self.total}")
        assert len(set(self._free)) == len(self._free), "free-list duplicate"
        assert all(self._refs[p] == 0 for p in self._free), (
            "referenced page on the free list")
        return {"total": self.total, "free": len(self._free),
                "in_use": refd, "refs": sum(self._refs)}


class PagedSlotDecoder:
    """One slot pool + its device programs: the KV region is a flat pool of
    ``total_pages`` fixed-size pages indexed by a per-slot page table
    (PagedAttention applied to the slot pool).

    Two kinds of per-slot state live under this one manager, by the model's
    layer kinds: what grows with the tokens held (an ``attention`` layer's
    k/v, an ``mla`` layer's latents) is paged; a layer whose mixer keeps a
    row state (``kda``: its recurrent matrix and filter tails; ``conv``: its
    filter's tail) gets a fixed block per slot, allocated once
    (``self.state``). Pages are counted for the paging layers only; the
    blocks need no allocator. The shared preamble is both: pages mapped
    copy-on-write, and a snapshot of every such layer's state at its
    last token, copied into the slot's block on admission.

    * **Admission** builds the slot's table — shared full prefix pages are
      retained, a partially-filled shared page is copied-on-write, the
      suffix gets fresh pages — then runs the suffix-only prefill program.
    * **Growth** happens at the host side of each iteration boundary:
      before a decode window every busy slot's table is extended to cover
      ``lens + window``; on pool exhaustion the SERVICE preempts a slot
      (accounted drop) and retries — the decoder only reports the failure.
    * **Release** returns every page reference the slot holds; the
      allocator identity (`PageAllocator.check`) holds at every boundary
      and all pages are free at quiescence.

    NOT thread-safe — owned by the slot lane's single worker thread (the
    service's contract).
    """

    def __init__(self, lm, slots: int, *, prompt_width: int = 384,
                 max_new_tokens: int = 128, prompt_bucket: int = 64,
                 page_size: int = 64, total_pages: Optional[int] = None,
                 prefix_text: Optional[str] = None):
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if prompt_bucket < 1:
            raise ValueError(
                f"prompt_bucket must be >= 1, got {prompt_bucket}")
        if page_size < 1 or (page_size & (page_size - 1)):
            raise ValueError(
                f"page_size must be a power of two, got {page_size}")
        cfg = lm.cfg
        # Bucket the width itself so the widest prefill is a ladder rung.
        width = prompt_bucket * (-(-prompt_width // prompt_bucket))
        max_len = width + max_new_tokens
        if max_len > cfg.max_seq:
            raise ValueError(
                f"slot cache needs {max_len} positions (prompt_width "
                f"{width} + max_new_tokens {max_new_tokens}) but "
                f"cfg.max_seq is {cfg.max_seq}")
        self.lm = lm
        self.cfg = cfg
        self.slots = slots
        self.prompt_width = width
        self.prompt_bucket = prompt_bucket
        self.max_new_tokens = max_new_tokens
        self.max_len = max_len
        self.page_size = page_size
        # Ceil: the last page may overhang max_len; the overhang is masked
        # like any position past a row's length.
        self.n_view = -(-max_len // page_size)
        total = slots * self.n_view if total_pages is None else total_pages
        if total < self.n_view:
            raise ValueError(
                f"total_pages {total} cannot hold even one worst-case row "
                f"({self.n_view} pages of {page_size})")
        self.total_pages = total
        self.pages = llm.init_kv_pages(cfg, total, page_size)
        # One block per slot for every layer that keeps a recurrent state
        # ({} for a model without one), and the one-row state a fresh prompt
        # starts from: zeros, or the shared preamble's once it is set.
        self.state = llm.init_state(cfg, slots)
        self._zero_state = llm.init_state(cfg, 1)
        self._prefix_state = self._zero_state
        self.allocator = PageAllocator(total)
        self._tables = np.zeros((slots, self.n_view), np.int32)
        self._cover = [0] * slots        # table entries resident per slot
        self._owned: List[List[int]] = [[] for _ in range(slots)]
        self._prefix_tokens: Optional[np.ndarray] = None
        self._prefix_len = 0
        self._prefix_pids: List[int] = []
        self.prefills = 0
        self.prefills_flash = 0         # ... whose suffix took the flash kernel
        self.steps = 0
        self.prefix_hits = 0
        self.cow_copies = 0
        self.prefix_tokens_saved = 0
        self.leaked_pages = 0
        # What the device programs count about the model's own routing and
        # state, summed on the host as the results come back (no sync of
        # their own: they ride with the first token and with the window's
        # tokens). All stay 0 for a model with no expert layer and no
        # recurrent state.
        self.moe_picks = 0              # expert choices made by real tokens
        self.moe_picks_held = 0         # ... that landed on a held expert
        self.moe_picks_zero = 0         # ... on a zero-compute expert
        self.moe_experts_touched = 0    # distinct held experts read, summed
        #                                 over decode steps and expert layers
        self.moe_expert_slots = 0       # the experts held, summed likewise:
        #                                 what moe_experts_touched could at
        #                                 most have been (the host's product)
        self.moe_prefill_load_max = 0   # busiest held expert's tokens, summed
        #                                 over prefills and expert layers
        # How the prefills' grouped expert product was sized, summed over
        # prefills and expert layers: tile steps run (one read of an expert's
        # weights each) over distinct experts touched = reads per touched
        # expert; held picks over the rows the tiles covered = tile fill.
        self.moe_prefill_tiles = 0
        self.moe_prefill_tile_rows = 0
        self.moe_prefill_experts_touched = 0
        self.moe_prefill_picks_held = 0
        self.state_restores = 0         # snapshots copied into a slot's block
        # A KDA gate without a lower bound: per-channel log-decays of real
        # tokens over its layers, prefill and decode, and those below
        # llm.DEEP_DECAY (the least one step of the bounded gate gives)
        self.kda_decays = 0
        self.kda_decays_deep = 0
        # One page's bytes across every layer/tensor array; the pool's
        # total (pages, plus the slots' state blocks); and what the pool
        # saves against a worst-case reservation of max_len positions for
        # every slot (the headline saving).
        per_pos = int(sum(a.dtype.itemsize * a.shape[2] * a.shape[3]
                          for a in self.pages.values()))
        self.page_bytes = per_pos * page_size
        self.kv_bytes = self.page_bytes * total + int(sum(
            a.size * a.dtype.itemsize for a in self.state.values()))
        self.kv_bytes_saved_vs_contiguous = (
            per_pos * max_len * slots - self.page_bytes * total)
        if prefix_text:
            self.set_prefix(prefix_text)

    def _count(self, stats, *, prefill: bool, steps_run: int = 0) -> None:
        """Add a program's counters to the host's sums; ``steps_run`` is the
        decode steps a window ran."""
        if stats is None:
            return
        got = dict(zip(llm.stat_names(self.cfg),
                       (int(v) for v in np.asarray(stats))))
        self.kda_decays += got.get("kda_decays", 0)
        self.kda_decays_deep += got.get("kda_decays_deep", 0)
        if "picks" not in got:
            return
        self.moe_picks += got["picks"]
        self.moe_picks_held += got["picks_held"]
        self.moe_picks_zero += got.get("picks_zero", 0)
        if prefill:
            self.moe_prefill_load_max += got["load_max"]
            self.moe_prefill_tiles += got["tiles"]
            self.moe_prefill_tile_rows += got["tile_rows"]
            self.moe_prefill_experts_touched += got["experts_touched"]
            self.moe_prefill_picks_held += got["picks_held"]
        else:
            self.moe_experts_touched += got["experts_touched"]
            self.moe_expert_slots += (steps_run * self.cfg.n_expert_layers
                                      * self.cfg.moe.held)

    # -- stats surface --------------------------------------------------

    @property
    def moe_prefill_load_mean(self) -> float:
        """The mean held expert's tokens, summed over prefills and expert
        layers (beside ``moe_prefill_load_max``, the busiest one's)."""
        held = self.moe_prefill_picks_held
        return held / self.cfg.moe.held if held else 0.0

    @property
    def kv_pages(self) -> int:
        return self.total_pages

    @property
    def pages_free(self) -> int:
        return self.allocator.free

    @property
    def prefix_pages(self) -> int:
        return len(self._prefix_pids)

    def allocator_snapshot(self) -> Dict[str, int]:
        """Allocator counters after verifying the accounting identity,
        extended with the table-side view: every reference is held by
        exactly one table slot or the decoder's prefix base ref."""
        snap = self.allocator.check()
        snap["pages_in_tables"] = sum(self._cover)
        snap["prefix_base_refs"] = len(self._prefix_pids)
        assert snap["refs"] == snap["pages_in_tables"] + \
            snap["prefix_base_refs"], (
            f"ref ledger broken: {snap}")
        return snap

    # -- prefix caching --------------------------------------------------

    def set_prefix(self, prefix_text: str, span: Callable = _no_span) -> None:
        """Prefill the shared preamble ONCE into read-only pages (under
        ``span("setup_preamble")``: the prefill and the state it leaves).

        The byte tokenizer is concatenation-safe (``encode(a + b)`` =
        ``[BOS] + bytes(a) + bytes(b)``), so a prompt shares the prefix
        iff its text starts with ``prefix_text`` — checked per admit at
        the token level. A layer that keeps a recurrent state leaves a
        snapshot of it at the preamble's last token instead of pages
        (``_prefix_state``). The preamble goes through the prefill program
        itself (``prefix_len`` 0) at a bucket-aligned width, straight into
        its own pages: its k/v are then the same positions' of any
        whole-prompt prefill (pinned by the parity tests)."""
        import jax
        import jax.numpy as jnp

        if self._prefix_pids:
            raise ValueError("prefix already set")
        toks = np.asarray(self.lm.tokenizer.encode(prefix_text), np.int32)
        lp = len(toks)
        if lp >= self.prompt_width:
            raise ValueError(
                f"shared prefix ({lp} tokens) must leave room for a "
                f"suffix inside prompt_width {self.prompt_width}")
        n_prefix = -(-lp // self.page_size)
        if self.total_pages < self.n_view + n_prefix:
            raise ValueError(
                f"total_pages {self.total_pages} cannot hold the prefix "
                f"({n_prefix} pages) plus one worst-case row "
                f"({self.n_view} pages) — raise the pool or drop sharing")
        wp = self.prompt_bucket * (-(-lp // self.prompt_bucket))
        padded = np.zeros((1, wp), np.int32)
        padded[0, :lp] = toks
        # The table row covers the bucketed width; a page past the
        # preamble's last holds only padding and goes back at once.
        pids = [self.allocator.alloc()
                for _ in range(-(-wp // self.page_size))]
        with span("setup_preamble"):
            _, self.pages, state, _ = llm.paged_slot_prefill(
                self.lm.params, jnp.asarray(padded), jnp.int32(lp), self.cfg,
                self.pages, jnp.asarray(pids, jnp.int32), jnp.float32(0.0),
                jax.random.PRNGKey(0), 0, self._zero_state,
                jnp.int32(0) if self.state else None)
        for pid in pids[n_prefix:]:
            self.allocator.release(pid)
        # ... and, beside the pages, every recurrent layer's state as the
        # preamble's last token left it.
        self._prefix_state = state
        self._prefix_tokens = toks
        self._prefix_len = lp
        self._prefix_pids = pids[:n_prefix]

    def _split_prompt(self, prompt_tokens: np.ndarray):
        """(prefix_len, suffix) — prefix_len is 0 unless the prompt starts
        with the cached preamble AND extends past it."""
        lp = self._prefix_len
        if (lp and len(prompt_tokens) > lp
                and np.array_equal(prompt_tokens[:lp], self._prefix_tokens)):
            return lp, prompt_tokens[lp:]
        return 0, prompt_tokens

    # -- admission -------------------------------------------------------

    def pages_needed(self, prompt_tokens: np.ndarray) -> int:
        """Fresh pages an admit would ALLOCATE (retained shared pages are
        free-list-neutral; the COW copy is not)."""
        lp, suffix = self._split_prompt(prompt_tokens)
        ts = self.prompt_bucket * (-(-len(suffix) // self.prompt_bucket))
        cover = -(-(lp + ts) // self.page_size)
        return cover - lp // self.page_size

    def can_admit(self, prompt_tokens: np.ndarray) -> bool:
        return self.allocator.free >= self.pages_needed(prompt_tokens)

    def _cow_prefix_page(self, slot: int, src: int) -> int:
        """Copy-on-write the partially-filled shared prefix page: the admit
        will append suffix k/v into it, and shared pages are never written
        — the slot gets a private device-side copy instead."""
        import jax.numpy as jnp

        dst = self.allocator.alloc()
        self.pages = llm.copy_kv_page(self.pages, jnp.int32(src),
                                      jnp.int32(dst))
        self.cow_copies += 1
        return dst

    def _table_for_admit(self, slot: int, prefix_len: int,
                         cover: int) -> None:
        """Build the slot's page table for admission: retain the full
        shared prefix pages, COW the partial one, then allocate fresh
        suffix pages. All-or-nothing — a mid-build exhaustion releases
        every reference taken so far and re-raises."""
        row: List[int] = []
        n_full = prefix_len // self.page_size
        try:
            for pid in self._prefix_pids[:n_full]:
                self.allocator.retain(pid)
                row.append(pid)
            if prefix_len % self.page_size:
                row.append(self._cow_prefix_page(
                    slot, self._prefix_pids[n_full]))
            while len(row) < cover:
                row.append(self.allocator.alloc())
        except PagePoolExhausted:
            for pid in row:
                self.allocator.release(pid)
            raise
        self._tables[slot, :cover] = row
        self._cover[slot] = cover
        self._owned[slot] = row

    def encode_prompt(self, prompt: str):
        """Tokenize + truncate to the slot width (head kept: analysis
        prompts front-load the instruction). Returns
        ``(int32 tokens, truncated bool)`` — truncation is counted, never
        silent (same honesty rule as the byte-featurize width)."""
        toks = self.lm.tokenizer.encode(prompt)
        truncated = len(toks) > self.prompt_width
        return np.asarray(toks[: self.prompt_width], np.int32), truncated

    def decode_text(self, tokens) -> str:
        return self.lm.tokenizer.decode(np.asarray(tokens, np.int32))

    def prefill(self, slot: int, prompt_tokens: np.ndarray,
                temperature: float, seed: int,
                span: Callable = _no_span) -> int:
        """Admit one prompt: build the slot's page table (alloc/retain/COW)
        FIRST, copy the state the suffix starts from into the slot's block
        (the preamble's snapshot, or zeros; under ``span("slot_state_restore")``
        and only where the model keeps such state), then run the suffix-only
        prefill program against both. Returns the FIRST sampled token
        (already part of the row's output)."""
        import jax
        import jax.numpy as jnp

        if self._cover[slot]:
            raise ValueError(f"slot {slot} admitted without release")
        n = len(prompt_tokens)
        lp, suffix = self._split_prompt(prompt_tokens)
        ts = self.prompt_bucket * (-(-len(suffix) // self.prompt_bucket))
        cover = -(-(lp + ts) // self.page_size)
        self._table_for_admit(slot, lp, cover)
        if self.state:
            with span("slot_state_restore"):
                self.state = llm.restore_slot_state(
                    self.state, self._prefix_state if lp else self._zero_state,
                    jnp.int32(slot))
            self.state_restores += 1
        padded = np.zeros((1, ts), np.int32)
        padded[0, :len(suffix)] = suffix
        tok, self.pages, self.state, stats = llm.paged_slot_prefill(
            self.lm.params, jnp.asarray(padded), jnp.int32(n), self.cfg,
            self.pages, jnp.asarray(self._tables[slot, :cover]),
            jnp.float32(temperature),
            jax.random.PRNGKey(seed & 0x7FFFFFFF), lp, self.state,
            jnp.int32(slot) if self.state else None)
        self.prefills += 1
        self.prefills_flash += llm.prefill_takes_flash(self.cfg, ts)
        if lp:
            self.prefix_hits += 1
            self.prefix_tokens_saved += lp
        tok = int(tok)
        self._count(stats, prefill=True)
        return tok

    # -- decode-window growth & release ----------------------------------

    def grow_for_window(self, slot: int, length: int, steps: int) -> bool:
        """Extend ``slot``'s table to cover ``length + steps`` positions
        (host side of the iteration boundary — the compiled window program
        never sees a table that can't hold its writes). False on pool
        exhaustion: the caller preempts a slot and retries."""
        need = -(-min(length + steps, self.max_len) // self.page_size)
        while self._cover[slot] < need:
            try:
                pid = self.allocator.alloc()
            except PagePoolExhausted:
                return False
            self._tables[slot, self._cover[slot]] = pid
            self._cover[slot] += 1
            self._owned[slot].append(pid)
        return True

    def release_slot(self, slot: int) -> None:
        """Drop every page reference the slot holds (fresh, COW, and
        retained shared pages alike — the refcount keeps shared prefix
        pages alive for the other tables)."""
        for pid in self._owned[slot]:
            self.allocator.release(pid)
        self._owned[slot] = []
        self._cover[slot] = 0
        self._tables[slot, :] = 0

    def reset_slots(self) -> None:
        for slot in range(self.slots):
            if self._cover[slot]:
                self.release_slot(slot)

    def close(self) -> None:
        """Release everything (slots, then the prefix base refs) and record
        any leak — at quiescence every page must be back on the free
        list."""
        self.reset_slots()
        for pid in self._prefix_pids:
            self.allocator.release(pid)
        self._prefix_pids = []
        self._prefix_len = 0
        self._prefix_tokens = None
        self._prefix_state = self._zero_state
        self.leaked_pages = self.allocator.in_use

    # -- decode ----------------------------------------------------------

    def step(self, tokens: np.ndarray, lens: np.ndarray, active: np.ndarray,
             remaining: np.ndarray, temperatures: np.ndarray, seed: int,
             steps: int, span: Callable = _no_span):
        """One fused decode window (up to ``steps`` iterations) over the
        whole pool; returns ``(out (B, steps) EOS-padded, new_lens,
        steps_run, active_row_steps)``. ONE host sync per window — the
        per-token dispatch amortized ``steps``-wide is what makes
        iteration-level scheduling pay on dispatch-bound hosts too.
        ``span(stage)`` opens the caller's span around each half:
        ``slot_launch`` (arguments placed, program enqueued) and
        ``slot_fetch`` (blocked until the window's tokens are on the
        host)."""
        import jax
        import jax.numpy as jnp

        with span("slot_launch"):
            out, new_lens, steps_run, n_act, self.pages, self.state, stats = \
                llm.paged_decode_window(
                    self.lm.params, jnp.asarray(tokens, jnp.int32),
                    jnp.asarray(lens, jnp.int32), jnp.asarray(active),
                    jnp.asarray(remaining, jnp.int32),
                    self.cfg, self.pages, jnp.asarray(self._tables),
                    jnp.asarray(temperatures, jnp.float32),
                    jax.random.PRNGKey(seed & 0x7FFFFFFF), int(steps),
                    self.state)
        self.steps += 1
        with span("slot_fetch"):
            # np.array, not asarray: the lens copy must be writable (the
            # service mutates it per-slot on prefill/release).
            fetched = (np.asarray(out), np.array(new_lens), int(steps_run),
                       int(n_act))
            self._count(stats, prefill=False, steps_run=fetched[2])
            return fetched

    def warm(self, steps: int, prompt: Optional[str] = None) -> None:
        """Compile the decode window + the smallest suffix bucket off the
        serving path, then return the pages (no residue)."""
        toks, _ = self.encode_prompt(prompt or "warm")
        self.prefill(0, toks, 0.0, 0)
        self.grow_for_window(0, len(toks), steps)
        lens = np.zeros(self.slots, np.int32)
        lens[0] = len(toks)
        active = np.zeros(self.slots, bool)
        active[0] = True
        remaining = np.ones(self.slots, np.int32)
        self.step(np.full(self.slots, self.cfg.EOS, np.int32), lens, active,
                  remaining, np.zeros(self.slots, np.float32), 0, steps)
        self.release_slot(0)
