"""Page-pool invariant suite (docs/explain_serving.md, PR 19).

Pins the Pagecraft CLAIMS:

* **bit-equality** — greedy decode through the page pool (page-table
  gather/scatter + shared-prefix reuse + COW) emits exactly the fixed-batch
  decode's tokens, including after slot reuse;
* **exact accounting** — the page allocator identity
  ``free + pages_with_refs == total`` (and the ref ledger
  ``refs == pages_in_tables + prefix_base_refs``) holds at every
  boundary, under queue overflow, close residue, decoder death, and pool
  exhaustion; zero pages leaked at quiescence;
* **prefix sharing** — the explain preamble prefills ONCE into refcounted
  read-only pages; admits that share it are counted (``prefix_hits``,
  ``prefix_tokens_saved``) and the partial page is copied-on-write, never
  written in place;
* **property** — any interleaving of admit/grow/release/death preserves
  the identity (seeded sweep always; Hypothesis when installed).
"""

import numpy as np
import pytest

from fraud_detection_tpu.explain.backends import frame_prompt
from fraud_detection_tpu.explain.onpod import flatten_chat
from fraud_detection_tpu.explain.prompts import analysis_prompt
from fraud_detection_tpu.explain.slotserve import (DROPPED_MARKER,
                                                   SlotServeService)
from fraud_detection_tpu.explain.slotserve.decode import (PagedSlotDecoder,
                                                          PageAllocator,
                                                          PagePoolExhausted)
from fraud_detection_tpu.explain.slotserve.service import \
    shared_explain_prefix
from fraud_detection_tpu.models import llm

pytestmark = pytest.mark.slotserve


@pytest.fixture(scope="module")
def lm():
    cfg = llm.TransformerConfig(d_model=64, n_layers=2, n_heads=4, d_ff=128,
                                max_seq=1024)
    return llm.LanguageModel.init_random(cfg, seed=3)


def make_service(lm, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("max_new_tokens", 24)
    kw.setdefault("prompt_width", 448)
    kw.setdefault("decode_window", 8)
    kw.setdefault("wait_timeout", 120.0)
    return SlotServeService(lm, **kw)


def analysis_prompts(n):
    """Framed analysis prompts — every one opens with the shared preamble,
    so admits hit the prefix cache."""
    out = []
    for i in range(n):
        d = ("Caller: this is your bank security department, read me the "
             "one-time code now or the account is frozen. "
             + "Customer hesitates. " * (i % 4))
        out.append(flatten_chat(frame_prompt(
            analysis_prompt(d, i % 2, 0.5 + 0.03 * i))))
    return out


def fixed_batch_greedy(lm, svc, framed, max_new):
    """The fixed-batch decode's greedy texts (``generate_tokens_batch``: what
    ``OnPodBackend.generate_batch`` runs) for already-framed prompts, cut to
    the lane's prompt width as the lane cuts them."""
    toks = [svc._decoder.encode_prompt(p)[0] for p in framed]
    return [lm.tokenizer.decode(t) for t in lm.generate_tokens_batch(
        toks, max_new_tokens=max_new)]


def assert_quiescent(svc):
    """The decoder at quiescence after close(): identity + zero leaks."""
    dec = svc._decoder
    assert dec.leaked_pages == 0
    assert dec.allocator.free == dec.total_pages
    dec.allocator.check()


# ---------------------------------------------------------------------------
# allocator unit + property
# ---------------------------------------------------------------------------

def test_allocator_alloc_retain_release_identity():
    a = PageAllocator(4)
    p0, p1 = a.alloc(), a.alloc()
    a.retain(p0)
    assert a.refcount(p0) == 2 and a.refcount(p1) == 1
    assert a.free == 2 and a.in_use == 2
    assert a.release(p0) == 1
    assert a.in_use == 2            # still referenced once
    assert a.release(p0) == 0
    assert a.free == 3
    a.check()
    # LIFO: the page just freed comes back first (warm reuse).
    assert a.alloc() == p0


def test_allocator_double_free_and_exhaustion_raise():
    a = PageAllocator(1)
    pid = a.alloc()
    with pytest.raises(PagePoolExhausted):
        a.alloc()
    a.release(pid)
    with pytest.raises(ValueError, match="double free"):
        a.release(pid)
    with pytest.raises(ValueError, match="unallocated"):
        a.retain(pid)
    a.check()


def _allocator_interleaving(total, ops):
    """Drive one random op sequence; the identity must hold after EVERY
    op and everything must free cleanly at the end."""
    a = PageAllocator(total)
    held = []                        # (pid, refs_held)
    for op in ops:
        if op == 0:                  # alloc
            try:
                held.append([a.alloc(), 1])
            except PagePoolExhausted:
                pass
        elif op == 1 and held:       # retain (share)
            held[len(held) // 2][1] += 1
            a.retain(held[len(held) // 2][0])
        elif op == 2 and held:       # release one ref
            pid, refs = held.pop(0)
            a.release(pid)
            if refs > 1:
                held.insert(0, [pid, refs - 1])
        elif op == 3:                # decoder death: drop everything
            for pid, refs in held:
                for _ in range(refs):
                    a.release(pid)
            held = []
        a.check()
    for pid, refs in held:
        for _ in range(refs):
            a.release(pid)
    snap = a.check()
    assert snap["free"] == total and snap["in_use"] == 0


def test_allocator_property_seeded_interleavings():
    rng = np.random.default_rng(19)
    for _ in range(60):
        total = int(rng.integers(1, 12))
        ops = rng.integers(0, 4, size=int(rng.integers(1, 80))).tolist()
        _allocator_interleaving(total, ops)


def test_allocator_property_hypothesis():
    hyp = pytest.importorskip(
        "hypothesis", reason="hypothesis not installed in this image")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=80, deadline=None)
    @given(total=st.integers(1, 12),
           ops=st.lists(st.integers(0, 3), min_size=1, max_size=80))
    def prop(total, ops):
        _allocator_interleaving(total, ops)

    prop()


# ---------------------------------------------------------------------------
# geometry + admission math
# ---------------------------------------------------------------------------

def test_paged_geometry_validation(lm):
    with pytest.raises(ValueError, match="power of two"):
        PagedSlotDecoder(lm, 2, page_size=48)
    with pytest.raises(ValueError, match="worst-case row"):
        PagedSlotDecoder(lm, 2, prompt_width=128, max_new_tokens=64,
                         page_size=32, total_pages=2)


def test_set_prefix_validation(lm):
    dec = PagedSlotDecoder(lm, 2, prompt_width=128, max_new_tokens=32,
                           page_size=32)
    with pytest.raises(ValueError, match="leave room"):
        dec.set_prefix("x" * 400)
    dec.set_prefix("shared preamble\n")
    with pytest.raises(ValueError, match="already set"):
        dec.set_prefix("another")
    # pool too small to hold prefix + one worst-case row
    small = PagedSlotDecoder(lm, 2, prompt_width=128, max_new_tokens=32,
                             page_size=32, total_pages=5)
    with pytest.raises(ValueError, match="cannot hold the prefix"):
        small.set_prefix("x" * 40)


def test_pages_needed_counts_only_fresh_pages(lm):
    dec = PagedSlotDecoder(lm, 2, prompt_width=256, max_new_tokens=32,
                           page_size=32, prompt_bucket=32)
    prefix = "p" * 70                          # 71 tokens with BOS
    dec.set_prefix(prefix)
    lp = dec._prefix_len
    shared = np.asarray(dec.lm.tokenizer.encode(prefix + "tail " * 10),
                        np.int32)
    plain = np.asarray(dec.lm.tokenizer.encode("unrelated " * 12), np.int32)
    need_shared = dec.pages_needed(shared)
    need_plain = dec.pages_needed(plain)
    # Shared admit allocates cover minus the FULL retained prefix pages
    # (the partial page is COW'd — a fresh alloc, so it still counts).
    ts = dec.prompt_bucket * (-(-(len(shared) - lp) // dec.prompt_bucket))
    cover = -(-(lp + ts) // dec.page_size)
    assert need_shared == cover - lp // dec.page_size
    # The unshared prompt allocates its full bucketed cover.
    tp = dec.prompt_bucket * (-(-len(plain) // dec.prompt_bucket))
    assert need_plain == -(-tp // dec.page_size)
    assert need_shared < cover          # retained pages are free-list-neutral
    assert dec.can_admit(shared) and dec.can_admit(plain)


# ---------------------------------------------------------------------------
# bit-equality: the page pool vs the fixed-batch decode, through the service
# ---------------------------------------------------------------------------

def test_paged_outputs_bit_equal_with_reuse_and_cow(lm):
    """10 analysis prompts through 4 slots: slot reuse, shared-prefix
    admits, COW on the partial preamble page — outputs must match the
    fixed-batch greedy decode byte for byte (max_len is 472, not
    page-aligned: the view's 40-position overhang is masked, which this
    also pins)."""
    prompts = analysis_prompts(10)
    paged = make_service(lm)
    try:
        reqs = [paged.submit(p, temperature=0.0) for p in prompts]
        got = [r.wait(120.0) for r in reqs]
        snap = paged.snapshot()
        want = fixed_batch_greedy(lm, paged, prompts, 24)
    finally:
        paged.close()
    assert paged._decoder.max_len == 472
    assert got == want
    assert len(set(got)) > 1
    assert snap["prefix_hits"] == 10
    assert snap["cow_copies"] == 10          # 293-token preamble: partial page
    assert snap["prefix_pages"] == 5
    assert snap["admitted"] == snap["completed"] + snap["dropped"]
    assert_quiescent(paged)


def test_paged_without_prefix_still_bit_equal(lm):
    """shared_prefix=False: whole-prompt admission (prefix_len 0) must also
    match the fixed-batch decode — no hidden dependence on the preamble
    cache."""
    prompts = analysis_prompts(6)
    paged = make_service(lm, slots=2, shared_prefix=False)
    try:
        reqs = [paged.submit(p, temperature=0.0) for p in prompts]
        got = [r.wait(120.0) for r in reqs]
        snap = paged.snapshot()
        want = fixed_batch_greedy(lm, paged, prompts, 24)
    finally:
        paged.close()
    assert got == want
    assert snap["prefix_hits"] == 0 and snap["prefix_pages"] == 0
    assert_quiescent(paged)


def test_paged_sampled_decode_deterministic_per_seed(lm):
    """Non-greedy rows stay per-seed deterministic through the page pool."""
    p = analysis_prompts(2)
    outs = []
    for _ in range(2):
        svc = make_service(lm, slots=2, seed=5)
        try:
            outs.append(svc.generate_batch(p, temperature=0.8,
                                           max_tokens=12))
        finally:
            svc.close()
    assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# accounting under overflow / close residue / decoder death / exhaustion
# ---------------------------------------------------------------------------

def test_paged_queue_overflow_accounting_and_no_leaks(lm):
    svc = make_service(lm, slots=1, max_queue=2, max_new_tokens=8)
    try:
        reqs = [svc.submit(p, max_tokens=8) for p in analysis_prompts(8)]
        texts = [r.wait(120.0) for r in reqs]
        assert any(t == DROPPED_MARKER.format(reason="queue_overflow")
                   for t in texts)
        snap = svc.snapshot()
        assert snap["admitted"] == snap["completed"] + snap["dropped"]
        svc._decoder.allocator_snapshot()
    finally:
        svc.close()
    assert_quiescent(svc)


def test_paged_close_residue_accounting_and_no_leaks(lm):
    svc = make_service(lm, slots=1, max_queue=64)
    reqs = [svc.submit(p, max_tokens=24) for p in analysis_prompts(6)]
    svc.close(timeout=0.05)
    texts = [r.wait(120.0) for r in reqs]
    assert any(t == DROPPED_MARKER.format(reason="closed") for t in texts)
    snap = svc.snapshot()
    assert snap["admitted"] == snap["completed"] + snap["dropped"]
    assert_quiescent(svc)


def test_paged_decoder_death_releases_pages_then_recovers(lm):
    from fraud_detection_tpu.explain.backends import BackendError
    svc = make_service(lm, slots=2)
    try:
        real_step = svc._decoder.step

        def boom(*a, **k):
            raise RuntimeError("device lost")

        svc._decoder.step = boom
        with pytest.raises(BackendError, match="decoder failed"):
            svc.generate_batch(analysis_prompts(1), max_tokens=8)
        snap = svc.snapshot()
        assert snap["admitted"] == snap["completed"] + snap["dropped"]
        # death path released every slot's pages (prefix base refs remain)
        alloc = svc._decoder.allocator_snapshot()
        assert alloc["pages_in_tables"] == 0
        # device comes back: the lane keeps serving, bit-equal again
        svc._decoder.step = real_step
        out = svc.generate_batch(analysis_prompts(1), temperature=0.0,
                                 max_tokens=8)
        assert len(out) == 1 and isinstance(out[0], str)
    finally:
        svc.close()
    assert_quiescent(svc)


def test_pool_exhaustion_preempts_newest_admit(lm):
    """Growth exhaustion mid-window: the service preempts the NEWEST
    admit as an accounted ``kv_pages_exhausted`` drop and the survivors
    finish. Forced deterministically by denying growth for whichever slot
    was admitted last."""
    svc = make_service(lm, slots=2, shared_prefix=False)
    try:
        real_grow = svc._decoder.grow_for_window
        denied = {"armed": True}

        def grow(slot, length, steps):
            if denied["armed"] and svc._admit_seq[slot] == 2:
                denied["armed"] = False
                return False
            return real_grow(slot, length, steps)

        svc._decoder.grow_for_window = grow
        reqs = [svc.submit(p, max_tokens=16) for p in analysis_prompts(2)]
        texts = [r.wait(120.0) for r in reqs]
        marker = DROPPED_MARKER.format(reason="kv_pages_exhausted")
        assert texts.count(marker) == 1
        assert texts[0] != marker      # oldest admit survives
        snap = svc.snapshot()
        assert snap["dropped"] == 1
        assert snap["admitted"] == snap["completed"] + snap["dropped"]
        svc._decoder.allocator_snapshot()
    finally:
        svc.close()
    assert_quiescent(svc)


def test_grow_for_window_reports_real_exhaustion(lm):
    """Unmocked exhaustion at the decoder level: a pool with zero slack
    cannot grow a second row past its prefill cover."""
    dec = PagedSlotDecoder(lm, 2, prompt_width=64, max_new_tokens=64,
                           page_size=32, prompt_bucket=64, total_pages=4)
    toks = np.asarray(dec.lm.tokenizer.encode("a" * 40), np.int32)
    dec.prefill(0, toks, 0.0, 0)       # 2 pages (64-token bucket)
    dec.prefill(1, toks, 0.0, 0)       # 2 pages — pool now empty
    assert dec.pages_free == 0
    assert dec.grow_for_window(0, 64, 8) is False
    dec.release_slot(1)
    assert dec.grow_for_window(0, 64, 8) is True
    dec.release_slot(0)
    dec.close()
    assert dec.leaked_pages == 0


# ---------------------------------------------------------------------------
# snapshot surface
# ---------------------------------------------------------------------------

def test_snapshot_paged_block_values(lm):
    """The snapshot reports the pool. The key SET is pinned by
    test_slotserve.py::SLOTSERVE_BLOCK_SCHEMA."""
    # Reduced pool: the headline kv_bytes saving is positive.
    paged = make_service(lm, slots=2, kv_pages=13)
    try:
        snap = paged.snapshot()
        assert snap["kv_pages"] == 13
        assert snap["page_bytes"] > 0
        assert snap["prefix_pages"] == 5
        assert snap["kv_bytes_saved_vs_contiguous"] > 0
        reqs = [paged.submit(p, temperature=0.0, max_tokens=8)
                for p in analysis_prompts(3)]
        got = [r.wait(120.0) for r in reqs]
        assert all(isinstance(t, str) for t in got)
        assert paged.snapshot()["prefix_hits"] == 3
    finally:
        paged.close()
    assert_quiescent(paged)


def test_default_service_serves_from_pages(lm):
    """A service built with defaults has the one pool there is: a worst-case
    row of pages for every slot (2 x 8 at width 448 + 24), the preamble
    prefilled into 5 of them. The ``paged`` keyword is all that is left of
    the switch."""
    svc = SlotServeService(lm, slots=2, max_new_tokens=24, prompt_width=448)
    try:
        snap = svc.snapshot()
        assert snap["kv_pages"] == 16 and snap["page_bytes"] > 0
        assert snap["prefix_pages"] == 5
        assert snap["pages_free"] == 11
    finally:
        svc.close()
    assert_quiescent(svc)
    with pytest.raises(ValueError, match="paged"):
        SlotServeService(lm, slots=2, paged=False)


def test_set_prefix_returns_the_scratch_page(lm):
    """The preamble's prefill runs at a bucketed width (64) over pages of
    16: 20 tokens hold 2 pages and the table row covers 4; the 2 that held
    only padding are back on the free list before the first admission."""
    dec = PagedSlotDecoder(lm, 2, prompt_width=128, max_new_tokens=32,
                           page_size=16, prompt_bucket=64)
    dec.set_prefix("p" * 19)                   # 20 tokens with BOS
    assert dec.prefix_pages == 2
    assert dec.allocator.in_use == dec.prefix_pages
    snap = dec.allocator_snapshot()
    assert snap["refs"] == snap["prefix_base_refs"] == 2
    dec.close()
    assert dec.leaked_pages == 0


def test_dense_preamble_pages_equal_whole_prompt_prefill(lm):
    """After ``set_prefix`` the preamble's pages hold, position for
    position, what a whole-prompt prefill of a prompt that starts with the
    preamble writes into its own pages (the dense twin of the hybrid's
    snapshot test below)."""
    prompt = analysis_prompts(1)[0]
    shared = PagedSlotDecoder(lm, 2, prompt_width=448, max_new_tokens=8,
                              prefix_text=shared_explain_prefix())
    whole = PagedSlotDecoder(lm, 2, prompt_width=448, max_new_tokens=8)
    toks, _ = shared.encode_prompt(prompt)
    whole.prefill(1, toks, 0.0, 0)
    lp = shared._prefix_len
    assert lp == 293 and np.array_equal(toks[:lp], shared._prefix_tokens)
    for name in shared.pages:
        pre = np.concatenate([np.asarray(shared.pages[name][pid])
                              for pid in shared._prefix_pids])[:lp]
        row = np.concatenate([np.asarray(whole.pages[name][pid])
                              for pid in whole._owned[1]])[:lp]
        assert pre.any()
        assert np.array_equal(pre, row), name
    for d in (shared, whole):
        d.close()
        assert d.leaked_pages == 0


def test_shared_prefix_matches_analysis_prompts(lm):
    """Every framed analysis prompt tokenizes to preamble + suffix —
    the split the prefix cache keys on."""
    pre = shared_explain_prefix()
    toks_pre = np.asarray(lm.tokenizer.encode(pre))
    for p in analysis_prompts(3):
        assert p.startswith(pre)
        toks = np.asarray(lm.tokenizer.encode(p))
        assert np.array_equal(toks[:len(toks_pre)], toks_pre)


# ---------------------------------------------------------------------------
# a long suffix attends through the flash kernel (ISSUE 34)
# ---------------------------------------------------------------------------

# Heads wide enough for the kernel's lanes (llm._FLASH_MIN_D): the suite's
# other tiny models have heads of 8 to 16 and keep materialized scores.
WIDE = {
    "attention": llm.TransformerConfig(d_model=128, n_layers=2, n_heads=4,
                                       n_kv_heads=2, head_dim_override=64,
                                       d_ff=128, max_seq=1024),
    "mla": llm.TransformerConfig(
        d_model=64, n_layers=2, n_heads=2, d_ff=128, max_seq=1024,
        tie_embeddings=False, layer_kinds=(("mla", "dense"),) * 2,
        mla=llm.MLAConfig(kv_rank=32, nope_dim=48, rope_dim=16, v_dim=32,
                          q_rank=24, kv_scale=2 ** 0.5, out_gate=False)),
}


@pytest.fixture
def down_attend(monkeypatch):
    """``with down_attend():`` traces ``paged_slot_prefill`` with every suffix
    under the flash threshold. jit keeps a trace by shapes and static
    arguments, not by the threshold, so the program's cache is emptied on the
    way in and on the way out."""
    import contextlib

    @contextlib.contextmanager
    def forced():
        llm.paged_slot_prefill.clear_cache()
        with monkeypatch.context() as m:
            m.setattr(llm, "_FLASH_MIN_T", 10 ** 9)
            yield
        llm.paged_slot_prefill.clear_cache()

    return forced


@pytest.mark.parametrize("mixer", ["attention", "mla"])
def test_long_suffix_prefill_through_flash_equals_attend(mixer, down_attend):
    """A suffix of 539 tokens (bucket 576 >= 512) behind the shared preamble:
    the flash kernel over the row's gathered view writes the same pages and
    samples the same first token as materialized scores under the offset
    mask, for grouped dense attention (4 heads over 2 kv heads of 64; k and v
    pages) and for latent attention expanded to keys of 64 and values of 32
    (latent pages); the decoder counts the prefill under ``prefills_flash``."""
    model_lm = llm.LanguageModel.init_random(WIDE[mixer], seed=3)
    cfg = model_lm.cfg
    toks = None

    def prefill():
        nonlocal toks
        dec = PagedSlotDecoder(model_lm, 2, prompt_width=832, max_new_tokens=8,
                               prefix_text=shared_explain_prefix())
        toks, _ = dec.encode_prompt(analysis_prompts(1)[0])
        assert len(toks) - dec._prefix_len == 539
        first = dec.prefill(1, toks, 0.0, 0)
        pages = {name: np.concatenate([np.asarray(arr[pid], np.float32)
                                       for pid in dec._owned[1]])[:len(toks)]
                 for name, arr in dec.pages.items()}
        counted = (dec.prefills, dec.prefills_flash)
        dec.close()
        assert dec.leaked_pages == 0
        return first, pages, counted

    with down_attend():
        assert not llm.prefill_takes_flash(cfg, 576)
        want_first, want_pages, counted = prefill()
        assert counted == (1, 0)
    assert llm.prefill_takes_flash(cfg, 576)
    assert not llm.prefill_takes_flash(cfg, 448)
    first, pages, counted = prefill()
    assert counted == (1, 1)
    assert first == want_first
    assert set(pages) == set(want_pages)
    for name in pages:
        assert pages[name].any()
        np.testing.assert_allclose(pages[name], want_pages[name], atol=2e-5,
                                   rtol=2e-5, err_msg=name)


# ---------------------------------------------------------------------------
# game day: the lane on a capped pool under a campaign wave
# ---------------------------------------------------------------------------

@pytest.mark.scenario
def test_campaign_explain_paged_gameday_passes():
    """The slotserve lane holds coverage == 1.0 on a 37-page pool where a
    worst-case row per slot would fit only half the slot count, with a
    prefix hit per admit and exact page accounting (the scenario's own
    prefix_shared / paged_pool_capped / hbm_saved gates)."""
    from fraud_detection_tpu.scenarios.gameday import (get_scenario,
                                                       run_gameday)

    result = run_gameday(get_scenario("campaign_explain_paged", seed=5,
                                      scale=0.25))
    assert result.ok, result.report.table()
    gates = {v.name: v for v in result.report.verdicts}
    assert gates["explain_coverage"].observed == 1.0
    assert gates["prefix_shared"].ok
    assert gates["paged_pool_capped"].ok
    assert gates["hbm_saved"].ok
    ex = result.evidence["explain"]
    assert ex["kv_pages"] == 37
    assert ex["admitted"] == ex["completed"] + ex["dropped"]
    # Every admit split on the shared preamble and COW'd the partial page.
    assert ex["prefix_hits"] == ex["admitted"]
    assert ex["cow_copies"] == ex["admitted"]


def test_gameday_validation_rejects_bad_paged_configs():
    from fraud_detection_tpu.scenarios.gameday import GameDay
    from fraud_detection_tpu.scenarios.traffic import SteadyLoad

    traffic = (SteadyLoad(name="s", rate=10, duration_s=1.0),)
    with pytest.raises(ValueError, match="needs explain_slots"):
        GameDay(name="x", description="", traffic=traffic, slos=(),
                explain_kv_pages=37)
    with pytest.raises(ValueError, match="explain_kv_pages must be"):
        GameDay(name="x", description="", traffic=traffic, slos=(),
                explain_slots=4, explain_kv_pages=0)


# ---------------------------------------------------------------------------
# serve CLI: --explain-kv-pages
# ---------------------------------------------------------------------------

def test_serve_cli_explain_kv_pages_e2e(capsys):
    import json

    from fraud_detection_tpu.app.serve import main as serve_main

    # Pool arithmetic at the CLI lane's geometry (prompt_width 384 +
    # 8 new tokens -> max_len 392 -> 7 view pages; the ~293-token shared
    # preamble is 5 pages, 4 full): 12 pages holds prefix + both slots
    # (5 + 3*2 = 11) and undercuts a reservation of 2 * 7 pages.
    rc = serve_main(["--model", "synthetic", "--demo", "120",
                     "--batch-size", "64", "--max-wait", "0.01",
                     "--explain", "onpod-demo", "--explain-slots", "2",
                     "--explain-tokens", "8", "--explain-kv-pages", "12"])
    assert rc == 0
    out = capsys.readouterr().out
    stats = json.loads([l for l in out.splitlines()
                        if l.startswith("{")][0])
    snap = stats["explain"]
    assert snap["slots"] == 2
    assert snap["admitted"] == snap["completed"] + snap["dropped"]
    assert snap["completed"] > 0
    # The pool is capped, saving HBM, and the preamble was
    # shared across every admit.
    assert snap["kv_pages"] == 12 and snap["page_bytes"] > 0
    assert snap["prefix_hits"] == snap["admitted"]
    assert snap["kv_bytes_saved_vs_contiguous"] > 0
    assert stats["health"]["explain"]["kv_pages"] == 12


def test_serve_cli_explain_kv_pages_validation():
    from fraud_detection_tpu.app.serve import main as serve_main

    with pytest.raises(SystemExit, match="needs --explain-slots"):
        serve_main(["--model", "synthetic", "--demo", "10",
                    "--explain", "onpod-demo", "--explain-kv-pages", "32"])
    with pytest.raises(SystemExit, match="explain-kv-pages must be"):
        serve_main(["--model", "synthetic", "--demo", "10",
                    "--explain", "onpod-demo", "--explain-slots", "2",
                    "--explain-kv-pages", "-1"])


# ---------------------------------------------------------------------------
# two kinds of per-slot state under one manager (the tiny hybrid)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def hybrid_lm():
    import hybrid_tiny

    return hybrid_tiny.language_model("float32")


def test_hybrid_pages_count_the_paging_layer_only(hybrid_lm, lm):
    """Of the hybrid's 8 layers one pages (its latents); the 7 recurrent
    ones hold a fixed block a slot. ``pages_needed`` / ``can_admit`` count
    pages, so they read as a dense model's; a page's bytes are the one
    layer's."""
    kw = dict(prompt_width=448, max_new_tokens=16, page_size=64)
    dec = PagedSlotDecoder(hybrid_lm, 2, **kw)
    dense = PagedSlotDecoder(lm, 2, **kw)
    cfg = hybrid_lm.cfg
    assert set(dec.pages) == {"l5.c"}
    assert dec.page_bytes == 64 * cfg.mla.latent_dim * 4
    assert set(dec.state) == {f"l{l}.{t}" for l in (0, 1, 2, 3, 4, 6, 7)
                              for t in ("S", "tail")}
    state_bytes = sum(a.size * a.dtype.itemsize for a in dec.state.values())
    assert dec.kv_bytes == dec.page_bytes * dec.total_pages + state_bytes
    for n in (5, 64, 65, 300):
        toks = np.arange(n, dtype=np.int32) % 250
        assert dec.pages_needed(toks) == dense.pages_needed(toks) == -(-n // 64)
        assert dec.can_admit(toks)
    dec.close(), dense.close()
    assert dec.leaked_pages == 0


def test_hybrid_preamble_snapshot_and_cow_pages_equal_whole_prompt_prefill(hybrid_lm):
    """Admission = the preamble's pages mapped copy-on-write + its state
    snapshot copied into the slot's block; the suffix prefill behind both
    lands where a prefill of the whole prompt does: the same first token and
    the same decode, the slot's state within float32 rounding of it (the
    chunks of the recurrence fall elsewhere)."""
    prompt = analysis_prompts(1)[0]
    shared = PagedSlotDecoder(hybrid_lm, 2, prompt_width=1088, max_new_tokens=8,
                              prefix_text=shared_explain_prefix())
    whole = PagedSlotDecoder(hybrid_lm, 2, prompt_width=1088, max_new_tokens=8)
    toks, _ = shared.encode_prompt(prompt)
    first = [d.prefill(1, toks, 0.0, 0) for d in (shared, whole)]
    assert first[0] == first[1]
    assert (shared.prefix_hits, shared.cow_copies, shared.state_restores) == (1, 1, 1)
    assert (whole.prefix_hits, whole.cow_copies, whole.state_restores) == (0, 0, 1)
    for name in shared.state:
        np.testing.assert_allclose(np.asarray(shared.state[name][1], np.float32),
                                   np.asarray(whole.state[name][1], np.float32),
                                   atol=2e-5)
    outs = []
    for d in (shared, whole):
        assert d.grow_for_window(1, len(toks), 4)
        out, *_ = d.step(np.asarray([0, first[0]], np.int32),
                         np.asarray([0, len(toks)], np.int32),
                         np.asarray([False, True]), np.asarray([0, 4], np.int32),
                         np.zeros(2, np.float32), 0, 4)
        outs.append(out[1].tolist())
        d.close()
        assert d.leaked_pages == 0
    assert outs[0] == outs[1]
