"""Property-based scheduler test (ISSUE 6): the REAL ``SlotServer`` driven
over a stub engine so thousands of admission/decode/retire schedules run in
milliseconds, checked against a pure-Python oracle.

Invariants (asserted after EVERY scheduler step, for random traffic across
paged/dense × chunked/monolithic configurations):

  * FIFO admission — requests enter slots in exactly submit order, even
    when page-count admission blocks the head.
  * Reservation conservation — reservations never exceed the admissible
    pool (``pool - watermark``), every claimed slot holds a reservation,
    and a row never pops more pages than its reservation promised.
  * Refcount conservation — the stub pool's free count plus every live
    row's held pages equals the pool size at all times, and the free list
    never over-pops (the scheduler's reservations are the only thing
    standing between the in-graph free-list and underflow).
  * Bounded stall — while any slot is occupied, every scheduler step runs
    EXACTLY one decode launch and at most one bounded prefill chunk: no
    decoding request ever waits for a whole prompt. A speculative verify
    launch counts as the step's one decode launch.
  * Speculation (ISSUE 7) — per verify launch, accepted <= drafted; every
    request still finishes with EXACTLY ``max_new`` tokens (multi-token
    emission never overshoots or double-counts), and every emitted token
    equals the stub's greedy pick for its slot.
  * Priority + preemption (ISSUE 8) — admission is per-class FIFO (each
    class's admissions happen in submit order even across preemptions and
    chunk aborts); page conservation holds through swap-out/swap-in (an
    evacuated row's pages return to the pool, a restored row re-pops within
    its reservation); the SwapStore drains by the time the queue does; and
    ``preemptions``/``cancelled``/``expired`` stats match the event log and
    terminal statuses exactly.
  * Fault storms (deterministic ``FaultPlan`` schedules) — pool squeezes,
    cancel/deadline storms, chunk-boundary aborts and straggler bursts all
    act through the same seams real traffic does, and every invariant
    above must survive them after EVERY step.
  * Session cache (ISSUE 9) — every retirement-park shows up in the
    evacuation log (``evacuations == preemptions + session_parks``);
    parked entries hold host BYTES, never pool pages, so page
    conservation is unchanged; fault-fabricated returning sessions
    (``resume`` events) admit as hits (restore, no insert) or fall back
    cold without disturbing per-class FIFO of first admissions; expiry
    racing a resume degrades to a cold admission, never a crash or leak.
  * Replicated ledger (ISSUE 10) — on a ``(dp, kv)`` serving mesh the
    page ledger is REPLICATED: the stub applies every ledger op to one
    independent replica per mesh device and asserts the replicas stay
    identical, so the scheduler can never feed an op device-dependent
    state; the same seeded traffic at (1,1), (1,2) and (2,2) must
    produce identical event logs and stats.

The deterministic seeded sweep always runs; the hypothesis variant widens
the search when hypothesis is installed (CI: requirements-dev.txt;
``HYPOTHESIS_MAX_EXAMPLES`` raises the example count on the nightly lane).
"""
import copy
import os
from types import SimpleNamespace

import numpy as np
import pytest

from repro.core.cache import SessionStore
from repro.distributed.fault import FaultEvent, FaultPlan, StragglerMonitor
from repro.serving import EngineConfig, Request, SlotServer
from repro.utils import cdiv

BLOCK, VOCAB = 64, 97


class _StubEngine:
    """Host-only engine exposing exactly the surface SlotServer touches.

    The ``cache`` is a dict: per-row held page counts + prompt token
    counts + a scalar free-page counter standing in for the device
    free-list. Page pops mirror the real engine's schedule: the
    block-aligned prompt pops at insert, decode pops one page whenever a
    row's block-aligned token count crosses a page multiple (capped at
    capacity). Every call is logged for the oracle.
    """

    def __init__(self, ecfg, pool_pages):
        self.cfg = SimpleNamespace(input_mode="tokens", family="dense")
        self.ecfg = ecfg
        self.pack_cfg = SimpleNamespace(
            pool_pages=pool_pages, block=BLOCK, residual=96, policy="none",
            page_size=ecfg.page_size)
        self._decode_multi = None
        self.log = []  # ("insert", rid) | ("chunk", rid) | ("decode",)

    # -- pool bookkeeping ---------------------------------------------------
    def _pages_for(self, n_tokens):
        lb = min(self.ecfg.capacity, (n_tokens // BLOCK) * BLOCK)
        return cdiv(lb, self.ecfg.page_size) if self.ecfg.paged else 0

    def _pop(self, cache, slot, n):
        if n:
            assert cache["free"] >= n, \
                f"free-list underflow: slot {slot} pops {n} of {cache['free']}"
            cache["free"] -= n
            cache["rows"][slot] += n

    def alloc_slot_cache(self):
        return {"free": self.pack_cfg.pool_pages,
                "rows": [0] * self.ecfg.max_batch,
                "toks": [0] * self.ecfg.max_batch}

    def free_slot(self, cache, slot):
        cache["free"] += cache["rows"][slot]
        cache["rows"][slot] = 0
        cache["toks"][slot] = 0
        return cache

    def mask_free(self, cache, active):
        return cache

    def bucket_for(self, n_max):
        return None

    # -- admission ----------------------------------------------------------
    def _insert_row(self, cache, slot, n_tokens, rid):
        self._pop(cache, slot, self._pages_for(n_tokens))
        cache["toks"][slot] = n_tokens
        self.log.append(("insert", rid))

    def insert_request(self, cache, slot, tokens):
        self._insert_row(cache, slot, len(tokens), int(tokens[0]))
        return np.zeros((1, VOCAB), np.float32), cache

    def chunk_tokens(self):
        return self.ecfg.prefill_chunk_pages * self.ecfg.page_size

    def chunk_init(self, prompt_len):
        return {"len": prompt_len, "seen": 0}

    def chunk_step(self, scratch, tokens, n_ctx):
        assert n_ctx == scratch["seen"], "chunks resumed out of order"
        scratch["seen"] += len(tokens)
        self.log.append(("chunk", int(tokens[0]) if n_ctx == 0 else None))
        return np.zeros((1, VOCAB), np.float32), scratch

    def chunk_insert(self, cache, slot, scratch):
        assert scratch["seen"] == scratch["len"], "insert before last chunk"
        self._insert_row(cache, slot, scratch["len"], None)
        return cache

    def chunk_final(self, cache, slot, scratch, tokens, n_ctx):
        # fused last chunk: one dispatch = chunk_step + chunk_insert
        logits, scratch = self.chunk_step(scratch, tokens, n_ctx)
        cache = self.chunk_insert(cache, slot, scratch)
        return logits, cache

    # -- decode -------------------------------------------------------------
    def decode(self, cache, tok, n_bucket=None):
        self.log.append(("decode", None))
        for i in range(self.ecfg.max_batch):
            if cache["toks"][i]:
                before = self._pages_for(cache["toks"][i])
                cache["toks"][i] += 1
                self._pop(cache, i, self._pages_for(cache["toks"][i]) - before)
        # greedy argmax of row i picks (i + 1) % VOCAB
        logits = np.zeros((self.ecfg.max_batch, VOCAB), np.float32)
        for i in range(self.ecfg.max_batch):
            logits[i, (i + 1) % VOCAB] = 1.0
        return logits, cache

    def decode_verify(self, cache, tokens, lens, active, n_bucket=None):
        """Stub verify launch: the greedy pick of row i is the constant
        (i + 1) % VOCAB at every window position, so a draft is accepted
        iff it proposes exactly that — the same acceptance rule as
        ``models.transformer.verify_steps``. Committing seed + accepted
        advances the row's token count (and page pops) all at once."""
        self.log.append(("decode", None))
        B = self.ecfg.max_batch
        hat = np.zeros((B, tokens.shape[1]), np.int32)
        n_accept = np.zeros((B,), np.int32)
        for i in range(B):
            if not active[i] or not cache["toks"][i]:
                continue
            c = (i + 1) % VOCAB
            hat[i, :] = c
            m = 0
            for j in range(int(lens[i]) - 1):
                if int(tokens[i, 1 + j]) != c:
                    break
                m += 1
            n_accept[i] = m
            self.log.append(("verify", int(lens[i]) - 1, m))
            before = self._pages_for(cache["toks"][i])
            cache["toks"][i] += 1 + m
            self._pop(cache, i, self._pages_for(cache["toks"][i]) - before)
        return hat, n_accept, cache

    # -- preemption (ISSUE 8) ------------------------------------------------
    def evacuate(self, cache, slot, n_pages, n_shared=0):
        """Swap-out: the row's pages go back to the free list, its token
        count rides out in the mini. The scheduler's ``n_pages`` hint is
        residual-aware (the REAL engine's flush model); the stub keeps its
        own simpler block-aligned model, so it ignores the hint — both
        models stay internally consistent and both are reservation-bounded."""
        mini = {"toks": cache["toks"][slot]}
        cache["free"] += cache["rows"][slot]
        cache["rows"][slot] = 0
        cache["toks"][slot] = 0
        self.log.append(("evacuate", slot))
        return cache, mini

    def restore(self, cache, slot, mini, shared_phys=(), n_pages=0,
                n_shared=0):
        self._pop(cache, slot, self._pages_for(mini["toks"]))
        cache["toks"][slot] = mini["toks"]
        self.log.append(("restore", slot))
        return cache


def _tree_eq(a, b):
    if isinstance(a, np.ndarray):
        return isinstance(b, np.ndarray) and np.array_equal(a, b)
    if isinstance(a, dict):
        return a.keys() == b.keys() and all(_tree_eq(a[k], b[k]) for k in a)
    if isinstance(a, (list, tuple)):
        return len(a) == len(b) and all(map(_tree_eq, a, b))
    return a == b


class _MeshStubEngine(_StubEngine):
    """The stub on a ``(dp, kv)`` mesh: the REAL sharded engine keeps its
    page ledger REPLICATED across every device (only pool payloads shard,
    by KV head), so every ledger transition must be a pure function of
    scheduler-visible state. Enforced by replay: each op runs once per
    mesh device on an independent deep copy of its inputs and the replica
    results must be identical — any device-dependent input the scheduler
    smuggled in would diverge them."""

    _REPLAYED = ("insert_request", "free_slot", "mask_free", "chunk_step",
                 "chunk_insert", "chunk_final", "decode", "decode_verify",
                 "evacuate", "restore")

    def __init__(self, ecfg, pool_pages, mesh_shape=(1, 1)):
        super().__init__(ecfg, pool_pages)
        self.n_dev = mesh_shape[0] * mesh_shape[1]

    def __getattribute__(self, name):
        if name in _MeshStubEngine._REPLAYED:
            base = getattr(_StubEngine, name)

            def replayed(*args, **kw):
                mark = len(self.log)
                first = base(self, *copy.deepcopy(args),
                             **copy.deepcopy(kw))
                for _ in range(self.n_dev - 1):
                    del self.log[mark:]  # replicas log once, not n_dev times
                    rep = base(self, *copy.deepcopy(args), **copy.deepcopy(kw))
                    assert _tree_eq(first, rep), \
                        f"ledger replica diverged in {name}"
                return first

            return replayed
        return super().__getattribute__(name)


def _drive(rng, *, paged, chunk_pages, spec=False, prio=False, preempt=False,
           session=False, fault_factory=None, straggler=None,
           mesh_shape=(1, 1), log_sink=None):
    """Run random traffic through SlotServer + stub; assert invariants
    after every step against the pure-Python oracle. Returns the run's
    ``SlotStats`` so sweeps can assert a path was actually exercised.

    ``prio`` draws per-request priority classes 0-2 (aging on);
    ``preempt`` turns on swap-out preemption; ``session`` turns on the
    voluntary session cache (every natural retirement parks);
    ``fault_factory`` builds a fresh deterministic ``FaultPlan`` per run;
    ``straggler`` builds a decode-launch watchdog to inject."""
    page = int(rng.choice([64, 128]))
    n_slots = int(rng.integers(1, 5))
    capacity = page * int(rng.integers(2, 5))
    pool = (n_slots * capacity // page if not rng.integers(0, 2)
            else max(2, int(rng.integers(2, n_slots * capacity // page + 1))))
    ecfg = EngineConfig(capacity=capacity, max_batch=n_slots, paged=paged,
                        page_size=page, pool_pages=pool, calibrate=False,
                        prefill_chunk_pages=chunk_pages, decode_chunk=1,
                        spec_decode=spec, spec_k=int(rng.integers(1, 5)),
                        spec_backoff=int(rng.choice([0, 1, 32])),
                        preempt=preempt, session_cache=session,
                        aging_steps=8 if prio else 32,
                        mesh_shape=mesh_shape)
    eng = _MeshStubEngine(ecfg, pool, mesh_shape)
    plan = fault_factory() if fault_factory is not None else None
    srv = SlotServer(eng, fault_plan=plan,
                     straggler=straggler() if straggler is not None else None)
    faulty = plan is not None

    n_req = int(rng.integers(1, 12))
    reqs = []
    prio_of = {}
    for rid in range(n_req):
        plen = int(rng.integers(1, capacity))
        max_new = int(rng.integers(1, capacity + 96 - plen + 1))
        if paged and cdiv(min(capacity, plen + max_new), page) > pool:
            max_new = 1  # keep it admissible; rejection has its own test
            plen = min(plen, (pool * page) - 1)
        # first prompt token carries the rid so the stub can log FIFO order
        toks = np.full((plen,), rid, np.int64)
        prio_of[rid] = int(rng.integers(0, 3)) if prio else 0
        reqs.append(Request(rid=rid, max_new=max_new, tokens=toks,
                            priority=prio_of[rid]))

    while reqs or srv.queue or srv.n_occupied or srv._task is not None:
        # interleave submits with steps at random
        while reqs and rng.integers(0, 2):
            srv.submit(reqs.pop(0))
        if not (srv.queue or srv.n_occupied or srv._task is not None):
            srv.submit(reqs.pop(0))  # idle server: force progress
        occ_before = srv.n_occupied
        decodes, chunks = (sum(e[0] == "decode" for e in eng.log),
                           sum(e[0] == "chunk" for e in eng.log))
        srv.step()
        d_dec = sum(e[0] == "decode" for e in eng.log) - decodes
        d_chk = sum(e[0] == "chunk" for e in eng.log) - chunks
        # bounded stall: an occupied table always decodes, and waits for
        # at most one bounded chunk first (monolithic mode may admit a
        # whole prompt per slot, which is exactly the stall being fixed).
        # A reap can empty the table mid-step, so gate on occupancy at the
        # decode point when requests can die.
        if occ_before and (srv.n_occupied or not (faulty or preempt)):
            assert d_dec == 1, "occupied step skipped decode"
            if chunk_pages:
                assert d_chk <= 1, "decode stalled behind >1 prefill chunk"
        # reservation conservation
        if paged:
            assert sum(srv._reserved.values()) <= pool - ecfg.page_watermark
            for slot, held in enumerate(srv.cache["rows"] if srv.cache
                                        else []):
                if held:
                    assert slot in srv._reserved, \
                        f"slot {slot} holds pages with no reservation"
                    assert held <= srv._reserved[slot], \
                        f"slot {slot} popped {held} > reserved"
        # page conservation: free + held == pool, never negative — evacuated
        # rows' pages are back in the pool, restores re-pop within their
        # reservation, so this holds THROUGH preemption and fault storms
        if srv.cache is not None:
            assert srv.cache["free"] + sum(srv.cache["rows"]) == pool
            assert srv.cache["free"] >= 0

    # every submitted request reached a terminal status; completed ones hold
    # EXACTLY max_new tokens (multi-token speculative emission never
    # overshoots or double-counts), dead ones at most their partial output
    # (fault-fabricated returning sessions add done entries past n_req)
    assert len(srv.done) >= n_req if session else len(srv.done) == n_req
    statuses = {}
    for rid in range(n_req):
        req = srv.done[rid]
        statuses[rid] = req.status
        out = req.output
        if req.status == "done":
            assert len(out) == req.max_new
        else:
            assert req.status in ("cancelled", "expired", "parked")
            assert len(out) <= req.max_new
        # token 0 is the prefill argmax (zero logits); every decoded token
        # is the slot's constant greedy pick. A preempted request may
        # resume in a DIFFERENT slot, so its constant may change once per
        # preemption but never more often. (A session HIT emits only slot
        # constants — no prefill argmax — so the bound still holds.)
        assert len(set(out[1:])) <= 1 + req.n_preempts, \
            f"rid {rid} mixed tokens: {out}"
    all_done = list(srv.done.values())
    n_parked = sum(r.status == "parked" for r in all_done)
    assert srv.stats.completed == sum(r.status == "done" for r in all_done)
    assert srv.stats.cancelled == sum(
        r.status == "cancelled" for r in all_done)
    assert srv.stats.expired == sum(r.status == "expired" for r in all_done)
    assert srv.stats.completed + srv.stats.cancelled + srv.stats.expired \
        + n_parked == len(all_done)
    if not session:
        assert n_parked == 0
    # preemption + session oracle: every evacuation in the stub's log is a
    # swap-out or a retirement park, every swapped row either streamed
    # back or died with its request (SwapStore drains; parked entries may
    # legitimately outlive the run — they hold host bytes, not pages)
    evacs = sum(e[0] == "evacuate" for e in eng.log)
    restores = sum(e[0] == "restore" for e in eng.log)
    assert srv.stats.preemptions + srv.stats.session_parks == evacs
    assert restores <= evacs
    if srv._swap is not None:
        assert len(srv._swap) == 0, "SwapStore leaked evacuated rows"
    if srv._sessions is not None:
        # store counters are self-consistent: everything parked was served
        # back, evicted/expired, or still resident
        st = srv._sessions
        assert st.parks == st.hits + st.evictions + st.expired + len(st)
        assert srv.stats.session_parks == st.parks
        assert srv.stats.session_hits == st.hits
    if not (preempt or session):
        assert evacs == 0
    # the pool is whole again once everything retired
    if srv.cache is not None:
        assert srv.cache["free"] == pool
    # speculation oracle: accepted <= drafted per verify launch, and the
    # stats roll-up matches the launch log
    verifies = [e for e in eng.log if e[0] == "verify"]
    for _, drafted, accepted in verifies:
        assert 0 <= accepted <= drafted
    assert srv.stats.spec_drafted == sum(e[1] for e in verifies)
    assert srv.stats.spec_accepted == sum(e[2] for e in verifies)
    if not spec:
        assert not verifies and srv.stats.spec_launches == 0
    # PER-CLASS FIFO: each class's rows were inserted in submit order, even
    # across preemptions and chunk aborts (requeues keep original submit
    # order within the class). Chunked tasks log their rid on the FIRST
    # chunk (n_ctx == 0); monolithic inserts log theirs; restores re-enter
    # without a fresh insert, so re-admissions never reorder the log.
    order = [e[1] for e in eng.log
             if e[0] in ("insert", "chunk") and e[1] is not None]
    if session:
        # a fault-fabricated resume that MISSES re-prefills cold and logs
        # the original rid again (the stub keys the log on tokens[0], and
        # a fabricated session's trace starts with the original prompt).
        # First admissions must still be per-class FIFO; re-walks may
        # interleave anywhere.
        seen: set = set()
        order = [rid for rid in order
                 if not (rid in seen or seen.add(rid))]
    for c in set(prio_of.values()):
        sub = [rid for rid in order if prio_of[rid] == c]
        assert sub == sorted(sub), \
            f"class {c} admission violated FIFO: {order}"
    if not (prio or faulty):
        assert order == sorted(order), f"admission violated FIFO: {order}"
        assert order == list(range(n_req))
    if log_sink is not None:
        log_sink.extend(eng.log)
    return srv.stats


CASES = [(False, 0, False), (False, 1, False), (True, 0, False),
         (True, 1, False), (True, 2, False),
         (False, 1, True), (True, 1, True), (True, 2, True)]


@pytest.mark.parametrize("paged,chunk_pages,spec", CASES)
def test_scheduler_invariants_seeded(paged, chunk_pages, spec):
    """Deterministic sweep — runs everywhere, no hypothesis needed."""
    n_verify = 0
    for seed in range(25):
        n_verify += _drive(np.random.default_rng(seed), paged=paged,
                           chunk_pages=chunk_pages, spec=spec).spec_launches
    if spec:  # the sweep must actually hit the verify path
        assert n_verify > 0


PREEMPT_CASES = [(False, 0), (False, 1), (True, 0), (True, 1), (True, 2)]


@pytest.mark.parametrize("paged,chunk_pages", PREEMPT_CASES)
def test_scheduler_priority_preempt_seeded(paged, chunk_pages):
    """Priority classes + swap-out preemption under random traffic:
    per-class FIFO admission, page conservation through evacuate/restore,
    SwapStore drainage and stats/log agreement (all inside ``_drive``)."""
    preempts = 0
    for seed in range(25):
        preempts += _drive(np.random.default_rng(seed), paged=paged,
                           chunk_pages=chunk_pages, prio=True,
                           preempt=True).preemptions
    assert preempts > 0, "sweep never exercised the swap-out path"


MESH_SHAPES = ((1, 1), (1, 2), (2, 2))


@pytest.mark.parametrize("paged,chunk_pages", [(True, 1), (True, 0),
                                               (False, 1)])
def test_scheduler_ledger_device_count_independent(paged, chunk_pages):
    """ISSUE 10: the scheduler's ledger decisions may not depend on the
    mesh shape. Same seeded traffic (with speculation, priorities,
    preemption and session parks all on) at (1,1), (1,2) and (2,2):
    identical per-op replica ledgers (asserted inside the stub), identical
    event logs, identical stats roll-ups."""
    fields = ("completed", "cancelled", "expired", "decode_steps",
              "prefill_chunks", "admitted", "preemptions", "session_parks",
              "session_hits", "spec_drafted", "spec_accepted",
              "pages_reserved_peak", "admission_blocks")
    for seed in range(8):
        runs = []
        for ms in MESH_SHAPES:
            log = []
            # the default straggler watchdog reads wall-clock launch times
            # and may turn speculation off in one run and not another; one
            # that never excludes keeps the logs down to ledger decisions
            stats = _drive(np.random.default_rng(seed), paged=paged,
                           chunk_pages=chunk_pages, spec=True, prio=True,
                           preempt=True, session=True, mesh_shape=ms,
                           log_sink=log,
                           straggler=lambda: StragglerMonitor(patience=2**31))
            runs.append((log, {f: getattr(stats, f) for f in fields}))
        for ms, (log, st) in zip(MESH_SHAPES[1:], runs[1:]):
            assert log == runs[0][0], \
                f"seed {seed}: event log at mesh {ms} != (1,1)"
            assert st == runs[0][1], \
                f"seed {seed}: stats at mesh {ms} != (1,1): {st}"


def _squeeze_plan():
    # squeeze the whole pool for a few steps, then release: admission must
    # block (not underflow) and resume afterwards
    return FaultPlan([FaultEvent(step=2, kind="pool_squeeze", arg=10**6),
                      FaultEvent(step=9, kind="pool_squeeze", arg=0)])


FAULT_CASES = [
    ("cancel_storm",
     lambda: FaultPlan.storm("cancel", start=3, count=4, every=2)),
    ("deadline_storm",
     lambda: FaultPlan.storm("deadline", start=4, count=3, every=3, arg=2)),
    ("pool_squeeze", _squeeze_plan),
    ("chunk_abort",
     lambda: FaultPlan.storm("chunk_abort", start=2, count=5, every=2)),
    ("mixed",
     lambda: FaultPlan.storm("cancel", start=3, count=3, every=4)
     + _squeeze_plan()
     + FaultPlan.storm("chunk_abort", start=5, count=3, every=3)),
]


@pytest.mark.parametrize("name,factory", FAULT_CASES,
                         ids=[c[0] for c in FAULT_CASES])
def test_scheduler_fault_storms(name, factory):
    """Deterministic fault schedules against the full priority+preemption
    scheduler: every conservation invariant must hold after every step of
    every storm, and every request must still reach a terminal status."""
    died = 0
    for seed in range(15):
        for paged, chunk_pages in ((True, 1), (True, 2), (False, 1),
                                   (True, 0)):
            stats = _drive(np.random.default_rng(seed), paged=paged,
                           chunk_pages=chunk_pages, prio=True, preempt=True,
                           fault_factory=factory)
            died += stats.cancelled + stats.expired
    if name in ("cancel_storm", "deadline_storm", "mixed"):
        assert died > 0, "storm never killed a request"


SESSION_CASES = [
    # voluntary mid-flight parks: rows retire as "parked", their bytes move
    # host-side, and the pool is whole after every step
    ("park_storm",
     lambda: FaultPlan.storm("park", start=2, count=5, every=2)),
    # parked sessions come back: fabricated returning requests must admit
    # as session hits (restore, no insert) or fall back to a cold prefill
    ("park_resume",
     lambda: FaultPlan.storm("park", start=2, count=4, every=3)
     + FaultPlan.storm("resume", start=4, count=4, every=3)),
    # a returning session under a squeezed pool must block, not underflow,
    # and stream back once the squeeze lifts
    ("resume_pressure",
     lambda: FaultPlan.storm("park", start=2, count=3, every=2)
     + FaultPlan.storm("resume", start=5, count=3, every=2)
     + FaultPlan([FaultEvent(step=6, kind="pool_squeeze", arg=10**6),
                  FaultEvent(step=12, kind="pool_squeeze", arg=0)])),
    # expiry racing a resume: the store may expire an entry the very step a
    # returning session arrives — it must degrade to a cold admission
    ("expiry_race",
     lambda: FaultPlan.storm("park", start=2, count=4, every=2)
     + FaultPlan.storm("session_expire", start=5, count=4, every=2)
     + FaultPlan.storm("resume", start=5, count=4, every=2)),
]


@pytest.mark.parametrize("name,factory", SESSION_CASES,
                         ids=[c[0] for c in SESSION_CASES])
def test_scheduler_session_storms(name, factory):
    """Deterministic park/resume/expire schedules against the session-cache
    scheduler: free+held == pool after every step, evacuations reconcile
    with parks+preemptions, and the session store's own counters balance
    (parks == hits + evictions + expired + resident) — across paged/dense,
    chunked/monolithic prefill, and preemption on/off."""
    parks = hits = 0
    for seed in range(15):
        for paged, chunk_pages, preempt in ((True, 1, True), (True, 2, False),
                                            (False, 1, True), (True, 0, False)):
            stats = _drive(np.random.default_rng(seed), paged=paged,
                           chunk_pages=chunk_pages, prio=True,
                           preempt=preempt, session=True,
                           fault_factory=factory)
            parks += stats.session_parks
            hits += stats.session_hits
    assert parks > 0, "storm never parked a session"
    if name == "park_resume":
        assert hits > 0, "resume storm never produced a session hit"


def test_straggler_watchdog_degrades_spec():
    """A straggler burst on the decode-launch watchdog auto-disables
    speculative decode — graceful degradation: outputs stay exact, and the
    mode switch is surfaced in ``SlotStats.degraded_steps``."""
    ecfg = EngineConfig(capacity=256, max_batch=2, paged=True, page_size=64,
                        pool_pages=8, calibrate=False, prefill_chunk_pages=1,
                        decode_chunk=1, spec_decode=True, spec_k=2)
    eng = _StubEngine(ecfg, 8)
    plan = FaultPlan.storm("straggler", start=8, count=3, every=1, arg=1e3)
    srv = SlotServer(eng, fault_plan=plan,
                     straggler=StragglerMonitor(patience=1))
    for rid in range(3):
        srv.submit(Request(rid=rid, max_new=40,
                           tokens=np.full((65,), rid, np.int64)))
    srv.run()
    assert srv._spec_degraded, "watchdog never excluded the straggler"
    assert srv.stats.degraded_steps > 0
    assert len(srv.done) == 3
    for r in srv.done.values():
        assert r.status == "done" and len(r.output) == r.max_new


def _flood(aging: int) -> Request:
    """One-slot server, endless class-0 flood, one class-2 request."""
    ecfg = EngineConfig(capacity=128, max_batch=1, paged=False,
                        calibrate=False, prefill_chunk_pages=0,
                        decode_chunk=1, aging_steps=aging)
    eng = _StubEngine(ecfg, 4)
    srv = SlotServer(eng)
    srv.submit(Request(rid=0, max_new=2, tokens=np.full((3,), 0, np.int64)))
    low = Request(rid=999, max_new=2, tokens=np.full((3,), 96, np.int64),
                  priority=2)
    srv.submit(low)
    for rid in range(1, 41):
        # keep the class-0 queue non-empty: one fresh flood request a step
        srv.submit(Request(rid=rid, max_new=2,
                           tokens=np.full((3,), rid % 97, np.int64)))
        srv.step()
    return low


def test_priority_aging_no_starvation():
    """Aging promotes a waiting class-2 head one class per ``aging_steps``
    steps; once promoted to class 0 its earlier submit order beats every
    later flood arrival — delayed, never starved."""
    assert _flood(aging=2).status == "done"


def test_strict_priority_starves_without_aging():
    """The control: ``aging_steps = 0`` is strict priority, and the same
    flood starves the class-2 request indefinitely."""
    assert _flood(aging=0).status == "queued"


def test_scheduler_invariants_hypothesis():
    """Adversarial widening of the same property when hypothesis is
    available (CI installs requirements-dev.txt)."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.settings(
        max_examples=int(os.environ.get("HYPOTHESIS_MAX_EXAMPLES", "120")),
        deadline=None, suppress_health_check=list(hyp.HealthCheck))
    @hyp.given(seed=st.integers(0, 2**31 - 1), paged=st.booleans(),
               chunk_pages=st.integers(0, 3), spec=st.booleans(),
               prio=st.booleans(), preempt=st.booleans(),
               session=st.booleans())
    def prop(seed, paged, chunk_pages, spec, prio, preempt, session):
        _drive(np.random.default_rng(seed), paged=paged,
               chunk_pages=chunk_pages, spec=spec, prio=prio,
               preempt=preempt, session=session)

    prop()
