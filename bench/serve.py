"""Drive one cell: build the served model, warm it, load it for a window.

The window drives ``SlotServer.submit`` and ``SlotServer.step`` on the
configuration's ``Engine`` exactly as a server loop would. The harness
wraps the engine's dispatch methods (``decode_chunk``, ``insert_request``,
``chunk_step``, ``chunk_final``) to record what each launch carried and
to mark it with a host span; the wrapped call is the engine's own.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import time

import numpy as np

from . import traffic as tf


@dataclasses.dataclass
class Rec:
    """One request as the harness saw it (times: ``perf_counter`` s)."""
    rid: int
    prompt_len: int
    max_new: int
    due: float  # when it was due (open loop) or sent (closed loop)
    sent: float
    req: object  # the program's Request
    done_at: float | None = None


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read about one run."""
    model: dict
    n_slots: int
    pool_pages: int
    page_size: int
    block: int
    residual: int
    capacity: int
    t_start: float  # process start
    t0: float  # window open
    t1: float  # window close (end of the last step)
    stats0: dict  # SlotStats at window open
    stats1: dict  # ... and at close
    recs: list[Rec]
    logits: dict[int, np.ndarray]  # rid -> admission's last-token logits
    launches: list[dict]  # decode launches in the window
    prefills: list[dict]  # prefill dispatches in the window
    cache_bytes: int  # device bytes of the slot cache
    comp_bytes_per_token: float  # pool bytes per compressed token
    compiles_in_window: int
    lateness: list[float]  # open loop: send time minus due time
    peak: dict | None = None  # chip peaks (None off the chip)
    trace: object = None  # trace.Trace of the window, --trace 1 only
    window_ns: tuple[float, float] | None = None  # window on the trace clock

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def delta(self, name: str) -> int:
        return self.stats1[name] - self.stats0[name]


def span(name: str):
    """A host span in the profiler's trace (free when it is off)."""
    import jax

    return jax.profiler.TraceAnnotation(f"bench.{name}")


def arch_of(cfg: dict):
    """The program's ``ArchConfig`` for a configuration file: its arch with
    every field the file's ``model`` names set as the file says."""
    from repro.configs import get_arch
    from repro.configs.base import ArchConfig

    fields = {f.name for f in dataclasses.fields(ArchConfig)}
    arch = get_arch(cfg["arch"])
    return dataclasses.replace(
        arch, **{k: v for k, v in cfg["model"].items() if k in fields})


def build(cfg: dict, seed: int, ref, packkv: dict | None = None):
    """(engine, params) for a configuration, weights from the seed;
    ``packkv`` replaces the configuration's cache settings (the control)."""
    import jax
    from repro.core.cache import PackKVConfig
    from repro.models import get_model
    from repro.serving import Engine, EngineConfig

    arch = arch_of(cfg)
    want = jax.eval_shape(lambda: get_model(arch).init(
        jax.random.PRNGKey(0), arch))
    params = ref.program_params(seed, cfg["model"])
    got = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    exp = jax.tree.map(lambda x: (x.shape, x.dtype), want)
    if got != exp:
        raise ValueError(f"reference weights do not match the served layout: "
                         f"{got} vs {exp}")
    eng = Engine(arch, params, PackKVConfig(**(packkv or cfg["packkv"])),
                 EngineConfig(**cfg["engine"]))
    return eng, params


def pool_bytes_per_token(eng) -> float:
    """Cache bytes that one more pool page adds, per token it holds."""
    import jax

    a, e, p = eng.api, eng.ecfg, eng.pack_cfg
    size = lambda pages: sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(jax.eval_shape(
            lambda: a.alloc_cache(eng.cfg, dataclasses.replace(
                p, pool_pages=pages), e.max_batch, e.capacity))))
    return (size(p.pool_pages + 1) - size(p.pool_pages)) / e.page_size


def tree_bytes(tree) -> int:
    import jax

    return sum(x.nbytes for x in jax.tree.leaves(tree))


class Compiles:
    """Counts XLA programs built or loaded (``backend_compile`` events)."""

    def __init__(self):
        import jax

        self.n = 0

        def on(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(on)


def instrument(eng, srv, rec: dict) -> None:
    """Wrap the engine's dispatches: a span each, and while
    ``rec["on"]`` a record of what each launch carried. Every admission's
    last-token logits, which seed the request's first token, are kept on
    the host under its request number for the check."""

    def wrap(name, fn, note):
        def call(*a, **k):
            if rec["on"]:
                note(*a, **k)
            with span(name):
                out = fn(*a, **k)
            if rec["on"] and name == "decode":
                rec["launches"][-1]["n_exec"] = out[1]
            if name == "prefill":
                rec["last_logits"] = out[0]
            return out
        return call

    activate = srv._activate

    def on_activate(req, i, tok):
        if req.rid >= 0:
            rec["logits"][req.rid] = np.asarray(rec["last_logits"],
                                                np.float32)
        return activate(req, i, tok)

    def note_decode(cache, token, active, n_steps, eos_id, n_bucket=None):
        rows = [(len(s.req.tokens), s.cached_tokens)
                for s in srv.slots if s is not None]
        rec["launches"].append({"bucket": n_bucket, "rows": rows})

    def note_insert(cache, slot, tokens):
        n = len(tokens)
        rec["prefills"].append({"n_tokens": n, "n_ctx": 0, "last": True})

    def note_step(scratch, tokens, n_ctx):
        rec["prefills"].append({"n_tokens": len(tokens), "n_ctx": n_ctx,
                                "last": False})

    def note_final(cache, slot, scratch, tokens, n_ctx):
        rec["prefills"].append({"n_tokens": len(tokens), "n_ctx": n_ctx,
                                "last": True})

    eng.decode_chunk = wrap("decode", eng.decode_chunk, note_decode)
    eng.insert_request = wrap("prefill", eng.insert_request, note_insert)
    eng.chunk_step = wrap("prefill", eng.chunk_step, note_step)
    eng.chunk_final = wrap("prefill", eng.chunk_final, note_final)
    srv._activate = on_activate


def warm(eng, srv, mix: dict, seed: int) -> None:
    """Compile every program this cell's traffic can reach, and no other:
    the decode launch of each length bucket the traffic can touch, and the
    admission path of each prompt length."""
    import jax.numpy as jnp

    prompts, outs = tf.lengths(mix)
    hi = max(prompts) + max(outs) + eng.ecfg.decode_chunk
    buckets = sorted({eng.bucket_for(n) for n in range(min(prompts) + 1, hi)},
                     key=lambda b: -1 if b is None else b)
    cache = eng.alloc_slot_cache()
    tok = jnp.zeros((eng.ecfg.max_batch, 1), jnp.int32)
    idle = np.zeros(eng.ecfg.max_batch, bool)
    for b in buckets:  # no row is active: each launch runs zero steps
        _, _, cache = eng.decode_chunk(cache, tok, idle, 1, None, b)
    del cache
    from repro.serving import Request

    for i, p in enumerate(prompts):
        srv.submit(Request(rid=-1 - i, max_new=1,
                           tokens=tf.prompt_tokens(seed, 2**40 + i, p,
                                                   eng.cfg.vocab)))
    srv.run()


def pool_blocked(srv) -> bool:
    """No admission runs and the next waiting request needs more pool
    pages than are free: admission waits for a request to finish."""
    if not srv.engine.ecfg.paged:
        return False
    head = srv._head()
    return (head is not None and srv._task is None
            and srv._pages_needed(head) > srv._pages_avail)


def stats_of(srv) -> dict:
    s = srv.stats
    return {f.name: getattr(s, f.name) for f in dataclasses.fields(s)
            if f.name != "launches"}


def drive(cfg: dict, mix: dict, seed: int, seconds: float, ref, *,
          t_start: float, trace: bool = False, log=print,
          packkv: dict | None = None):
    """Build, warm and load one cell, then free the program's state.
    Returns (Run, the device's peak bytes in use or None)."""
    import jax
    from repro.serving import Request, SlotServer

    from . import trace as tr

    eng, params = build(cfg, seed, ref, packkv)
    log(f"built: weights and engine ({time.perf_counter() - t_start:.1f}s "
        f"since start)")
    srv = SlotServer(eng)
    rec = {"on": False, "launches": [], "prefills": [], "logits": {}}
    instrument(eng, srv, rec)
    compiles = Compiles()
    warm(eng, srv, mix, seed)
    log(f"warm: {compiles.n} programs built or loaded "
        f"({time.perf_counter() - t_start:.1f}s since start)")

    recs: dict[int, Rec] = {}
    next_rid = [0]
    vocab = eng.cfg.vocab

    def send(card, due):
        rid = next_rid[0]
        next_rid[0] += 1
        req = Request(rid=rid, max_new=card.output_len,
                      tokens=tf.prompt_tokens(seed, rid, card.prompt_len,
                                              vocab))
        with span("submit"):
            srv.submit(req)
        recs[rid] = Rec(rid, card.prompt_len, card.output_len, due,
                        time.perf_counter(), req)
        return recs[rid]

    closed = mix["loop"] == "closed"
    lateness: list[float] = []
    pending: list = []
    if closed:
        stream = tf.cards(mix)
        n_fill = min(int(mix["clients"]), srv.n_slots)
        for _ in range(int(mix["clients"])):
            send(next(stream), time.perf_counter())
        # fill every slot before the window, or as many as the pool's
        # free pages admit
        while srv.n_occupied < n_fill and not pool_blocked(srv):
            for r in srv.step():
                recs[r.rid].done_at = time.perf_counter()
                send(next(stream), time.perf_counter())
    else:
        t_sched = time.perf_counter() + float(mix.get("warmup_s", 0.0))
        pending = [(t_sched + a.due_s, a.card)
                   for a in tf.arrivals(mix, seconds)]
    window = {"t0": float("inf")}

    def pump(now):
        """Open loop: send every request now due."""
        while pending and pending[0][0] <= now:
            due, card = pending.pop(0)
            r = send(card, due)
            if due >= window["t0"]:
                lateness.append(r.sent - due)

    def loop(until):
        while (now := time.perf_counter()) < until:
            with span("generator"):
                pump(now)
            if not srv.queue and not srv.n_occupied and \
                    getattr(srv, "_task", None) is None:
                nxt = pending[0][0] if pending else until
                time.sleep(max(0.0, min(nxt, until) - now))
                continue
            with span("step"):
                done = srv.step()
            for r in done:
                recs[r.rid].done_at = time.perf_counter()
                if closed:
                    send(next(stream), time.perf_counter())

    if not closed:
        loop(t_sched)  # warm-up arrivals: a steady state before the window
    log(f"filled: {srv.n_occupied} of {srv.n_slots} slots busy "
        f"({time.perf_counter() - t_start:.1f}s since start)")
    ctx = tr.capture() if trace else contextlib.nullcontext()
    gc.collect()
    gc.disable()  # no collector pause inside the window
    with ctx as cap:
        stats0 = stats_of(srv)
        n0 = compiles.n
        rec["on"] = True
        with span("window"):
            t0 = window["t0"] = time.perf_counter()
            loop(t0 + seconds)
            t1 = time.perf_counter()
        rec["on"] = False
    gc.enable()
    stats1 = stats_of(srv)
    in_window = compiles.n - n0
    dev = jax.devices()[0]
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    run = Run(
        model=cfg["model"], n_slots=srv.n_slots,
        pool_pages=eng.pack_cfg.pool_pages, page_size=eng.ecfg.page_size,
        block=eng.pack_cfg.block, residual=eng.pack_cfg.residual,
        capacity=eng.ecfg.capacity, t_start=t_start, t0=t0, t1=t1,
        stats0=stats0, stats1=stats1, recs=list(recs.values()),
        logits=rec["logits"],
        launches=rec["launches"], prefills=rec["prefills"],
        cache_bytes=tree_bytes(srv.cache),
        comp_bytes_per_token=pool_bytes_per_token(eng),
        compiles_in_window=in_window, lateness=lateness)
    if trace:
        if cap["path"] is None:
            raise RuntimeError("the profiler wrote no trace")
        run.trace = tr.load(cap["path"])
        tr.cleanup(cap)
        run.window_ns = run.trace.window()
    # the program's state goes before the reference runs
    srv.cache = None
    del srv, eng, params
    gc.collect()
    return run, mem
