"""Serving engine: calibration, jitted prefill/decode, continuous batching.

Build sequence (mirrors a production bring-up):
  1. CALIBRATE — run a short prefill with the uncompressed policy, collect
     raw K/V, pick static TierSpecs (core.cache.calibrate_specs). This is
     the paper's per-model configuration sweep (§IV-B) done once at engine
     build, before compilation.
  2. COMPILE — jit prefill + decode with the calibrated PackKVConfig.
  3. SERVE — ``SlotServer`` runs a continuous-batching scheduler over a
     fixed slot table of ``max_batch`` rows. Every sequence owns one row of
     the decode state — per-row ``n_comp``/``n_resid`` counters for the KV
     families, a batch row of the recurrent leaves for rwkv6/hybrid_rglru:
     a queued request is admitted into a free slot at its TRUE prompt
     length (no left-padding, so pad tokens never pollute cache or
     recurrent state), all occupied slots decode together each step, and a
     row is recycled the moment its request finishes (EOS / max_new) while
     the other rows keep decoding.

EVERY family serves through this one engine (the old ``WaveServer``
left-pad wave is gone). Admission is CHUNK-INTERLEAVED by default: instead
of a monolithic prefill dispatch that stalls every occupied slot for the
whole prompt, the scheduler advances the pending admission by at most
``EngineConfig.prefill_chunk_pages`` pages' worth of tokens per step and
runs a decode launch in the same cadence — no occupied slot ever waits
longer than one bounded chunk for its next token. See docs/serving.md for
the slot table layout, admission policy and per-row counter plumbing, and
docs/architecture.md for the paged pool.

Invariants the scheduler maintains (and the cache layer relies on):
  * the host-side token counts (``_Active.cached_tokens``) upper-bound the
    device counters — buckets and page reservations are computed without a
    device sync and are always safe over-estimates;
  * in paged mode, reserved pages (sum over active slots of worst-case
    ``ceil(min(capacity, prompt + max_new) / page_size)``) never exceed
    ``pool_pages - page_watermark`` — the in-graph free-list can never
    over-pop, so oversubscribed pools serve mixed traffic exactly;
  * a retired slot's pages are back in the pool (``reset_slot``) before
    the next admission runs, so FIFO admission makes progress whenever any
    slot retires.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from functools import partial, wraps

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from ..core.cache import (
    PackKVConfig,
    SessionStore,
    SwapStore,
    calibrate_specs,
)
from ..distributed.fault import FaultPlan, StragglerMonitor
from ..models import get_model

Array = jax.Array


def span(name: str, **args):
    """The host span ``packkv.<name>`` in any ``jax.profiler`` trace, on
    the device trace's clock (about a microsecond when no trace runs)."""
    return jax.profiler.TraceAnnotation(f"packkv.{name}", **args)


def _named(fn, name: str):
    """``fn`` under ``name``: jit names its XLA module ``jit_<name>``."""
    @wraps(fn)
    def call(*args, **kwargs):
        return fn(*args, **kwargs)

    call.__name__ = call.__qualname__ = name
    return call


@dataclasses.dataclass
class EngineConfig:
    capacity: int = 4096  # compressed-region token capacity
    max_batch: int = 8  # slot-table size
    backend: str = "xla"  # xla | pallas
    calibrate: bool = True
    calib_tokens: int = 192  # multiple of the 64-token block
    # multi-device serving (see kernels/sharded.py + docs/architecture.md):
    mesh_shape: tuple = (1, 1)  # (dp, kv): data-parallel row shards x
    #   KV-head shards. (1, 1) = feature off (plain single-device jits,
    #   bit-for-bit the pre-mesh engine). Anything else builds a
    #   jax Mesh over dp*kv devices and routes every cache-touching
    #   dispatch through a shard_map lane: pool payloads shard by KV head
    #   over 'kv', the page ledger + counters stay replicated, attention
    #   work partitions over 'dp' by row masking. Outputs stay
    #   bit-identical to (1, 1); recurrent families reject loudly.
    # length-aware launches (see docs/performance.md):
    bucketed: bool = True  # slice the compressed region to a live-length bucket
    bucket_unit: int = 256  # smallest bucket; power-of-two multiples up to capacity
    decode_chunk: int = 8  # decode steps per donated multi-step launch (1 = per-token)
    log_launches: bool = False  # keep per-launch telemetry (unbounded; bench only)
    # self-speculative decode (see docs/performance.md):
    spec_decode: bool = False  # n-gram drafting + batched k-token verify
    spec_k: int = 4  # max drafted tokens per verify launch (window = k + 1)
    spec_backoff: int = 32  # max per-slot draft cooldown (scheduler steps)
    #   after fully-rejected launches: doubles 1, 2, .. spec_backoff while a
    #   slot's drafts keep dying, so acceptance~0 traffic degrades to the
    #   plain chunked-decode path instead of paying verify windows for one
    #   token each. Any accepted draft resets the slot to eager drafting.
    #   0 disables the backoff (every launch drafts when the table matches).
    # chunked prefill/decode interleaving (see docs/serving.md):
    prefill_chunk_pages: int = 1  # admission chunk budget, in pages of
    #   ``page_size`` tokens per scheduler step (dense engines use the same
    #   token unit). 0 = legacy monolithic prefill-insert: the whole prompt
    #   in one dispatch, stalling every occupied slot for its duration.
    # paged compressed region (see docs/architecture.md):
    paged: bool = False  # page-pool storage + page-reservation admission
    page_size: int = 256  # tokens per physical page (power of two, >= block)
    pool_pages: int | None = None  # physical pages; None = max_batch * capacity
    #   / page_size (no oversubscription). Setting it lower oversubscribes:
    #   admission then blocks on page reservations instead of free slots.
    page_watermark: int = 0  # spare pages admission always holds back
    # shared-prefix page cache (requires paged; see docs/serving.md):
    prefix_cache: bool = False  # content-addressed prefix reuse across requests
    prefix_cache_pages: int | None = None  # max pages the index may pin
    #   (None = unbounded; pool-pressure eviction still applies either way)
    # preemptive serving (see docs/serving.md):
    preempt: bool = False  # compressed-page swap-out of lower-class victims
    #   when a higher-class admission cannot reserve pages (or find a slot);
    #   the victim resumes later bit-identically from a host-RAM SwapStore
    aging_steps: int = 32  # scheduler steps per priority-class promotion of
    #   a queued request (the no-starvation bound: a class-p head competes
    #   as class 0 after p * aging_steps steps). 0 disables aging — strict
    #   priority, a permanent high-class flood then starves lower classes.
    # voluntary multi-turn session cache (ISSUE 9; see docs/serving.md):
    session_cache: bool = False  # park a retiring slot's compressed pages
    #   host-side, keyed by the session's raw token trace; a returning
    #   turn streams them back (no forward pass over restored tokens) and
    #   ingests only its new suffix through teacher-forced decode launches
    session_cache_mb: int = 256  # host-RAM tier budget (LRU by bytes)
    session_ttl_s: float | None = None  # idle expiry for parked sessions
    #   (None = parked entries never age out)
    session_disk_dir: str | None = None  # LRU spill tier: demote host-tier
    #   victims to disk via the checkpoint.sharded mini-cache serializers
    #   instead of dropping them (None = evict outright)
    debug_invariants: bool = False  # assert refcount conservation after every
    #   admit/retire (device sync per check — tests/bring-up only)


class Engine:
    def __init__(self, cfg: ArchConfig, params, pack_cfg: PackKVConfig,
                 ecfg: EngineConfig):
        self.cfg = cfg
        self.params = params
        self.ecfg = ecfg
        self.api = get_model(cfg)
        mesh_shape = tuple(ecfg.mesh_shape)
        if mesh_shape == (1, 1):
            self.mesh = None  # feature off: plain single-device jits
        else:
            n_dp, n_kv = mesh_shape
            if n_dp < 1 or n_kv < 1:
                raise ValueError(f"mesh_shape must be positive, got "
                                 f"{mesh_shape}")
            if cfg.family in ("rwkv6", "hybrid_rglru"):
                raise ValueError(
                    f"family {cfg.family!r} cannot serve --mesh: its "
                    "recurrent slot state has no KV-head axis to shard "
                    "over the 'kv' mesh axis — drop --mesh (single-device "
                    "serving still applies)")
            if cfg.n_kv_heads % n_kv:
                raise ValueError(
                    f"n_kv_heads {cfg.n_kv_heads} not divisible by "
                    f"kv_shards {n_kv} — pool payloads shard by whole KV "
                    "heads")
            n_dev = len(jax.devices())
            if n_dev < n_dp * n_kv:
                raise ValueError(
                    f"mesh {n_dp}x{n_kv} needs {n_dp * n_kv} devices, have "
                    f"{n_dev} (host-platform testing: set "
                    "XLA_FORCE_HOST_PLATFORM_DEVICE_COUNT / "
                    "--xla_force_host_platform_device_count before jax "
                    "initializes)")
            from jax.sharding import Mesh, NamedSharding, PartitionSpec

            devs = np.array(jax.devices()[: n_dp * n_kv]).reshape(n_dp, n_kv)
            self.mesh = Mesh(devs, ("dp", "kv"))
            # params are replicated once at build; every lane reads them
            # with a replicated in_spec, so no dispatch re-broadcasts
            self.params = jax.device_put(
                self.params, NamedSharding(self.mesh, PartitionSpec()))
        if ecfg.prefix_cache:
            if self.api.prefill_prefix is None:
                raise ValueError(
                    f"family {cfg.family!r} cannot serve --prefix-cache: its "
                    "recurrent decode state has no page-addressable KV pages "
                    "to share — drop --prefix-cache (plain chunked admission "
                    "still applies)"
                )
            if not ecfg.paged:
                raise ValueError(
                    "--prefix-cache requires --paged: shared prefixes live "
                    "in the refcounted page pool"
                )
            if cfg.window:
                raise ValueError(
                    "--prefix-cache does not support sliding-window "
                    f"attention (window={cfg.window}): evicted window "
                    "tokens break page-aligned prefix identity"
                )
        if ecfg.paged:
            if not self.api.supports_paged:
                raise ValueError(
                    f"family {cfg.family!r} cannot serve paged: its "
                    "recurrent decode state is not page-addressable"
                )
            if ecfg.capacity % ecfg.page_size:
                raise ValueError(
                    f"capacity {ecfg.capacity} not a multiple of page_size "
                    f"{ecfg.page_size}"
                )
            pool_pages = (
                ecfg.pool_pages
                if ecfg.pool_pages is not None
                else ecfg.max_batch * ecfg.capacity // ecfg.page_size
            )
            pack_cfg = dataclasses.replace(
                pack_cfg, paged=True, page_size=ecfg.page_size,
                pool_pages=pool_pages,
            )
        self.pack_cfg = (
            self._calibrate(pack_cfg) if (
                ecfg.calibrate
                and pack_cfg.policy == "packkv"
                and cfg.family not in ("rwkv6",)
            ) else pack_cfg
        )
        self._prefill = self._lane_jit(
            partial(self.api.prefill, cfg=cfg, pack_cfg=self.pack_cfg,
                    capacity=ecfg.capacity),
            name="packkv_prefill",
        )
        # one compile per launch bucket (bounded: core.cache.bucket_set)
        self._decode = self._lane_jit(
            partial(self.api.decode_step, cfg=cfg, backend=ecfg.backend),
            name="packkv_decode", static=("n_bucket",),
        )
        # one compile per distinct prompt length; slot index is traced
        self._insert = self._lane_jit(
            partial(self.api.prefill_into_slot, cfg=cfg,
                    pack_cfg=self.pack_cfg, capacity=ecfg.capacity),
            name="packkv_insert",
        )
        self._reset = self._lane_jit(self.api.reset_slot, name="packkv_reset")
        self._mask_free = self._lane_jit(self.api.mask_free,
                                         name="packkv_mask_free")
        # chunked interleaved admission: one bounded prefill chunk per
        # scheduler step (one compile per distinct (chunk length, offset)).
        # The chunk scratch is raw full-head K/V and carries no cache, so
        # it stays a plain replicated jit even on a mesh.
        self._chunk_step = jax.jit(
            _named(partial(self.api.prefill_chunk, cfg=cfg,
                           pack_cfg=self.pack_cfg), "packkv_chunk_step"),
            static_argnames=("n_ctx",),
        )
        self._chunk_insert = self._lane_jit(
            partial(self.api.prefill_chunk_insert, cfg=cfg,
                    pack_cfg=self.pack_cfg, capacity=ecfg.capacity),
            name="packkv_chunk_insert",
        )

        def _chunk_final_fn(params, cache, slot, scratch, tokens, n_ctx):
            logits, scratch = self.api.prefill_chunk(
                params, scratch=scratch, tokens=tokens, n_ctx=n_ctx,
                cfg=cfg, pack_cfg=self.pack_cfg
            )
            cache = self.api.prefill_chunk_insert(
                cache=cache, slot=slot, scratch=scratch,
                cfg=cfg, pack_cfg=self.pack_cfg, capacity=ecfg.capacity
            )
            return logits, cache

        # final chunk fused with the row insert: one dispatch instead of
        # chunk_step + chunk_insert, and no scratch round-trip, on the last
        # step of every multi-chunk admission
        self._chunk_final = self._lane_jit(_chunk_final_fn,
                                           name="packkv_chunk_final",
                                           static=("n_ctx",))
        if ecfg.prefix_cache:
            from ..core.cache import acquire_pages, release_pages

            # one compile per (prompt length, matched-prefix length) pair
            self._insert_prefix = self._lane_jit(
                partial(self.api.prefill_prefix, cfg=cfg,
                        pack_cfg=self.pack_cfg, capacity=ecfg.capacity),
                name="packkv_insert_prefix", static=("n_prefix",),
            )
            # interleaved prefix admission: the same per-page segments,
            # one dispatch each (mini-cache round-trips between them)
            self._prefix_chunk_init = self._lane_jit(
                partial(self.api.prefix_chunk_init, cfg=cfg,
                        pack_cfg=self.pack_cfg, capacity=ecfg.capacity),
                name="packkv_prefix_chunk_init",
                static=("n_prefix", "prompt_len"),
            )
            self._prefix_chunk = self._lane_jit(
                partial(self.api.prefix_chunk, cfg=cfg,
                        pack_cfg=self.pack_cfg),
                name="packkv_prefix_chunk", static=("n_ctx",),
            )
            self._prefix_chunk_insert = self._lane_jit(
                partial(self.api.prefix_chunk_insert, pack_cfg=self.pack_cfg),
                name="packkv_prefix_chunk_insert",
                static=("n_prefix", "prompt_len"),
            )
            # index pin/unpin ops take sentinel-padded fixed-length id
            # vectors, so each compiles exactly once
            self._acquire_pages = self._lane_jit(
                acquire_pages, name="packkv_acquire_pages")
            self._release_pages = self._lane_jit(
                release_pages, name="packkv_release_pages")
            self._dummy_perm = jnp.broadcast_to(
                jnp.arange(cfg.hd, dtype=jnp.int32),
                (cfg.n_layers, cfg.n_kv_heads, cfg.hd),
            )
        if ecfg.spec_decode:
            if self.api.decode_verify is None:
                raise ValueError(
                    f"family {cfg.family!r} cannot serve --spec-decode: its "
                    "recurrent state update is sequential per token, so "
                    "there is no batched q_len=k verify pass to amortize "
                    "the weights-read over — drop --spec-decode"
                )
            if ecfg.spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {ecfg.spec_k}")
            # one batched forward over the q_len = spec_k + 1 draft window;
            # fixed window width -> one compile per launch bucket, ragged
            # per-row draft lengths ride through the ``lens`` mask. The
            # acceptance rule, the counter-only commit of the accepted
            # prefix, and free-row masking all run inside the same program
            # (models/*.verify_steps), so one dispatch per spec step.
            self._verify = self._lane_jit(
                partial(self.api.decode_verify, cfg=cfg, backend=ecfg.backend),
                name="packkv_verify",
                static=("n_bucket",),
                donate=("cache",),
            )
        if ecfg.session_cache and cfg.window:
            raise ValueError(
                "--session-cache does not support sliding-window attention "
                f"(window={cfg.window}): evicted window tokens break the "
                "parked-trace identity the session key relies on"
            )
        if ecfg.preempt or ecfg.session_cache:
            if self.api.evacuate_slot is None:
                feature = "--preempt" if ecfg.preempt else "--session-cache"
                raise ValueError(
                    f"family {cfg.family!r} cannot serve {feature}: its "
                    "recurrent slot state has no evacuate/restore ops to "
                    f"swap through — drop {feature}"
                )
            # one compile per (live pages, shared-prefix pages) pair — the
            # same specialization granularity as prompt-length admission
            self._evacuate = self._lane_jit(
                self.api.evacuate_slot, name="packkv_evacuate",
                static=("n_pages", "n_shared"),
            )
            self._restore = self._lane_jit(
                self.api.restore_slot, name="packkv_restore",
                static=("n_pages", "n_shared"),
            )
        if self.api.decode_multi is not None:
            # donated multi-step decode: the chunk loop updates the cache
            # buffers in place (no per-token copy) and one dispatch covers
            # up to ``decode_chunk`` tokens
            self._decode_multi = self._lane_jit(
                partial(self.api.decode_multi, cfg=cfg, backend=ecfg.backend),
                name="packkv_decode_multi",
                static=("t_max", "n_bucket"),
                donate=("cache",),
            )
        else:
            self._decode_multi = None

    # -- mesh lanes ---------------------------------------------------------
    def _lane_jit(self, fn, *, name, static=(), donate=()):
        """jit one serving dispatch as the XLA program ``jit_<name>`` (the
        name a profile shows); on a mesh, the body runs inside a shard_map
        lane (kernels/sharded.py) with cache-spec-derived in/out specs.
        Off-mesh (``mesh_shape == (1, 1)``) this is ``jax.jit(fn)`` under
        that name — the pre-mesh engine's compiled code, byte for byte.

        Mechanics on a mesh: the wrapper binds the caller's args against
        ``fn``'s signature, closes over the static (python) args, derives
        per-arg PartitionSpecs by name (``LayerKVCache`` args/outputs get
        ``serving_cache_specs`` — payloads by KV head, ledger replicated;
        calibration perms shard their head dim; everything else is
        replicated), gets output specs from ``jax.eval_shape`` over the
        unsharded body (global shapes ARE the out-spec shapes), and
        dispatches through ``sharded_call``, which installs the Lane the
        model code queries via ``active_lane()``.
        """
        if self.mesh is None:
            return jax.jit(_named(fn, name), static_argnames=static,
                           donate_argnames=donate)
        import inspect

        from ..distributed.sharding import serving_specs
        from ..kernels.sharded import sharded_call

        mesh = self.mesh
        sig = inspect.signature(fn)

        def mesh_fn(*args, **kwargs):
            ba = sig.bind(*args, **kwargs)
            statics = {k: ba.arguments.pop(k) for k in static
                       if k in ba.arguments}
            names = list(ba.arguments)
            vals = [ba.arguments[k] for k in names]
            body = lambda *a: fn(**dict(zip(names, a)), **statics)
            in_specs = tuple(self._arg_specs(n, v)
                             for n, v in zip(names, vals))
            out_specs = serving_specs(jax.eval_shape(body, *vals), mesh)
            return sharded_call(body, mesh, in_specs, out_specs)(*vals)

        mesh_fn.__signature__ = sig  # so jit resolves static/donated names
        mesh_fn.__name__ = mesh_fn.__qualname__ = name
        return jax.jit(mesh_fn, static_argnames=static, donate_argnames=donate)

    def _arg_specs(self, name, val):
        from ..distributed.sharding import serving_specs, spec_with_fallback

        if name in ("k_perm", "v_perm"):
            # [n_layers, H_kv, D] calibration perms ride head-sharded so
            # the lane's local mini-cache seeds from its own head block
            want = [None] * (val.ndim - 2) + ["kv", None]
            return spec_with_fallback(val.shape, want, self.mesh)
        return serving_specs(val, self.mesh)

    # -- calibration --------------------------------------------------------
    def _calibrate(self, pack_cfg: PackKVConfig) -> PackKVConfig:
        S = self.ecfg.calib_tokens
        rng = np.random.default_rng(0)
        B = 1
        batch = {"tokens": jnp.asarray(rng.integers(0, self.cfg.vocab, (B, S)),
                                       jnp.int32)}
        if self.cfg.input_mode == "tokens_patches":
            batch["patches"] = jnp.asarray(
                rng.normal(size=(B, self.cfg.n_patches, self.cfg.d_model)),
                jnp.float32,
            )
        # calibration reads raw prefill K/V from a dense layout; paged
        # placement is irrelevant to spec choice, so strip it here
        none_cfg = dataclasses.replace(pack_cfg, policy="none", paged=False)
        cap = max(S + self.cfg.n_patches * (self.cfg.input_mode == "tokens_patches"),
                  pack_cfg.block)
        cap = -(-cap // pack_cfg.block) * pack_cfg.block
        if self.cfg.family == "hybrid_rglru":
            _, state = self.api.prefill(self.params, self.cfg, none_cfg, cap, batch)
            cache = state.cache
            n = min(int(jnp.min(cache.n_comp)), self.cfg.window)
        else:
            _, cache = self.api.prefill(self.params, self.cfg, none_cfg, cap, batch)
            n = int(jnp.min(cache.n_comp))
        n = (n // pack_cfg.block) * pack_cfg.block
        if n == 0:
            return pack_cfg
        rk, rv = cache.raw_k, cache.raw_v  # [L?, B, H, cap, D]
        lead = rk.shape[: rk.ndim - 3]
        D = rk.shape[-1]
        k = rk.reshape(-1, *rk.shape[-3:])[:, :, :n, :]  # [L*B, H, n, D]
        v = rv.reshape(-1, *rv.shape[-3:])[:, :, :n, :]
        return calibrate_specs(k, v, pack_cfg)

    # -- serving ------------------------------------------------------------
    def prefill(self, batch: dict):
        return self._prefill(self.params, batch=batch)

    def decode(self, cache, token: Array, n_bucket: int | None = None):
        return self._decode(self.params, cache=cache, token=token,
                            n_bucket=n_bucket)

    def decode_chunk(self, cache, token: Array, active, n_steps: int,
                     eos_id: int | None, n_bucket: int | None = None):
        """Donated multi-step decode (see models/*.decode_steps).

        The ``cache`` argument is DONATED: the caller must drop its
        reference and use the returned cache. Returns
        (tokens np [t_max, B], n_exec int, cache). Spans: the dispatch
        (``packkv.decode.dispatch``) and the host's wait for its tokens
        (``packkv.decode.wait``, which records ``n_exec``).
        """
        bucket = self.ecfg.capacity if n_bucket is None else n_bucket
        with span("decode.dispatch", bucket=bucket, n_steps=n_steps):
            toks, n_exec, cache = self._decode_multi(
                self.params,
                cache=cache,
                token=token,
                active=jnp.asarray(active, bool),
                n_steps=jnp.int32(n_steps),
                eos_id=jnp.int32(-1 if eos_id is None else eos_id),
                t_max=self.ecfg.decode_chunk,
                n_bucket=n_bucket,
            )
        with span("decode.wait") as wait:
            toks, n_exec = np.asarray(toks), int(n_exec)
            wait.set_metadata(n_exec=n_exec)
        return toks, n_exec, cache

    def decode_verify(self, cache, tokens: np.ndarray, lens: np.ndarray,
                      active, n_bucket: int | None = None):
        """One speculative verify launch (see models/*.verify_steps).

        tokens: [B, w] i32 host array (seed + drafts, junk-padded); lens:
        [B] valid window lengths; active: bool [B] occupied rows. The
        ``cache`` argument is DONATED and comes back with the accepted
        prefixes already committed and free rows re-zeroed. Returns
        (hat np [B, w] — per-position greedy argmax, n_accept np [B],
        cache)."""
        hat, n_accept, cache = self._verify(
            self.params,
            cache=cache,
            tokens=jnp.asarray(tokens, jnp.int32),
            lens=jnp.asarray(lens, jnp.int32),
            active=jnp.asarray(active, bool),
            n_bucket=n_bucket,
        )
        return np.asarray(hat), np.asarray(n_accept), cache

    def bucket_for(self, n_max: int) -> int | None:
        """Launch bucket covering ``n_max`` compressed tokens (None = full).

        Paged engines bucket the PAGE COUNT: the unit is raised to the page
        size so every bucket is a whole number of pages and the gather /
        page-indexed kernels see page-aligned launches.
        """
        if not self.ecfg.bucketed or not self.api.supports_paged:
            # recurrent families ignore n_bucket (O(1)/window-bounded
            # state); None avoids one decode recompile per bucket value
            return None
        from ..core.cache import bucket_length

        unit = self.ecfg.bucket_unit
        if self.ecfg.paged:
            unit = max(unit, self.ecfg.page_size)
        return bucket_length(n_max, self.ecfg.capacity, unit)

    def alloc_slot_cache(self):
        """Slot-table decode cache: max_batch rows, per-row counters.

        On a mesh the fresh cache is placed with its serving shardings up
        front (payloads by KV head over 'kv', ledger + counters
        replicated), so every later lane dispatch consumes and produces
        it with zero resharding."""
        cache = self.api.alloc_cache(
            self.cfg, self.pack_cfg, self.ecfg.max_batch, self.ecfg.capacity
        )
        if self.mesh is not None:
            from ..distributed.sharding import serving_cache_specs, to_named

            cache = jax.device_put(
                cache, to_named(serving_cache_specs(cache, self.mesh),
                                self.mesh))
        return cache

    def insert_request(self, cache, slot: int, tokens: np.ndarray):
        """Jitted single-slot prefill-insert; returns (last logits [V], cache)."""
        batch = {"tokens": jnp.asarray(np.asarray(tokens)[None], jnp.int32)}
        logits, cache = self._insert(
            self.params, cache=cache, slot=jnp.int32(slot), batch=batch
        )
        return logits[0], cache

    def insert_request_prefix(self, cache, slot: int, tokens: np.ndarray,
                              pages, perms):
        """Jitted chunked prefill-insert (prefix-cache engines only).

        ``pages``: physical ids of the matched page-aligned prompt prefix
        (mapped into the slot by reference — empty for a cold admission);
        ``perms``: the index entry's (k_perm, v_perm) calibration, or None
        (cold / policy 'none'). Returns (last logits [V], cache)."""
        batch = {"tokens": jnp.asarray(np.asarray(tokens)[None], jnp.int32)}
        phys = jnp.asarray(np.asarray(pages, np.int64), jnp.int32)
        kp, vp = perms if perms is not None else (self._dummy_perm,
                                                  self._dummy_perm)
        logits, cache = self._insert_prefix(
            self.params, cache=cache, slot=jnp.int32(slot), batch=batch,
            prefix_phys=phys, k_perm=kp, v_perm=vp,
            n_prefix=len(pages) * self.ecfg.page_size,
        )
        return logits[0], cache

    # -- chunked interleaved admission --------------------------------------
    def chunk_tokens(self) -> int:
        """Admission chunk budget in tokens (page-aligned)."""
        return self.ecfg.prefill_chunk_pages * self.ecfg.page_size

    def chunk_init(self, prompt_len: int):
        """Fresh admission scratch for a ``prompt_len``-token prompt."""
        return self.api.prefill_chunk_init(
            self.cfg, self.pack_cfg, self.ecfg.capacity, prompt_len=prompt_len
        )

    def chunk_step(self, scratch, tokens: np.ndarray, n_ctx: int):
        """One bounded prefill chunk at absolute offset ``n_ctx`` (STATIC).
        Returns (last-token logits [V], scratch) — only the final chunk's
        logits are meaningful."""
        logits, scratch = self._chunk_step(
            self.params, scratch=scratch,
            tokens=jnp.asarray(np.asarray(tokens)[None], jnp.int32),
            n_ctx=n_ctx,
        )
        return logits[0], scratch

    def chunk_insert(self, cache, slot: int, scratch):
        """Finish a chunked admission: build + scatter row ``slot``."""
        return self._chunk_insert(
            cache=cache, slot=jnp.int32(slot), scratch=scratch
        )

    def chunk_final(self, cache, slot: int, scratch, tokens: np.ndarray,
                    n_ctx: int):
        """Fused last chunk: prefill the final segment AND scatter the
        finished row into slot ``slot``, one dispatch. Returns (last-token
        logits [V], cache)."""
        logits, cache = self._chunk_final(
            self.params, cache, jnp.int32(slot), scratch,
            jnp.asarray(np.asarray(tokens)[None], jnp.int32), n_ctx=n_ctx,
        )
        return logits[0], cache

    def prefix_chunk_bounds(self, prompt_len: int, n_matched_pages: int):
        """Host-side segment bounds for an interleaved prefix admission."""
        return self.api.prefix_chunk_bounds(
            self.pack_cfg, prompt_len, n_matched_pages * self.ecfg.page_size
        )

    def prefix_chunk_start(self, cache, prompt_len: int, pages, perms):
        """Mini-cache seeded with the matched shared pages (prefix engines)."""
        phys = jnp.asarray(np.asarray(pages, np.int64), jnp.int32)
        kp, vp = perms if perms is not None else (self._dummy_perm,
                                                  self._dummy_perm)
        return self._prefix_chunk_init(
            cache=cache, prefix_phys=phys, k_perm=kp, v_perm=vp,
            n_prefix=len(pages) * self.ecfg.page_size, prompt_len=prompt_len,
        )

    def prefix_chunk_step(self, mini, tokens: np.ndarray, n_ctx: int):
        """One page-aligned segment of an interleaved prefix admission."""
        logits, mini = self._prefix_chunk(
            self.params, mini=mini,
            tokens=jnp.asarray(np.asarray(tokens)[None], jnp.int32),
            n_ctx=n_ctx,
        )
        return logits[0], mini

    def prefix_chunk_finish(self, cache, slot: int, mini, pages,
                            prompt_len: int):
        """Scatter the finished mini-cache into pool pages (shared prefix
        pages mapped by reference)."""
        phys = jnp.asarray(np.asarray(pages, np.int64), jnp.int32)
        return self._prefix_chunk_insert(
            cache=cache, slot=jnp.int32(slot), mini=mini, prefix_phys=phys,
            n_prefix=len(pages) * self.ecfg.page_size, prompt_len=prompt_len,
        )

    def _pad_ids(self, ids) -> Array:
        """Sentinel-pad page ids to the fixed per-slot table width so the
        pin/unpin jits compile once (sentinel entries are dropped)."""
        width = self.ecfg.capacity // self.ecfg.page_size
        out = np.full((width,), self.pack_cfg.pool_pages, np.int64)
        out[: len(ids)] = np.asarray(ids, np.int64)
        return jnp.asarray(out, jnp.int32)

    def index_acquire(self, cache, ids):
        """Pin pages for the prefix index (+1 ref each)."""
        return self._acquire_pages(cache, self._pad_ids(ids))

    def index_release(self, cache, ids):
        """Unpin evicted index pages (-1 ref; freed at zero)."""
        return self._release_pages(cache, self._pad_ids(ids))

    def free_slot(self, cache, slot: int):
        return self._reset(cache, jnp.int32(slot))

    def evacuate(self, cache, slot: int, n_pages: int, n_shared: int = 0):
        """Swap row ``slot`` out: returns (cache with the row freed, dense
        B=1 mini-cache holding the row's owned bytes). ``n_pages`` is the
        row's exact live page count (host-mirrored), ``n_shared`` its
        shared-prefix pages (released by reference, not copied)."""
        return self._evacuate(cache, jnp.int32(slot), n_pages=n_pages,
                              n_shared=n_shared)

    def restore(self, cache, slot: int, mini, shared_phys=(),
                n_pages: int = 0, n_shared: int = 0):
        """Stream an evacuated row back into ``slot`` (no forward pass)."""
        phys = jnp.asarray(np.asarray(shared_phys, np.int64), jnp.int32)
        return self._restore(cache, jnp.int32(slot), mini, phys,
                             n_pages=n_pages, n_shared=n_shared)

    def mask_free(self, cache, active):
        """Re-zero counters of inactive rows (see core.cache.mask_free_slots)."""
        return self._mask_free(cache, active)

    def generate(self, batch: dict, max_new: int, eos_id: int | None = None):
        """Greedy wave decode. Returns tokens [B, max_new] (stops early only
        when every row has emitted ``eos_id``)."""
        logits, cache = self.prefill(batch)
        B = logits.shape[0]
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        done = jnp.zeros((B,), bool)
        outs = []
        for _ in range(max_new):
            outs.append(np.asarray(tok[:, 0]))
            if eos_id is not None:
                done = done | (tok[:, 0] == eos_id)
                if bool(done.all()):
                    break
            logits, cache = self.decode(cache, tok)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        return np.stack(outs, axis=1), cache


@dataclasses.dataclass
class Request:
    rid: int
    tokens: np.ndarray  # [S] prompt at its true length
    max_new: int
    output: np.ndarray | None = None
    # admission class: 0 is the most urgent; FIFO within a class, lower
    # classes delayed (never starved — see EngineConfig.aging_steps)
    priority: int = 0
    # wall-clock budget in ms from submit; a request past its deadline is
    # retired with status 'expired' at the next scheduler step (partial
    # output kept). None = no deadline.
    deadline_ms: float | None = None
    # lifecycle: queued -> active -> done | cancelled | expired | parked
    # (a preempted request goes back to queued and keeps its place;
    # 'parked' is a fault-forced voluntary end-of-turn — partial output
    # kept, cache state parked in the session store when it is on)
    status: str = "queued"
    # latency telemetry (wall-clock seconds; filled by SlotServer):
    t_submit: float = 0.0  # stamped by submit()
    t_first: float | None = None  # first token ready (TTFT = t_first - t_submit)
    token_times: list = dataclasses.field(default_factory=list)  # one per
    #   token; tokens emitted by one multi-step launch share a timestamp
    n_preempts: int = 0  # times this request was swapped out mid-decode
    # scheduler bookkeeping (stamped by submit):
    _seq: int = dataclasses.field(default=0, repr=False)  # global submit order
    _enq_step: int = dataclasses.field(default=0, repr=False)  # step when
    #   (re-)queued — the aging clock

    def cancel(self) -> None:
        """Request cooperative cancellation: honored at the next scheduler
        step — queued, mid-prefill-chunk, swapped-out or decoding alike —
        through the shared retirement path (partial output kept)."""
        self._cancelled = True

    @property
    def cancelled(self) -> bool:
        return getattr(self, "_cancelled", False)


@dataclasses.dataclass
class SlotStats:
    """Scheduler telemetry (throughput/occupancy counters)."""

    # per-launch log for length-aware accounting: (steps, bucket tokens
    # launched per row, live token count per occupied row) — the substrate
    # for the dead-tile fraction reported by benchmarks/bench_ragged.py.
    # Grows per launch, so it only fills when EngineConfig.log_launches is on.
    launches: list = dataclasses.field(default_factory=list)
    n_slots: int = 0
    decode_steps: int = 0  # decode steps executed (tokens per occupied row)
    chunk_launches: int = 0  # jitted decode dispatches (== steps when chunk=1)
    occupied_slot_steps: int = 0  # sum over steps of occupied slots
    tokens_out: int = 0  # useful tokens delivered to requests
    admitted: int = 0
    completed: int = 0
    slot_reuses: int = 0  # admissions into a previously-used slot
    wall_s: float = 0.0
    # paged admission telemetry (zeros for dense engines):
    admission_blocks: int = 0  # admissions deferred for lack of free pages
    pages_reserved_peak: int = 0  # max simultaneously-reserved pool pages
    # chunked admission telemetry (zeros when prefill_chunk_pages == 0):
    prefill_chunks: int = 0  # bounded prefill dispatches (single-chunk
    # plain prompts take the fused monolithic launch and count zero)
    prefill_tokens: int = 0  # prompt tokens run through prefill dispatches
    #   (every admission path; a prefix hit's shared pages are not run)
    # paged Pallas attention work (zeros unless paged + backend "pallas"),
    # counted over executed steps x layers from the host counter mirror:
    attn_grid_steps: int = 0  # grid steps the paged kernel ran
    attn_dead_steps: int = 0  # ... whose page lies past its row's live
    #   compressed pages (every step of a free row included)
    attn_field_major_steps: int = 0  # ... that decoded their tiles in
    #   field-major order (every K and V tier of one width)
    # prefix-cache telemetry (zeros when the feature is off):
    prefix_lookups: int = 0  # admissions that consulted the prefix index
    prefix_hits: int = 0  # admissions that matched >= 1 full page
    prefix_pages_shared: int = 0  # pages mapped by reference (cumulative)
    prefix_evictions: int = 0  # index entries dropped (pressure or cap)
    # speculative-decode telemetry (zeros when spec_decode is off). With
    # speculation on, ``decode_steps`` counts MODEL PASSES (verify launches
    # included), not tokens — ``tokens_out`` stays the token truth:
    spec_launches: int = 0  # verify dispatches (q_len = spec_k + 1)
    spec_drafted: int = 0  # drafted tokens submitted for verification
    spec_accepted: int = 0  # drafted tokens accepted (emitted for free)
    # preemptive-serving telemetry (ISSUE 8; zeros when preempt is off):
    preemptions: int = 0  # slot swap-outs in favor of a higher class
    swapped_pages: int = 0  # pool pages evacuated to the host SwapStore
    restored_pages: int = 0  # pool pages streamed back on re-admission
    cancelled: int = 0  # requests retired via Request.cancel()
    expired: int = 0  # requests retired past their deadline_ms
    # decode-launch watchdog (zeros without spec decode / watchdog):
    degraded_steps: int = 0  # decode steps run with spec decode auto-disabled
    # session-cache telemetry (ISSUE 9; zeros when session_cache is off):
    session_lookups: int = 0  # admissions that consulted the session store
    session_parks: int = 0  # retiring slots parked host-side
    session_hits: int = 0  # admissions served from a parked session
    session_evictions: int = 0  # parked entries lost (capacity/TTL/invalid)
    session_restored_pages: int = 0  # pool pages streamed back on hits

    @property
    def acceptance_rate(self) -> float:
        return self.spec_accepted / self.spec_drafted if self.spec_drafted \
            else 0.0

    @property
    def session_hit_rate(self) -> float:
        return self.session_hits / self.session_lookups if \
            self.session_lookups else 0.0

    @property
    def prefix_hit_rate(self) -> float:
        return self.prefix_hits / self.prefix_lookups if self.prefix_lookups \
            else 0.0

    def to_json(self) -> dict:
        """JSON-serializable dump: every counter plus the derived rates
        (the per-launch ``launches`` log is dropped — unbounded)."""
        d = {f.name: getattr(self, f.name)
             for f in dataclasses.fields(self) if f.name != "launches"}
        d.update(
            occupancy=self.occupancy,
            decode_tok_s=self.decode_tok_s,
            prefix_hit_rate=self.prefix_hit_rate,
            acceptance_rate=self.acceptance_rate,
            session_hit_rate=self.session_hit_rate,
        )
        return d

    @property
    def occupancy(self) -> float:
        """Fraction of slot-steps that decoded a live request."""
        total = self.decode_steps * max(self.n_slots, 1)
        return self.occupied_slot_steps / total if total else 0.0

    @property
    def decode_tok_s(self) -> float:
        return self.tokens_out / self.wall_s if self.wall_s else 0.0


class _PrefixNode:
    """One full compressed page of a cached prompt prefix (trie node)."""

    __slots__ = ("chunk", "page", "parent", "children", "last_used", "perms")

    def __init__(self, chunk: bytes, page: int, parent):
        self.chunk = chunk  # raw token ids of this page's span (the key)
        self.page = page  # physical pool page id (one index reference held)
        self.parent = parent  # None for depth-0 nodes
        self.children: dict[bytes, "_PrefixNode"] = {}
        self.last_used = 0
        self.perms = None  # depth-0 only: (k_perm, v_perm) device arrays


class PrefixIndex:
    """Host-side content-addressed prefix index over FULL compressed pages.

    A trie keyed by page-aligned chunks of raw prompt token ids; each node
    owns exactly one physical pool page and holds ONE device reference on
    it (``core.cache.acquire_pages``), so cached pages survive their
    originating slot's retirement and are never handed out by the
    allocator. Lookup walks the longest matching chain; eviction removes
    LRU LEAVES only (an interior page is still reachable through its
    children), skipping pages currently mapped into a live slot by
    reference — evicting those would break the scheduler's reservation
    bound (a shared page is reserved by NO slot). Depth-0 nodes carry the
    donor's page-0 channel calibration so a hit compresses its suffix under
    the identical permutation. Pure host state: every device mutation is
    the ``SlotServer``'s, through ``Engine.index_acquire/index_release``.
    """

    def __init__(self, page_size: int):
        self.page_size = page_size
        self.roots: dict[bytes, _PrefixNode] = {}
        self.n_held = 0  # pages the index holds a reference on
        self.pages: set[int] = set()  # their ids (each in exactly one node)
        self._clock = 0

    def chunks(self, tokens) -> list[bytes]:
        """Page-aligned raw-token-id chunks (the trie keys)."""
        t = np.ascontiguousarray(np.asarray(tokens, np.int64))
        p = self.page_size
        return [t[i * p:(i + 1) * p].tobytes() for i in range(len(t) // p)]

    def touch(self, node: _PrefixNode) -> None:
        self._clock += 1
        node.last_used = self._clock

    def descend(self, parent, chunk: bytes):
        m = self.roots if parent is None else parent.children
        return m.get(chunk)

    def lookup(self, tokens, max_pages: int):
        """Longest-prefix match, LRU-bumping the path.

        Returns (page ids, (k_perm, v_perm) | None)."""
        pages: list[int] = []
        perms = None
        node = None
        for chunk in self.chunks(tokens)[:max_pages]:
            node = self.descend(node, chunk)
            if node is None:
                break
            self.touch(node)
            if node.perms is not None:
                perms = node.perms
            pages.append(node.page)
        return pages, perms

    def insert(self, parent, chunk: bytes, page: int, perms=None):
        node = _PrefixNode(chunk, page, parent)
        self.touch(node)
        node.perms = perms if parent is None else None
        (self.roots if parent is None else parent.children)[chunk] = node
        self.n_held += 1
        self.pages.add(page)
        return node

    def evict_lru(self, protected: set[int]):
        """Drop the least-recently-used unprotected LEAF; returns its page
        id (the caller must release the device reference) or None."""
        best = None
        stack = list(self.roots.values())
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            if n.children or n.page in protected:
                continue
            if best is None or n.last_used < best.last_used:
                best = n
        if best is None:
            return None
        owner = self.roots if best.parent is None else best.parent.children
        del owner[best.chunk]
        self.n_held -= 1
        self.pages.discard(best.page)
        return best.page


class NGramDrafter:
    """Host-side per-slot suffix n-gram drafter (self-speculation).

    No separate draft checkpoint: the draft distribution is the sequence
    itself — per slot, keep prompt + emitted tokens and propose the
    continuation of the most recent earlier occurrence of the current
    suffix n-gram ("prompt lookup" drafting). Pure host state, O(n·L) list
    scan per draft (L = slot sequence length, n <= max_ngram) — noise next
    to a model pass. Draft quality only affects SPEED (acceptance rate);
    the verify pass guarantees greedy outputs are exact for arbitrary
    drafts, so a drafter can be swapped freely (benchmarks inject
    adversarial ones).
    """

    def __init__(self, max_ngram: int = 3):
        self.max_ngram = max_ngram
        self._seq: dict[int, list[int]] = {}

    def seed(self, slot: int, tokens) -> None:
        """Start tracking ``slot``: prompt + first generated token."""
        self._seq[slot] = [int(t) for t in tokens]

    def extend(self, slot: int, tokens) -> None:
        """Append the tokens a launch just emitted for ``slot``."""
        self._seq[slot].extend(int(t) for t in tokens)

    def drop(self, slot: int) -> None:
        self._seq.pop(slot, None)

    def draft(self, slot: int, k: int) -> list[int]:
        """Up to ``k`` proposed continuations of ``slot``'s sequence.

        Longest-suffix match first: for n = max_ngram..1, find the most
        recent PRIOR occurrence of the sequence's last n tokens and
        propose what followed it. Empty when nothing matches — the
        scheduler then falls back to a plain decode launch, which is what
        keeps the acceptance≈0 regime at baseline speed."""
        seq = self._seq.get(slot)
        if not seq or k <= 0:
            return []
        L = len(seq)
        for n in range(min(self.max_ngram, L - 1), 0, -1):
            key = seq[L - n:]
            for s in range(L - n - 1, -1, -1):
                if seq[s:s + n] == key:
                    # s + n <= L - 1, so the continuation is never empty
                    return seq[s + n:s + n + k]
        return []


class _Active:
    """One occupied slot: the request plus its generation state.

    ``forced`` is the teacher-forced ingestion queue of a session resume
    (the returning turn's uncached suffix tokens): while it is non-empty,
    decode launches still append to the row's cache, but the launch argmax
    is overridden host-side by the next forced token — suffix ingestion
    rides the shared decode launches (no extra jitted op) and is
    continuation-exact by construction. Forced tokens are prompt, not
    output: nothing is emitted while the queue drains.

    ``base`` re-anchors the host counter mirror (``SlotServer._counters``)
    for rows whose cache state did NOT come from prefilling their own
    prompt (session resume, or preemption of such a row): ``(n_comp0,
    n_resid0, cached0, out0)`` snapshotted at the anchor moment."""

    __slots__ = ("req", "out", "done", "forced", "k0", "base")

    def __init__(self, req: Request, first_tok: int | None,
                 eos_id: int | None, forced=None, base=None):
        self.req = req
        self.forced: list[int] = list(forced) if forced else []
        self.k0 = len(self.forced)  # forced count at the anchor
        self.base = base
        if first_tok is None:  # session resume: nothing emitted yet
            self.out: list[int] = []
            self.done = False
        else:
            self.out = [first_tok]
            self.done = (eos_id is not None and first_tok == eos_id) or \
                req.max_new <= 1

    @property
    def remaining(self) -> int:
        return self.req.max_new - len(self.out)

    @property
    def cached_tokens(self) -> int:
        """Host-side mirror of this row's cache occupancy (n_comp + n_resid).

        The prompt is inserted at prefill; each decode step appends the
        PREVIOUS token, so the first generated token is not yet cached.
        Re-anchored rows count appends SINCE the anchor instead: every
        drained forced token and every emitted token is one append."""
        if self.base is not None:
            _, _, cached0, out0 = self.base
            return cached0 + (self.k0 - len(self.forced)) + \
                (len(self.out) - out0)
        return len(self.req.tokens) + len(self.out) - 1


class _PrefillTask:
    """An in-flight chunked admission: one request advancing through its
    page-aligned prefill segments, interleaved with decode launches.

    The slot is claimed (and its pages reserved) at task start but stays
    ``None`` in the slot table until the final segment inserts the row —
    decode launches in between see it as a free ride-along row."""

    __slots__ = ("req", "slot", "kind", "scratch", "bounds", "idx",
                 "match_pages", "match_perms", "logits")

    def __init__(self, req: Request, slot: int, kind: str, scratch,
                 bounds: list[int], match_pages: tuple[int, ...] = (),
                 match_perms=None):
        self.req = req
        self.slot = slot
        self.kind = kind  # "plain" | "prefix"
        self.scratch = scratch  # raw-K/V scratch | seeded mini-cache
        self.bounds = bounds  # segment offsets; [i, i+1) spans one dispatch
        self.idx = 0  # next segment
        self.match_pages = match_pages
        self.match_perms = match_perms
        self.logits = None  # last segment's logits seed decode

    @property
    def done(self) -> bool:
        return self.idx >= len(self.bounds) - 1


class SlotServer:
    """Continuous-batching scheduler over a fixed slot table — ONE engine
    for every family (KV transformers and recurrent rwkv6/hybrid_rglru).

    Each step: (1) PREFILL CHUNK — advance the pending admission (FIFO
    head) by at most ``prefill_chunk_pages`` pages' worth of prompt, the
    final chunk inserting the finished row into its claimed slot;
    (2) DECODE — one batched greedy decode launch over the whole table
    (free rows ride along masked); (3) RETIRE — rows that hit EOS or
    ``max_new`` record their output, their slot state is reset, and the
    slot is reusable on the very next step. Because every scheduler step
    runs a decode launch, no occupied slot ever stalls for more than one
    bounded prefill chunk (the old monolithic admission stalled decode for
    the WHOLE prompt). ``prefill_chunk_pages=0`` restores the monolithic
    path. Per-request greedy outputs are bit-identical to a batch-size-1
    ``Engine.generate`` run either way (per-row state + per-row positions +
    row-independent attention; chunk boundaries are exact resume points —
    see ``models.layers.resume_attention`` and the per-family
    ``prefill_chunk`` docstrings).

    PAGED engines admit on FREE PAGES, not free slots: each admitted
    request reserves its worst-case page count (``ceil(min(capacity,
    prompt + max_new) / page_size)``) and admission blocks — FIFO order
    preserved — while reservations plus the watermark would overflow the
    pool. Reservations are the host-side guarantee that the in-graph
    free-list never over-pops, which is what makes oversubscription
    (``pool_pages < max_batch * capacity / page_size``) safe under mixed
    traffic.

    PREFIX-CACHE engines additionally keep a host-side ``PrefixIndex``:
    admission looks up the longest page-aligned prompt prefix already
    compressed in the pool, maps those pages into the new slot BY
    REFERENCE (refcounted — they reserve ZERO new pages), runs the chunked
    prefill only over the uncovered suffix, and registers the admitted
    prompt's full pages back into the index. Under pool pressure the
    scheduler EVICTS cold cached prefixes (LRU leaves not mapped into any
    live slot) instead of blocking admission. Cache-hit admissions are
    bit-identical to cold ones: see ``models.transformer.
    prefill_into_slot_prefix`` for why page boundaries are exact resume
    points.

    PREEMPTIVE serving (ISSUE 8; ``EngineConfig.preempt``): requests carry
    a priority class — admission is per-class FIFO with aging (delayed,
    never starved) — and when a higher-class head cannot seat (no free
    slot, or pages short even after index eviction) the scheduler swaps a
    strictly-lower-class victim OUT: its compressed pages, residual and
    counters are evacuated to a host-RAM ``SwapStore``
    (``core.cache.evacuate_row``), shared-prefix pages release their refs
    instead of copying, and the victim requeues with its
    generated-so-far tokens. On re-admission the row streams back
    (``restore_row`` — one scatter, no forward pass) and decoding resumes
    bit-identically to an uninterrupted run. ``Request.deadline_ms`` /
    ``cancel()`` retire work at the next scheduler step — mid-prefill-chunk
    included — through the same ``_retire_slot``/``_finish_dead`` path,
    and a ``distributed.fault.FaultPlan`` can drive all of it
    deterministically (see docs/serving.md).

    SESSION CACHE (ISSUE 9; ``EngineConfig.session_cache``): multi-turn
    traffic parks instead of discarding — when a slot retires, its
    compressed pages, residual, counters and channel calibration are
    evacuated (the same ``evacuate_row`` gather preemption uses) into a
    host-RAM ``SessionStore`` keyed by the session's raw token trace
    (LRU-by-bytes with optional disk spill + TTL; shared prefix pages
    release their refs and are revalidated against the live trie on
    return). A returning turn whose prompt extends a parked trace
    restores the row with ZERO forward passes over the restored tokens
    and ingests only its new suffix, teacher-forced through the ordinary
    decode launches — so a session hit is bit-identical to never having
    parked at all (the continuation-exactness bar of preemption, extended
    across turns; see docs/serving.md).
    """

    def __init__(self, engine: Engine, eos_id: int | None = None,
                 drafter: NGramDrafter | None = None,
                 fault_plan: FaultPlan | None = None,
                 straggler: StragglerMonitor | None = None,
                 session_store: SessionStore | None = None):
        if engine.cfg.input_mode != "tokens":
            raise ValueError(
                f"input_mode {engine.cfg.input_mode!r} not servable per-slot "
                "(Request carries tokens only); batch such inputs through "
                "Engine.generate"
            )
        self.engine = engine
        self.eos_id = eos_id
        # speculative decode: per-slot drafter (injectable — draft quality
        # only moves the acceptance rate, never the outputs)
        self._drafter = (
            (drafter if drafter is not None else NGramDrafter())
            if engine.ecfg.spec_decode else None
        )
        # per-slot acceptance bookkeeping: fully-rejected launches push the
        # slot into an exponentially growing draft cooldown (see
        # EngineConfig.spec_backoff); any accepted draft resets it
        self._spec_backoff = [0] * engine.ecfg.max_batch
        self._spec_cooldown = [0] * engine.ecfg.max_batch
        self.n_slots = engine.ecfg.max_batch
        self.cache = None  # allocated on first admission
        self.slots: list[_Active | None] = [None] * self.n_slots
        self._ever_used = [False] * self.n_slots
        self._last_tok = np.zeros((self.n_slots,), np.int32)
        # per-class FIFO queues (priority 0 = most urgent); the flattened
        # ``queue`` property is the back-compat view
        self.queues: dict[int, deque[Request]] = {}
        self.done: dict[int, Request] = {}
        self.stats = SlotStats(n_slots=self.n_slots)
        self._reserved: dict[int, int] = {}  # slot -> NEWLY-allocatable pages
        self._index = (PrefixIndex(engine.ecfg.page_size)
                       if engine.ecfg.prefix_cache else None)
        self._slot_shared: dict[int, tuple[int, ...]] = {}  # slot -> mapped
        self._task: _PrefillTask | None = None  # in-flight chunked admission
        # preemption: host-RAM store of evacuated rows (ISSUE 8)
        self._swap: SwapStore | None = SwapStore() if engine.ecfg.preempt \
            else None
        # voluntary session cache: parked retiring rows (ISSUE 9; the
        # injectable store lets tests freeze clocks / shrink capacities)
        self._sessions: SessionStore | None = None
        if engine.ecfg.session_cache:
            self._sessions = session_store if session_store is not None \
                else SessionStore(
                    capacity_bytes=engine.ecfg.session_cache_mb << 20,
                    ttl_s=engine.ecfg.session_ttl_s,
                    disk_dir=engine.ecfg.session_disk_dir,
                )
        self._fault_rid = 1_000_000_000  # rid range for fault-fabricated
        #   returning sessions (far above real traffic — never collides)
        self._seq = 0  # global submit stamp (FIFO order within a class)
        self._step_no = 0  # scheduler step counter (aging + fault clock)
        # deterministic fault schedule (tests/bring-up; None in production)
        self._faults = fault_plan
        self._squeeze = 0  # pool pages a pool_squeeze fault holds back
        # decode-launch watchdog: sustained stragglers auto-disable spec
        # decode (exactness-neutral — speculation only changes speed)
        self._watchdog = straggler if straggler is not None else (
            StragglerMonitor() if engine.ecfg.spec_decode else None
        )
        self._spec_degraded = False  # sticky once the watchdog says exclude

    @property
    def queue(self) -> list[Request]:
        """Flattened queue view: classes ascending, FIFO within each — for
        truthiness/len/iteration. Mutate through submit(), never this list."""
        return [r for p in sorted(self.queues) for r in self.queues[p]]

    # -- paged admission accounting ----------------------------------------
    @property
    def _pages_avail(self) -> int:
        """Pool pages not spoken for: total minus the watermark, minus every
        slot's reservation of pages it may NEWLY allocate, minus pages the
        prefix index pins. Donor pages counted by both a reservation and
        the index are double-counted — conservative, never unsafe — and
        index pages are reclaimable on demand (``_evict_to_fit``)."""
        ecfg = self.engine.ecfg
        total = self.engine.pack_cfg.pool_pages
        held = self._index.n_held if self._index is not None else 0
        return (total - ecfg.page_watermark - self._squeeze
                - sum(self._reserved.values()) - held)

    def _pages_needed(self, req: Request) -> int:
        """Worst-case resident pages over the request's lifetime: its
        compressed tokens never exceed min(capacity, prompt + max_new)."""
        from ..utils import cdiv

        ecfg = self.engine.ecfg
        hi = min(ecfg.capacity, len(req.tokens) + req.max_new)
        return cdiv(hi, ecfg.page_size)

    def _match(self, req: Request) -> tuple[list[int], object]:
        """Longest page-aligned prefix the index can serve for ``req``.

        Capped one token short of the prompt so the suffix is never empty
        (admission needs last-token logits to seed decode)."""
        max_m = (len(req.tokens) - 1) // self.engine.ecfg.page_size
        return self._index.lookup(req.tokens, max_m)

    def _live_shared(self) -> set[int]:
        """Shared pages a live slot maps by reference — plus pages a
        SWAPPED-OUT request will re-map on restore (its slot released its
        device refs at evacuation, so only the index still pins them; they
        must survive eviction until the request resumes or dies)."""
        live = {p for t in self._slot_shared.values() for p in t}
        if self._swap is not None:
            for meta in self._swap.metas():
                live.update(meta["shared"])
        return live

    def _evict_to_fit(self, need_new: int, protected: set[int]) -> bool:
        """Reclaim index-pinned pages (LRU leaves first) until ``need_new``
        fits, instead of blocking admission. Never evicts pages mapped into
        a live slot by reference (they are covered by NO reservation) or
        the pages just matched for the pending admission."""
        if self._index is None:
            return need_new <= self._pages_avail
        protected = protected | self._live_shared()
        while need_new > self._pages_avail:
            page = self._index.evict_lru(protected)
            if page is None:
                return False
            self.cache = self.engine.index_release(self.cache, [page])
            self.stats.prefix_evictions += 1
        return True

    def _register(self, req: Request, slot: int) -> None:
        """Index every full compressed page of the freshly-admitted prompt.

        Matched pages already have nodes (bumped); new pages get nodes and
        one device reference each. Registration respects
        ``prefix_cache_pages`` by evicting LRU leaves first and simply
        stops when nothing is evictable (a shorter registered chain is
        still a correct trie)."""
        pack = self.engine.pack_cfg
        page = self.engine.ecfg.page_size
        k = (len(req.tokens) // pack.block) * pack.block // page
        if not k:
            return
        cap = self.engine.ecfg.prefix_cache_pages
        row = np.asarray(self.cache.pages.page_table[0, slot, :k])
        perms = None
        if pack.policy != "none":
            perms = (self.cache.k.chan_perm[:, slot],
                     self.cache.v.chan_perm[:, slot])
        protected = self._live_shared() | {int(p) for p in row}
        acquired: list[int] = []
        parent = None
        for d, chunk in enumerate(self._index.chunks(req.tokens)[:k]):
            node = self._index.descend(parent, chunk)
            if node is None:
                if cap is not None and self._index.n_held >= cap:
                    ev = self._index.evict_lru(protected)
                    if ev is None:
                        break
                    self.cache = self.engine.index_release(self.cache, [ev])
                    self.stats.prefix_evictions += 1
                node = self._index.insert(parent, chunk, int(row[d]),
                                          perms if d == 0 else None)
                acquired.append(int(row[d]))
            else:
                self._index.touch(node)
            parent = node
        if acquired:
            self.cache = self.engine.index_acquire(self.cache, acquired)

    def _check_invariants(self) -> None:
        """Debug mode: refcount conservation after every admit/retire.

        ``free ⇔ ref == 0`` in both directions — the number of held pages
        plus the stack height equals the pool size, and every stack entry
        has a zero count. Device sync per call; gate on
        ``EngineConfig.debug_invariants``."""
        if not (self.engine.ecfg.debug_invariants and self.engine.ecfg.paged
                and self.cache is not None):
            return
        pool = self.cache.pages
        ref = np.asarray(pool.ref[0])
        nf = int(pool.n_free[0])
        free = np.asarray(pool.free[0])
        P = ref.shape[0]
        assert int((ref > 0).sum()) + nf == P, (ref, nf)
        assert int((ref == 0).sum()) == nf, (ref, nf)
        assert (ref[free[:nf]] == 0).all(), (ref, free[:nf])
        if self._index is not None:
            assert all(int(ref[p]) >= 1 for p in self._index.pages)

    # -- queue --------------------------------------------------------------
    def submit(self, req: Request) -> None:
        if req.max_new < 1:
            raise ValueError(f"request {req.rid}: max_new must be >= 1")
        if req.priority < 0:
            raise ValueError(f"request {req.rid}: priority must be >= 0")
        if req.deadline_ms is not None and req.deadline_ms <= 0:
            # an already-expired deadline is a caller bug, not traffic:
            # reject upstream instead of admitting work doomed to reap
            raise ValueError(
                f"request {req.rid}: deadline_ms must be > 0"
            )
        if self.engine.ecfg.paged:
            ecfg = self.engine.ecfg
            pack = self.engine.pack_cfg
            # prefill block-flushes the WHOLE prompt, so its block-aligned
            # length must fit the compressed capacity outright (a longer
            # one would pop more pages than a table row holds)
            lb = (len(req.tokens) // pack.block) * pack.block
            if lb > ecfg.capacity:
                raise ValueError(
                    f"request {req.rid}: block-aligned prompt length {lb} "
                    f"exceeds compressed capacity {ecfg.capacity}"
                )
            hi = len(req.tokens) + req.max_new
            if hi > ecfg.capacity + pack.residual:
                # over-contract rows stop flushing at capacity (their page
                # reservation stays a true bound) and would degrade their
                # own residual — enforce the documented upstream rejection
                raise ValueError(
                    f"request {req.rid}: prompt + max_new = {hi} exceeds "
                    f"capacity + residual = {ecfg.capacity + pack.residual}"
                )
            total = self.engine.pack_cfg.pool_pages
            need = self._pages_needed(req)
            if need > total - ecfg.page_watermark:
                raise ValueError(
                    f"request {req.rid} needs {need} pages but the pool "
                    f"admits at most {total - ecfg.page_watermark}"
                )
        req.t_submit = time.perf_counter()
        req._seq = self._seq
        self._seq += 1
        req._enq_step = self._step_no
        self.queues.setdefault(req.priority, deque()).append(req)

    def _head(self) -> Request | None:
        """The next request to admit: among per-class FIFO heads, the one
        with the smallest (effective class, submit seq). Aging promotes a
        waiting head one class per ``aging_steps`` scheduler steps, so a
        permanent higher-class flood delays lower classes but never starves
        them; ``aging_steps = 0`` is strict priority."""
        ag = self.engine.ecfg.aging_steps
        best, best_key = None, None
        for p, q in self.queues.items():
            if not q:
                continue
            h = q[0]
            eff = max(0, p - (self._step_no - h._enq_step) // ag) if ag > 0 \
                else p
            key = (eff, h._seq)
            if best_key is None or key < best_key:
                best, best_key = h, key
        return best

    def _pop_head(self, head: Request) -> Request:
        got = self.queues[head.priority].popleft()
        assert got is head
        return got

    def _requeue(self, req: Request) -> None:
        """Put a preempted/aborted request back, keeping per-class FIFO by
        ORIGINAL submit order (everything still queued in its class was
        submitted later, so it normally lands at the front)."""
        req.status = "queued"
        req._enq_step = self._step_no
        q = self.queues.setdefault(req.priority, deque())
        pos = 0
        while pos < len(q) and q[pos]._seq < req._seq:
            pos += 1
        q.insert(pos, req)

    @property
    def n_occupied(self) -> int:
        return sum(s is not None for s in self.slots)

    # -- retirement (the ONE path out of a slot) ----------------------------
    def _release_slot(self, i: int, free_pages: bool = True) -> None:
        """Tear down slot ``i``'s scheduler state: drafter row, page
        reservation, shared-prefix mapping, and — unless the pages were
        already returned in-graph by an evacuation — the device row itself.
        Every way out of a slot (EOS, max_new, cancel, deadline, preemption)
        funnels through here, so nothing can leak pages or refcounts."""
        self.slots[i] = None
        if self._drafter is not None:
            self._drafter.drop(i)
        if free_pages:
            self.cache = self.engine.free_slot(self.cache, i)
        self._reserved.pop(i, None)  # paged: pages return with the reset
        self._slot_shared.pop(i, None)  # shared pages: ref back to the index
        self._check_invariants()

    def _retire_slot(self, i: int, reason: str = "done") -> Request:
        """Finish the request in slot ``i`` (reason: done | cancelled |
        expired | parked) and recycle the slot. Natural and voluntary
        ends of turn ("done" / "parked") park the row's cache state in the
        session store when it is on; cancelled/expired work is discarded
        (a killed request is not a conversation that will return)."""
        act = self.slots[i]
        act.req.output = np.asarray(act.out, np.int32)
        act.req.status = reason
        self.done[act.req.rid] = act.req
        parked = reason in ("done", "parked") and self._park_row(i)
        # a parked row was evacuated: its pages are already back in the pool
        self._release_slot(i, free_pages=not parked)
        if reason == "done":
            self.stats.completed += 1
        elif reason == "cancelled":
            self.stats.cancelled += 1
        elif reason == "expired":
            self.stats.expired += 1
        return act.req

    # -- session cache: voluntary park / resume ------------------------------
    def _park_row(self, i: int) -> bool:
        """Park slot ``i``'s cache state in the session store at retirement.

        The parked key is the row's cached token TRACE — the first
        ``cached_tokens + 1`` tokens of prompt + generated (the +1 is the
        pending seed, ``_last_tok``, which the cache has not appended yet).
        That formula is exact whether the row completed, was force-parked
        mid-generation, or was even mid-suffix-ingestion. Shared-prefix
        pages release their refs (never copy); their trie NODE chain is
        remembered so a resume can prove, by object identity, that the
        index still holds the same physical pages. Returns True when the
        row was evacuated (caller must then release with
        ``free_pages=False``)."""
        if self._sessions is None or self.cache is None:
            return False
        act = self.slots[i]
        trace = np.concatenate([
            np.asarray(act.req.tokens, np.int64),
            np.asarray(act.out, np.int64),
        ])[: act.cached_tokens + 1]
        c, r = self._counters(act)
        n_pages = n_shared = 0
        if self.engine.ecfg.paged:
            n_pages = -(-c // self.engine.ecfg.page_size)
            n_shared = len(self._slot_shared.get(i, ()))
        shared = tuple(self._slot_shared.get(i, ()))
        nodes: list = []
        if shared and self._index is not None:
            parent = None
            for chunk in self._index.chunks(trace)[: len(shared)]:
                parent = self._index.descend(parent, chunk)
                assert parent is not None  # live-shared pages can't evict
                nodes.append(parent)
        self.cache, mini = self.engine.evacuate(self.cache, i, n_pages,
                                                n_shared)
        self._sessions.put(trace, mini, dict(
            last_tok=int(self._last_tok[i]), n_pages=n_pages,
            n_shared=n_shared, shared=shared, nodes=tuple(nodes),
            counters=(c, r),
        ))
        self.stats.session_parks += 1
        return True

    def _session_valid(self, meta: dict) -> bool:
        """A parked entry's shared-prefix pages are servable iff the SAME
        trie nodes still hold the SAME physical pages — parking holds no
        device refs, so pool pressure may have evicted (or evicted and
        rebuilt with different calibration) the chain while the session
        was away. Object identity over the remembered node chain is the
        airtight check: nodes die at eviction and are never resurrected."""
        if self._index is None:
            return False
        node = None
        for want, page in zip(meta["nodes"], meta["shared"]):
            node = self._index.descend(node, want.chunk)
            if node is not want or node.page != page:
                return False
        for n in meta["nodes"]:
            self._index.touch(n)
        return True

    def _session_try(self, head: Request, slot: int) -> str:
        """Try to serve ``head`` from a parked session.

        Returns "hit" (popped + admitted into ``slot``), "blocked" (a
        matching entry exists but its pages don't fit yet — the entry is
        kept and the admission retries next step) or "miss" (cold path).
        """
        if self._sessions is None or self.cache is None:
            return "miss"
        while True:
            key = self._sessions.match(head.tokens)
            if key is None:
                return "miss"
            meta = self._sessions.meta(key)
            if meta["n_shared"] and not self._session_valid(meta):
                self._sessions.drop(key)
                continue
            if self.engine.ecfg.paged and not self._fit_pages(
                    head, self._pages_needed(head) - meta["n_shared"],
                    set(meta["shared"])):
                return "blocked"
            req = self._pop_head(head)
            mini, meta = self._sessions.take(key)
            self.stats.session_lookups += 1
            self._session_resume(req, slot, len(key) // 8, mini, meta)
            return "hit"

    def _session_resume(self, req: Request, i: int, trace_len: int,
                        mini, meta: dict) -> None:
        """Re-admit a returning session: stream the parked row back into
        slot ``i`` (shared prefix re-mapped by reference — pure data
        movement, NO forward pass) and queue the prompt's uncached suffix
        for teacher-forced ingestion through the decode launches. The
        counter anchor pins ``_counters`` to the parked row's exact
        (n_comp, n_resid) so later flush arithmetic stays a host mirror."""
        if self.engine.ecfg.paged:
            self._reserved[i] = self._pages_needed(req) - meta["n_shared"]
            self.stats.pages_reserved_peak = max(
                self.stats.pages_reserved_peak, sum(self._reserved.values())
            )
        self.cache = self.engine.restore(
            self.cache, i, mini, meta["shared"],
            n_pages=meta["n_pages"], n_shared=meta["n_shared"],
        )
        if meta["shared"]:
            self._slot_shared[i] = tuple(meta["shared"])
        c0, r0 = meta["counters"]
        forced = [int(t) for t in np.asarray(req.tokens)[trace_len:]]
        act = _Active(req, None, self.eos_id, forced=forced,
                      base=(c0, r0, trace_len - 1, 0))
        self.slots[i] = act
        req.status = "active"
        self._last_tok[i] = meta["last_tok"]
        self._spec_backoff[i] = 0
        self._spec_cooldown[i] = 0
        if self._drafter is not None:
            self._drafter.seed(
                i, [int(t) for t in np.asarray(req.tokens)[:trace_len]]
            )
        if self._ever_used[i]:
            self.stats.slot_reuses += 1
        self._ever_used[i] = True
        self.stats.admitted += 1
        self.stats.session_hits += 1
        self.stats.session_restored_pages += meta["n_pages"] - meta["n_shared"]
        self._check_invariants()

    def _fault_resume(self, n: int) -> None:
        """Fabricate up to ``n`` returning sessions from the oldest parked
        traces (fault injection: deterministic continuations — a short
        fixed suffix, a dedicated rid range far above real traffic).
        Entries whose continuation would not pass admission bounds are
        skipped."""
        if self._sessions is None:
            return
        for trace in self._sessions.traces(n):
            toks = np.concatenate([trace, np.zeros((3,), np.int64)])
            req = Request(rid=self._fault_rid, max_new=2, tokens=toks)
            self._fault_rid += 1
            try:
                self.submit(req)
            except ValueError:
                continue

    # -- preemption: compressed swap-out / swap-in ---------------------------
    def _swap_out_one(self, head: Request) -> bool:
        """Evacuate ONE victim slot to make room for ``head``.

        Victims must be STRICTLY lower class (raw ``priority`` — aging
        promotes a head's admission ORDER, not its preemption rights, so a
        requeued victim can never bounce straight back into its preemptor);
        among them, pick the lowest class with the most remaining work (the
        one that would hold its slot/pages longest), lowest slot index on
        ties. The victim's owned bytes land in the host SwapStore, its
        shared-prefix pages are released by reference, and it requeues at
        its original submit order — resumed outputs are bit-identical to an
        uninterrupted run (cache bytes are placement-independent; counters
        and positions derive from prompt + generated length)."""
        if self._swap is None:
            return False
        cand = [i for i in range(self.n_slots)
                if self.slots[i] is not None
                and self.slots[i].req.priority > head.priority]
        if not cand:
            return False
        i = max(cand, key=lambda j: (self.slots[j].req.priority,
                                     self.slots[j].remaining, -j))
        act = self.slots[i]
        req = act.req
        c, r = self._counters(act)
        n_pages = n_shared = 0
        if self.engine.ecfg.paged:
            n_pages = -(-c // self.engine.ecfg.page_size)
            n_shared = len(self._slot_shared.get(i, ()))
        shared = tuple(self._slot_shared.get(i, ()))
        self.cache, mini = self.engine.evacuate(self.cache, i, n_pages,
                                                n_shared)
        # the counter re-anchor + forced queue make the swap meta exact for
        # ANY row — including a session resume preempted mid-ingestion
        self._swap.put(req.rid, mini, dict(
            out=list(act.out), last_tok=int(self._last_tok[i]),
            n_pages=n_pages, n_shared=n_shared, shared=shared,
            forced=list(act.forced),
            base=(c, r, act.cached_tokens, len(act.out)),
        ))
        req.n_preempts += 1
        self._requeue(req)
        # the evacuation already returned the row's pages in-graph
        self._release_slot(i, free_pages=False)
        self.stats.preemptions += 1
        self.stats.swapped_pages += n_pages - n_shared
        return True

    def _resume(self, req: Request, i: int) -> None:
        """Re-admit a swapped-out request into slot ``i``: stream its pages
        back (shared prefix re-mapped by reference), rebuild the host-side
        generation state, and continue decoding from its saved seed token.
        NO forward pass runs — the seed was never cached, exactly as if the
        preemption never happened."""
        mini, meta = self._swap.pop(req.rid)
        if self.engine.ecfg.paged:
            self._reserved[i] = self._pages_needed(req) - meta["n_shared"]
            self.stats.pages_reserved_peak = max(
                self.stats.pages_reserved_peak, sum(self._reserved.values())
            )
        self.cache = self.engine.restore(
            self.cache, i, mini, meta["shared"],
            n_pages=meta["n_pages"], n_shared=meta["n_shared"],
        )
        if meta["shared"]:
            self._slot_shared[i] = tuple(meta["shared"])
        act = _Active(req, None, self.eos_id, forced=meta["forced"],
                      base=meta["base"])
        act.out = list(meta["out"])
        self.slots[i] = act
        req.status = "active"
        self._last_tok[i] = meta["last_tok"]
        self._spec_backoff[i] = 0
        self._spec_cooldown[i] = 0
        self._ever_used[i] = True
        if self._drafter is not None:
            # the drafter mirrors the CACHED sequence + pending seed: for a
            # row preempted mid-suffix-ingestion that is a prompt prefix,
            # not the whole prompt (the rest drains through ``forced``)
            n_seen = act.cached_tokens + 1 - len(act.out)
            self._drafter.seed(
                i, [int(t) for t in np.asarray(req.tokens)[:n_seen]]
                + list(act.out)
            )
        self.stats.restored_pages += meta["n_pages"] - meta["n_shared"]
        self._check_invariants()

    def _seat(self, head: Request) -> int | None:
        """A free slot for ``head`` — swapping lower-class victims out one
        at a time when preemption is on and the table is full."""
        while True:
            try:
                return self.slots.index(None)
            except ValueError:
                if not self._swap_out_one(head):
                    return None

    def _fit_pages(self, head: Request, need_new: int,
                   protected: set[int]) -> bool:
        """Make ``need_new`` pages reservable: evict cold index prefixes
        first (cheap — recomputable), then swap out lower-class victims."""
        while not self._evict_to_fit(need_new, protected):
            if not self._swap_out_one(head):
                # page-count admission: keep class/FIFO order, wait for a
                # retirement
                self.stats.admission_blocks += 1
                return False
        return True

    def _admit(self) -> list[Request]:
        """Monolithic admission sweep (``prefill_chunk_pages == 0``): seat
        queue heads — swapped-out requests resume, fresh ones prefill in one
        fused dispatch — until the queue drains or admission blocks."""
        finished: list[Request] = []
        paged = self.engine.ecfg.paged
        while True:
            head = self._head()
            if head is None:
                break
            i = self._seat(head)
            if i is None:
                break
            if self._swap is not None and head.rid in self._swap:
                meta = self._swap.meta(head.rid)
                if paged and not self._fit_pages(
                        head, self._pages_needed(head) - meta["n_shared"],
                        set(meta["shared"])):
                    break
                self._resume(self._pop_head(head), i)
                continue
            hit = self._session_try(head, i)
            if hit == "hit":
                continue
            if hit == "blocked":
                break
            match_pages: list[int] = []
            match_perms = None
            if self._index is not None and self.cache is not None:
                match_pages, match_perms = self._match(head)
            if paged:
                # suffix-only reservation: shared prefix pages reserve 0 —
                # the slot can only ever NEWLY pop pages past the match
                need_new = self._pages_needed(head) - len(match_pages)
                if not self._fit_pages(head, need_new, set(match_pages)):
                    break
            req = self._pop_head(head)
            if self._sessions is not None:
                self.stats.session_lookups += 1  # cold admission == miss
            if self.cache is None:
                self.cache = self.engine.alloc_slot_cache()
            if paged:
                self._reserved[i] = self._pages_needed(req) - len(match_pages)
                self.stats.pages_reserved_peak = max(
                    self.stats.pages_reserved_peak, sum(self._reserved.values())
                )
            if self._index is not None:
                self.stats.prefix_lookups += 1
                if match_pages:
                    self.stats.prefix_hits += 1
                    self.stats.prefix_pages_shared += len(match_pages)
                self.stats.prefill_tokens += len(req.tokens) - \
                    len(match_pages) * self.engine.ecfg.page_size
                logits, self.cache = self.engine.insert_request_prefix(
                    self.cache, i, req.tokens, match_pages, match_perms
                )
                self._slot_shared[i] = tuple(int(p) for p in match_pages)
                self._register(req, i)
            else:
                with self._prefill_span(req, "insert", len(req.tokens)):
                    logits, self.cache = self.engine.insert_request(
                        self.cache, i, req.tokens
                    )
            self._activate(req, i, self._first_token(req, logits))
            self._check_invariants()
            if self.slots[i].done:  # max_new == 1 or instant EOS
                finished.append(self._retire_slot(i))
        return finished

    def _prefill_span(self, req: Request, kind: str, n_tokens: int,
                      n_ctx: int = 0):
        """Count a plain prefill dispatch's prompt tokens; its span
        (``kind``: insert, chunk or final)."""
        self.stats.prefill_tokens += n_tokens
        return span("prefill", rid=req.rid, kind=kind, tokens=n_tokens,
                    n_ctx=n_ctx)

    def _first_token(self, req: Request, logits) -> int:
        """An admission's first token: the host waits here for its
        prefill."""
        with span("prefill.wait", rid=req.rid):
            return int(jnp.argmax(logits))

    def _activate(self, req: Request, i: int, tok: int) -> None:
        """Occupy slot ``i`` with ``req`` whose first token is ``tok``."""
        req.status = "active"
        self.slots[i] = _Active(req, tok, self.eos_id)
        self._last_tok[i] = tok
        self._spec_backoff[i] = 0
        self._spec_cooldown[i] = 0
        if self._drafter is not None:
            # the drafter sees prompt + every generated token (the first
            # token included: it is the next launch's seed)
            self._drafter.seed(i, list(np.asarray(req.tokens)) + [tok])
        now = time.perf_counter()
        req.t_first = now
        req.token_times.append(now)
        self.stats.admitted += 1
        self.stats.tokens_out += 1
        if self._ever_used[i]:
            self.stats.slot_reuses += 1
        self._ever_used[i] = True

    # -- chunked interleaved admission --------------------------------------
    def _start_task(self, finished: list[Request]) -> _PrefillTask | None:
        """Claim a slot (and pages) for the FIFO head and build its chunked
        admission task; None while blocked (no free slot / no pages).

        A plain prompt no longer than one chunk budget is admitted here
        directly through the fused monolithic prefill+insert launch: the
        bounded stall is the whole prefill either way, and one dispatch
        beats chunk_step + chunk_insert. Such admissions complete within
        this call (appending to ``finished`` on instant retirement) and
        return None with no task outstanding. Swapped-out requests resume
        here too — a restore is one scatter, not a prefill, so it also
        completes within the call."""
        head = self._head()
        if head is None:
            return None
        slot = self._seat(head)
        if slot is None:
            return None
        if self._swap is not None and head.rid in self._swap:
            meta = self._swap.meta(head.rid)
            if self.engine.ecfg.paged and not self._fit_pages(
                    head, self._pages_needed(head) - meta["n_shared"],
                    set(meta["shared"])):
                return None
            self._resume(self._pop_head(head), slot)
            return None
        if self._session_try(head, slot) != "miss":
            # hit: resumed (a restore is one scatter, not a prefill);
            # blocked: the parked entry waits for pages — either way no task
            return None
        match_pages: list[int] = []
        match_perms = None
        if self._index is not None and self.cache is not None:
            match_pages, match_perms = self._match(head)
        if self.engine.ecfg.paged:
            need_new = self._pages_needed(head) - len(match_pages)
            if not self._fit_pages(head, need_new, set(match_pages)):
                return None
        req = self._pop_head(head)
        if self._sessions is not None:
            self.stats.session_lookups += 1  # cold admission == miss
        if self.cache is None:
            self.cache = self.engine.alloc_slot_cache()
        if self.engine.ecfg.paged:
            self._reserved[slot] = self._pages_needed(req) - len(match_pages)
            self.stats.pages_reserved_peak = max(
                self.stats.pages_reserved_peak, sum(self._reserved.values())
            )
        S = len(req.tokens)
        if self._index is not None:
            self.stats.prefix_lookups += 1
            if match_pages:
                self.stats.prefix_hits += 1
                self.stats.prefix_pages_shared += len(match_pages)
            scratch = self.engine.prefix_chunk_start(
                self.cache, S, match_pages, match_perms
            )
            bounds = self.engine.prefix_chunk_bounds(S, len(match_pages))
            kind = "prefix"
        else:
            c = self.engine.chunk_tokens()
            if S <= c:  # single-chunk prompt: fused fast path
                with self._prefill_span(req, "insert", S):
                    logits, self.cache = self.engine.insert_request(
                        self.cache, slot, req.tokens
                    )
                self._activate(req, slot, self._first_token(req, logits))
                self._check_invariants()
                if self.slots[slot].done:  # max_new == 1 or instant EOS
                    finished.append(self._retire_slot(slot))
                return None
            scratch = self.engine.chunk_init(S)
            bounds = sorted(set(range(0, S, c)) | {S})
            kind = "plain"
        return _PrefillTask(req, slot, kind, scratch, bounds,
                            tuple(int(p) for p in match_pages), match_perms)

    def _advance_task(self, finished: list[Request]) -> None:
        """One scheduler step's worth of admission progress: at most
        ``prefill_chunk_pages`` pages of prefill, inserting + activating
        the row when the last segment completes."""
        if self._task is None:
            self._task = self._start_task(finished)
        t = self._task
        if t is None:
            return
        # plain segments already span the full chunk budget; prefix
        # segments are single pages (PR-5 trace), so batch them up to it
        budget = 1 if t.kind == "plain" \
            else max(1, self.engine.ecfg.prefill_chunk_pages)
        for _ in range(budget):
            if t.done:
                break
            s0, s1 = t.bounds[t.idx], t.bounds[t.idx + 1]
            seg = t.req.tokens[s0:s1]
            if t.kind == "plain":
                if t.idx == len(t.bounds) - 2:  # last segment: fused insert
                    with self._prefill_span(t.req, "final", s1 - s0, s0):
                        t.logits, self.cache = self.engine.chunk_final(
                            self.cache, t.slot, t.scratch, seg, s0
                        )
                    t.scratch = None
                else:
                    with self._prefill_span(t.req, "chunk", s1 - s0, s0):
                        t.logits, t.scratch = self.engine.chunk_step(
                            t.scratch, seg, s0
                        )
            else:
                self.stats.prefill_tokens += s1 - s0
                t.logits, t.scratch = self.engine.prefix_chunk_step(
                    t.scratch, seg, s0
                )
            t.idx += 1
            self.stats.prefill_chunks += 1
        if t.done:
            self._finish_task(t, finished)
            self._task = None

    def _finish_task(self, t: _PrefillTask, finished: list[Request]) -> None:
        i = t.slot
        if t.kind == "prefix":
            self.cache = self.engine.prefix_chunk_finish(
                self.cache, i, t.scratch, t.match_pages, len(t.req.tokens)
            )
            self._slot_shared[i] = t.match_pages
            self._register(t.req, i)
        # plain rows were already scattered by the fused final chunk
        self._activate(t.req, i, self._first_token(t.req, t.logits))
        self._check_invariants()
        if self.slots[i].done:  # max_new == 1 or instant EOS
            finished.append(self._retire_slot(i))

    def _chunk_plan(self) -> tuple[int, int | None]:
        """(n_steps, n_bucket) for the next decode launch.

        n_steps = min(decode_chunk, min over occupied rows of remaining
        budget) — no row can overshoot its ``max_new`` inside a chunk, so
        retirement stays exact. n_bucket upper-bounds every row's n_comp
        through the WHOLE chunk via the host-side token counts (n_comp <=
        cached tokens <= cached_tokens_now + n_steps)."""
        occupied = [a for a in self.slots if a is not None]
        n_steps = max(1, min(self.engine.ecfg.decode_chunk,
                             min(a.remaining for a in occupied)))
        if any(a.forced for a in occupied):
            # teacher-forced suffix ingestion overrides the launch argmax
            # from the HOST — a multi-step in-graph chunk would feed the
            # model its own (wrong) token, so ingesting steps go one at a
            # time (the other rows still decode usefully in the launch)
            n_steps = 1
        n_max = max(a.cached_tokens for a in occupied) + n_steps
        return n_steps, self.engine.bucket_for(n_max)

    def _log_launch(self, n_steps: int, n_bucket: int | None):
        if not self.engine.ecfg.log_launches:
            return
        self.stats.launches.append((
            n_steps,
            self.engine.ecfg.capacity if n_bucket is None else n_bucket,
            [a.cached_tokens for a in self.slots if a is not None],
        ))

    # -- cancellation / deadlines / faults -----------------------------------
    def _finish_dead(self, req: Request, why: str,
                     finished: list[Request]) -> None:
        """Retire a request that never (re-)reached a slot: queued, swapped
        out, or mid-prefill-chunk. Output is whatever was generated before
        it was swapped out (empty otherwise)."""
        out: list[int] = []
        if self._swap is not None and req.rid in self._swap:
            out = self._swap.meta(req.rid)["out"]
            self._swap.drop(req.rid)
        req.output = np.asarray(out, np.int32)
        req.status = why
        self.done[req.rid] = req
        if why == "cancelled":
            self.stats.cancelled += 1
        else:
            self.stats.expired += 1
        finished.append(req)

    def _abort_task(self) -> None:
        """Drop the in-flight chunked admission at its current chunk
        boundary. Mid-task state is leak-free by construction: plain tasks
        hold no device pages before their fused final chunk, and prefix
        tasks take shared-page references only at ``prefix_chunk_finish`` —
        the only thing to hand back is the host-side reservation."""
        t, self._task = self._task, None
        self._reserved.pop(t.slot, None)
        self._check_invariants()

    def _reap(self, finished: list[Request]) -> None:
        """Honor ``cancel()`` and ``deadline_ms`` at the top of the step —
        before any new work launches — for queued, swapped-out,
        mid-prefill-chunk and decoding requests alike. All three ends meet
        the same retirement path (``_retire_slot`` / ``_finish_dead``)."""
        now = time.perf_counter()

        def dead(req: Request) -> str | None:
            if req.cancelled:
                return "cancelled"
            if req.deadline_ms is not None and \
                    (now - req.t_submit) * 1e3 > req.deadline_ms:
                return "expired"
            return None

        for q in self.queues.values():
            for req in [r for r in q if dead(r)]:
                q.remove(req)
                self._finish_dead(req, dead(req), finished)
        if self._task is not None and dead(self._task.req):
            req = self._task.req
            self._abort_task()
            self._finish_dead(req, dead(req), finished)
        for i in range(self.n_slots):
            act = self.slots[i]
            if act is not None:
                why = dead(act.req)
                if why is not None:
                    finished.append(self._retire_slot(i, why))

    def _fault_victims(self, n: int) -> list[Request]:
        """Deterministic victim order for cancel/deadline storms: occupied
        slots ascending, then queued requests in submit order, then the
        in-flight prefill task."""
        out: list[Request] = []
        for i in range(self.n_slots):
            if len(out) >= n:
                return out
            if self.slots[i] is not None:
                out.append(self.slots[i].req)
        for req in sorted((r for q in self.queues.values() for r in q),
                          key=lambda r: r._seq):
            if len(out) >= n:
                return out
            out.append(req)
        if len(out) < n and self._task is not None:
            out.append(self._task.req)
        return out

    def _apply_faults(self, finished: list[Request]) -> None:
        """Fire this step's scheduled faults (see ``distributed.fault.
        FaultPlan`` for kind semantics). Faults act through the same seams
        real traffic does — cancel flags, deadline rewrites, requeues,
        forced end-of-turn parks — so every invariant the scheduler
        maintains must survive them."""
        if self._faults is None:
            return
        for ev in self._faults.at(self._step_no):
            self._faults.fired.append(ev)
            if ev.kind == "pool_squeeze":
                self._squeeze = max(0, int(ev.arg))
            elif ev.kind in ("cancel", "deadline"):
                for req in self._fault_victims(max(1, int(ev.arg))):
                    if ev.kind == "cancel":
                        req.cancel()
                    else:
                        req.deadline_ms = 1e-9  # expired at the next reap
            elif ev.kind == "chunk_abort":
                if self._task is not None:
                    req = self._task.req
                    self._abort_task()
                    self._requeue(req)  # prefill restarts from scratch
            elif ev.kind == "straggler":
                self._observe_launch(float(ev.arg))
            elif ev.kind == "park":
                # voluntary end-of-turn mid-generation: the user stopped
                # typing — retire with the partial output, park the cache
                n = max(1, int(ev.arg))
                for i, act in enumerate(self.slots):
                    if n == 0:
                        break
                    if act is not None:
                        finished.append(self._retire_slot(i, "parked"))
                        n -= 1
            elif ev.kind == "resume":
                self._fault_resume(max(1, int(ev.arg)))
            elif ev.kind == "session_expire":
                if self._sessions is not None:
                    self._sessions.expire_now(max(1, int(ev.arg)))

    def _observe_launch(self, dt: float) -> None:
        """Feed one decode-launch wall time to the straggler watchdog; a
        sustained-straggler verdict permanently degrades this server to
        plain decode (speculation off — graceful, exactness-neutral)."""
        if self._watchdog is None or self._spec_degraded:
            return
        if self._watchdog.observe(dt) == "exclude":
            self._spec_degraded = True

    def step(self) -> list[Request]:
        """Reap cancellations/deadlines, fire scheduled faults, then one
        bounded prefill chunk (or a monolithic admission sweep when
        ``prefill_chunk_pages == 0``) + one decode launch + retire. Returns
        requests finished now.

        One launch is a donated multi-step chunk (``decode_chunk`` > 1) or a
        single decode step; both mask attention to each row's own length and
        give per-request outputs bit-identical to B=1 ``Engine.generate``.
        """
        t0 = time.perf_counter()
        self._step_no += 1
        finished: list[Request] = []
        queued = sum(len(q) for q in self.queues.values())
        with span("step", step=self._step_no, occupied=self.n_occupied,
                  queued=queued):
            self._apply_faults(finished)
            with span("reap"):
                self._reap(finished)
            with span("admit"):
                if self.engine.ecfg.prefill_chunk_pages > 0:
                    self._advance_task(finished)
                else:
                    finished.extend(self._admit())
            if self.n_occupied:
                t_dec = time.perf_counter()
                if self.engine.ecfg.spec_decode and not self._spec_degraded:
                    self._decode_spec(finished)
                else:
                    if self.engine.ecfg.spec_decode:
                        self.stats.degraded_steps += 1
                    self._decode_plain(finished)
                self._observe_launch(time.perf_counter() - t_dec)
        if self._sessions is not None:  # mirror store-side eviction counts
            self.stats.session_evictions = (
                self._sessions.evictions + self._sessions.expired
            )
        self.stats.wall_s += time.perf_counter() - t0
        return finished

    def _decode_plain(self, finished: list[Request]) -> None:
        """The non-speculative launch: donated multi-step chunk or a
        single bucketed decode step."""
        chunked = self.engine.ecfg.decode_chunk > 1 and \
            self.engine._decode_multi is not None
        with span("decode.plan"):
            n_steps, n_bucket = self._chunk_plan()
            rows = self._rows_ahead(n_steps if chunked else 1)
        if chunked:
            self._decode_chunk(n_steps, n_bucket, rows, finished)
        else:
            self._decode_single(n_bucket, rows, finished)

    def _decode_single(self, n_bucket: int | None, rows: list[list[int]],
                       finished: list[Request]):
        """PR-2 style per-token launch (decode_chunk=1), optionally bucketed."""
        tok = jnp.asarray(self._last_tok[:, None])
        logits, self.cache = self.engine.decode(self.cache, tok, n_bucket)
        nxt = np.asarray(jnp.argmax(logits, -1).astype(jnp.int32))
        now = time.perf_counter()
        self.stats.decode_steps += 1
        self.stats.chunk_launches += 1
        self._count_attn(rows, n_bucket)
        self._log_launch(1, n_bucket)
        for i, act in enumerate(self.slots):
            if act is None:
                continue
            self.stats.occupied_slot_steps += 1
            if act.forced:
                # teacher-forced suffix ingestion (session resume): the
                # launch cached the previous token; the argmax is
                # overridden by the next already-known prompt token —
                # nothing is emitted, no EOS/max_new bookkeeping applies
                t = act.forced.pop(0)
                self._last_tok[i] = t
                if self._drafter is not None:
                    self._drafter.extend(i, (t,))
                continue
            t = int(nxt[i])
            act.out.append(t)
            if act.req.t_first is None:  # first real token of a session hit
                act.req.t_first = now
            act.req.token_times.append(now)
            self._last_tok[i] = t
            self.stats.tokens_out += 1
            if self._drafter is not None:
                self._drafter.extend(i, (t,))
            if (self.eos_id is not None and t == self.eos_id) or \
                    len(act.out) >= act.req.max_new:
                finished.append(self._retire_slot(i))
        if self.n_occupied < self.n_slots:
            # free rows received a junk append this step; re-zero their
            # counters so free slots stay inert (never flush, never grow)
            active = jnp.asarray([s is not None for s in self.slots], bool)
            self.cache = self.engine.mask_free(self.cache, active)

    def _decode_chunk(self, n_steps: int, n_bucket: int | None,
                      rows: list[list[int]], finished: list[Request]):
        """Donated multi-step launch: up to ``n_steps`` tokens per row.

        Rows that emit EOS mid-chunk keep decoding (their later tokens are
        junk, discarded here — rows are independent, so other rows are
        unaffected); the in-graph loop early-exits once ALL rows hit EOS.
        """
        active = [a is not None for a in self.slots]
        toks, n_exec, self.cache = self.engine.decode_chunk(
            self.cache, jnp.asarray(self._last_tok[:, None]), active,
            n_steps, self.eos_id, n_bucket,
        )
        now = time.perf_counter()
        self.stats.chunk_launches += 1
        self.stats.decode_steps += n_exec
        self.stats.occupied_slot_steps += n_exec * self.n_occupied
        grid, dead, field = self._count_attn(rows[:n_exec], n_bucket)
        self._log_launch(n_exec, n_bucket)
        with span("decode.emit", n_exec=n_exec, grid_steps=grid,
                  dead_steps=dead, field_major_steps=field):
            self._emit_chunk(toks, n_exec, now, finished)

    def _emit_chunk(self, toks: np.ndarray, n_exec: int, now: float,
                    finished: list[Request]) -> None:
        """Hand each row its tokens from a multi-step launch; retire the
        rows that finished."""
        for i, act in enumerate(self.slots):
            if act is None:
                continue
            if act.forced:
                # teacher-forced suffix ingestion (session resume): the
                # chunk plan pins n_steps to 1 while any row has forced
                # tokens pending, so exactly one append landed — override
                # the argmax with the already-known prompt token
                if n_exec:
                    t = act.forced.pop(0)
                    self._last_tok[i] = t
                    if self._drafter is not None:
                        self._drafter.extend(i, (t,))
                continue
            emitted = []
            for s in range(n_exec):
                t = int(toks[s, i])
                emitted.append(t)
                act.out.append(t)
                if act.req.t_first is None:  # first real token of a hit
                    act.req.t_first = now
                act.req.token_times.append(now)
                self._last_tok[i] = t
                self.stats.tokens_out += 1
                if (self.eos_id is not None and t == self.eos_id) or \
                        len(act.out) >= act.req.max_new:
                    act.done = True
                    break  # tokens past EOS are junk
            if self._drafter is not None:
                self._drafter.extend(i, emitted)
            if act.done:
                finished.append(self._retire_slot(i))
        # no trailing mask_free here: decode_steps re-zeroes free-row
        # counters in-graph every iteration, and _retire resets the rows
        # freed just now, so the cache already satisfies the invariant

    # -- paged attention work -------------------------------------------------
    def _paged_kernel(self) -> bool:
        """Decode attention runs the paged Pallas kernel."""
        e = self.engine
        return e.ecfg.paged and e.ecfg.backend == "pallas" and \
            e.pack_cfg.policy != "none"

    def _rows_ahead(self, n_steps: int) -> list[list[int]]:
        """Per step of a launch of ``n_steps``, every row's compressed
        tokens when that step's attention reads them (after its append);
        0 for a free row. Empty where no paged kernel runs."""
        if not self._paged_kernel():
            return []
        return [[0 if a is None else self._counters(a, s)[0]
                 for a in self.slots] for s in range(1, n_steps + 1)]

    def _count_attn(self, rows: list[list[int]], n_bucket: int | None,
                    per_layer: int = 1) -> tuple[int, int, int]:
        """Add the paged kernel's (grid, dead, field-major) steps over a
        launch's executed steps (``rows``, from ``_rows_ahead``) to the
        stats, the kernel running ``per_layer`` times in each layer of each
        step; on a mesh, the whole launch's. Returns what it added."""
        if not rows:
            return 0, 0, 0
        from ..kernels.packed_attention import paged_grid_steps, paged_pieces

        e = self.engine
        n_tokens = min(e.ecfg.capacity if n_bucket is None else n_bucket,
                       e.ecfg.capacity)
        grid = dead = 0
        for n_comp in rows:
            g, d = paged_grid_steps(n_comp, e.cfg.n_kv_heads, n_tokens,
                                    e.ecfg.page_size)
            grid, dead = grid + g, dead + d
        k = e.cfg.n_layers * per_layer
        # every tier of one width: the kernel decodes field-major
        pieces = paged_pieces(e.pack_cfg.k_spec(e.cfg.hd),
                              e.pack_cfg.v_spec(e.cfg.hd), e.ecfg.page_size)
        field = k * grid if pieces > 1 else 0
        self.stats.attn_grid_steps += k * grid
        self.stats.attn_dead_steps += k * dead
        self.stats.attn_field_major_steps += field
        return k * grid, k * dead, field

    # -- speculative decode --------------------------------------------------
    def _counters(self, act: _Active, ahead: int = 0) -> tuple[int, int]:
        """Host-mirrored (n_comp, n_resid) for an occupied slot, after
        ``ahead`` more appends — exact, zero device syncs. The device
        counters are a deterministic function of prompt length and
        cached-token count: prefill flushes every full
        block (``n_comp = Lb``), then each cached decode token appends one
        residual slot with a block flush whenever the residual hits R at
        append start (paged rows stop flushing once the compressed region
        is at capacity, exactly ``core.cache.append_token``'s guard).

        Re-anchored rows (session resume, or a preemption of one) start
        from the anchor's exact ``(n_comp, n_resid)`` snapshot and apply
        the same append recurrence to the tokens cached since — the
        closed-form flush count is anchor-independent."""
        pack = self.engine.pack_cfg
        if act.base is not None:
            lb, r0, cached0, _ = act.base
            r = r0 + (act.cached_tokens + ahead - cached0)
        else:
            S = len(act.req.tokens)
            lb = (S // pack.block) * pack.block
            # the residual as if no flush had ever fired
            r = S - lb + len(act.out) - 1 + ahead
        f = 0
        if r > pack.residual:  # flushes fire as soon as r crosses R
            f = -(-(r - pack.residual) // pack.block)
        if self.engine.ecfg.paged:
            f = min(f, (self.engine.ecfg.capacity - lb) // pack.block)
        return lb + f * pack.block, r - f * pack.block

    def _plan_spec(self) -> tuple[np.ndarray, np.ndarray] | None:
        """Per-row draft plan for one verify launch.

        The window width is FIXED at ``spec_k + 1`` (one compiled program
        per launch bucket); ragged per-row draft lengths ride through the
        ``lens`` mask, junk-padded. Each row's draft is capped by (a) its
        post-seed residual headroom — the verify window must never cross a
        compression flush or page pop (``core.cache.append_window``) — and
        (b) ``remaining - 1``, so accepted-prefix emission can never
        overshoot ``max_new``. Rows in acceptance backoff (every draft of
        their recent launches died) sit the launch out and their cooldown
        ticks down. Returns None when no active row has a proposal: a
        verify window would then be pure overhead, and the caller falls
        back to the plain decode launch (this fallback plus the backoff is
        what keeps the acceptance≈0 regime at baseline speed)."""
        ecfg = self.engine.ecfg
        pack = self.engine.pack_cfg
        w = ecfg.spec_k + 1
        toks = np.zeros((self.n_slots, w), np.int32)
        lens = np.ones((self.n_slots,), np.int32)
        any_draft = False
        for i, act in enumerate(self.slots):
            if act is None:
                continue
            toks[i, 0] = self._last_tok[i]
            if act.forced:
                continue  # suffix ingestion: seed-only, next token is known
            if self._spec_cooldown[i] > 0:
                self._spec_cooldown[i] -= 1
                continue
            c, r = self._counters(act)
            # simulate the seed append: the headroom cap is on POST-seed
            # n_resid (drafts sit at n_resid + i - 1, i <= lens - 1 <= R)
            if r >= pack.residual and (
                    not ecfg.paged or c + pack.block <= ecfg.capacity):
                r -= pack.block
            r += 1
            kb = min(ecfg.spec_k, pack.residual - r, act.remaining - 1)
            if kb <= 0:
                continue
            d = self._drafter.draft(i, kb)
            if not d:
                continue
            toks[i, 1:1 + len(d)] = d
            lens[i] = 1 + len(d)
            any_draft = True
        return (toks, lens) if any_draft else None

    def _decode_spec(self, finished: list[Request]) -> None:
        """Speculative launch: per-slot n-gram drafts verified by ONE
        batched q_len=w forward over the compressed paged cache; the
        accepted prefix commits by counter advance, rejected drafts die as
        dead bytes past ``n_resid``. Acceptance rule: draft i is accepted
        iff it equals the greedy argmax after window position i-1 — so
        every emitted token equals what stepwise decode would have emitted
        (for ANY draft content), and per-request outputs stay
        bit-identical to the plain path. Speculation only changes how many
        tokens one model pass yields."""
        plan = self._plan_spec()
        if plan is None:
            self._decode_plain(finished)
            return
        toks, lens = plan
        w = toks.shape[1]
        # TIGHT compressed-region bound: the headroom cap guarantees the
        # window never flushes after the seed, so post-seed ``n_comp`` is
        # known exactly on the host — the verify bucket only has to cover
        # it (the plain chunk path can flush mid-chunk, so it must bound by
        # total tokens; this tighter bound is speculation-only and is a
        # real fraction of the verify win at long residuals)
        n_comp = [0] * self.n_slots
        for i, a in enumerate(self.slots):
            if a is None:
                continue
            c, r = self._counters(a)
            if r >= self.engine.pack_cfg.residual and (
                    not self.engine.ecfg.paged or
                    c + self.engine.pack_cfg.block <= self.engine.ecfg.capacity):
                c += self.engine.pack_cfg.block  # the seed append flushes
            n_comp[i] = c
        n_bucket = self.engine.bucket_for(max([1, *n_comp]))
        active = [s is not None for s in self.slots]
        # one dispatch: verify + accept + commit + free-row masking (the
        # commit lands in-graph BEFORE the retire resets below, so a
        # retiring row's reset is never resurrected by a late commit)
        hat, n_accept, self.cache = self.engine.decode_verify(
            self.cache, toks, lens, active, n_bucket
        )
        now = time.perf_counter()
        self.stats.decode_steps += 1
        self.stats.chunk_launches += 1
        self.stats.spec_launches += 1
        if self._paged_kernel():  # one kernel launch per window position
            self._count_attn([n_comp], n_bucket, per_layer=w)
        self._log_launch(1, n_bucket)
        for i, act in enumerate(self.slots):
            if act is None:
                continue
            self.stats.occupied_slot_steps += 1
            if act.forced:
                # teacher-forced suffix ingestion: the row rode the verify
                # launch seed-only (lens == 1, its seed append committed);
                # the model's next token is overridden by the known one
                t = act.forced.pop(0)
                self._last_tok[i] = t
                self._drafter.extend(i, (t,))
                continue
            m = int(n_accept[i])  # accepted drafts (in-graph rule)
            kb = int(lens[i]) - 1
            self.stats.spec_drafted += kb
            self.stats.spec_accepted += m
            if kb > 0 and self.engine.ecfg.spec_backoff > 0:
                if m == 0:
                    # every draft died: exponential cooldown before this
                    # slot may draft again (capped at ecfg.spec_backoff)
                    self._spec_backoff[i] = min(
                        max(1, self._spec_backoff[i] * 2),
                        self.engine.ecfg.spec_backoff,
                    )
                    self._spec_cooldown[i] = self._spec_backoff[i]
                else:
                    self._spec_backoff[i] = 0
            # emit the m accepted tokens plus the model's own next token
            # (the correction when m < kb, the bonus token when m == kb)
            emitted = []
            for j in range(m + 1):
                t = int(hat[i, j])
                emitted.append(t)
                act.out.append(t)
                if act.req.t_first is None:  # first real token of a hit
                    act.req.t_first = now
                act.req.token_times.append(now)
                self._last_tok[i] = t
                self.stats.tokens_out += 1
                if (self.eos_id is not None and t == self.eos_id) or \
                        len(act.out) >= act.req.max_new:
                    act.done = True
                    break  # tokens past EOS are junk
            self._drafter.extend(i, emitted)
        for i, act in enumerate(self.slots):
            if act is not None and act.done:
                finished.append(self._retire_slot(i))

    def run(self) -> list[Request]:
        """Drain the queue and all slots; returns every finished request."""
        finished: list[Request] = []
        while self.queue or self.n_occupied or self._task is not None:
            finished.extend(self.step())
        return finished
