"""The inference engine: one jitted chunked iteration over fixed shapes,
paged KV, continuous batching with a per-iteration token budget,
temperature/top-p sampling.

Design (TPU-native, runs for real on CPU; see DESIGN.md §2):
  - prefill and decode are ONE model path (``LM.decode_chunk``): every batch
    row feeds a chunk of tokens of one sequence whose KV is written straight
    into the paged pool. Decode is a chunk of 1.
  - two fixed call shapes, each compiled once as its own named program:
    ``prefill_step`` (chunk_rows, prefill_chunk) for the prefill pack and
    ``decode_step`` (max_slots, 1) for the decode sweep (``spec_step_<C>``
    per speculative width, ``cow_copy`` for page copies), so the device
    trace tells them apart. There is no per-length bucket recompile ladder,
    no dense per-request prefill cache, and no post-prefill scatter copy.
  - each ``step()`` is a token-budget iteration (Sarathi-style): all pending
    decode tokens plus up to ``token_budget - n_decode`` prefill-chunk
    tokens. Long prompts prefill over several iterations, so an admitted
    prompt never head-of-line blocks running decodes.
  - the scheduler's max-utilization policy pauses requests under page
    pressure (see scheduler.py); a paused, partially-prefilled slot resumes
    from chunk 0 with its generated tokens intact.

``host_overhead_s`` models engine-runtime software overhead per iteration and
is used ONLY by the benchmark harness to represent baseline engines
(HF/vLLM-class host overhead) — the ScaleLLM engine runs with 0.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kv_cache import PagedAllocator, PrefixCache
from repro.core.metrics import Request, now
from repro.core.observability import Tracer, span
from repro.core.scheduler import ContinuousBatchScheduler, SlotState
from repro.core.spec import PromptLookupDraft, verify_draft
from repro.core.timeline import StepRecord
from repro.models import LM, RunCtx

# fixed operand width of the jitted COW page-copy call (pads with 0->0
# null-page self-copies) so repeated copies never retrace
COW_BUF = 8


@dataclass
class EngineConfig:
    max_slots: int = 8
    page_size: int = 16
    num_pages: int = 512
    max_seq: int = 512
    prefill_bucket: int = 32          # legacy knob: default for prefill_chunk
    prefill_chunk: int = 0            # chunked-prefill size (0: prefill_bucket)
    token_budget: int = 0             # per-iteration token cap (0: slots+2*chunk)
    temperature: float = 0.5
    top_p: float = 0.7
    greedy: bool = False
    scheduler: str = "max_utilization"
    enable_prefix_cache: bool = True  # shared-prefix KV reuse (auto-off for
                                      # ssm/encdec/vlm: pages alone don't
                                      # capture their recurrent/cross state)
    enable_speculative: bool = False  # prompt-lookup drafting + multi-token
                                      # verify on the chunk path (auto-off
                                      # for ssm/hybrid: conv + recurrent
                                      # carry advance on every fed token and
                                      # cannot be rolled back per position)
    spec_k: int = 4                   # max draft tokens per slot per step
    spec_ngram_max: int = 3           # prompt-lookup suffix n-gram bounds
    spec_ngram_min: int = 1
    eos_id: int = -1                  # -1: no EOS (length-controlled)
    host_overhead_s: float = 0.0      # baseline-engine emulation knob (benchmarks)
    profile_steps: bool = True        # keep one StepRecord per iteration in a
                                      # bounded ring (cheap: a dataclass + a
                                      # dozen counter reads per device call)
    step_records_cap: int = 4096      # ring-buffer capacity for step records
    cache_dtype: Any = jnp.float32
    seed: int = 0

    @property
    def max_pages_per_seq(self) -> int:
        return (self.max_seq + self.page_size - 1) // self.page_size


@dataclass
class TokenEvent:
    request: Request
    token: int                 # -1: terminal no-token event (rejected request)
    t_emit: float
    finished: bool


# Module-level jit cache: replicas sharing a model reuse compiled programs
# (a fleet of N replicas compiles once, not N times). Each entry is one
# named program (``name``) of one call shape.
_JIT_CACHE: Dict[Tuple, Callable] = {}


def _cached_jit(name: str, model, ctx, sampling, fn, donate: int):
    """``jax.jit(fn)`` under ``name``: the program, and the module the
    device trace shows for it, are called ``jit_<name>``."""
    key = (name, id(model), ctx, sampling)
    if key not in _JIT_CACHE:
        def program(*args):
            return fn(*args)
        program.__name__ = program.__qualname__ = name
        _JIT_CACHE[key] = jax.jit(program, donate_argnums=(donate,))
    return _JIT_CACHE[key]


# the paged cache's page pools, by key: K and V of plain attention layers,
# the latent rows of latent layers; each (layers, num_pages, ...)
PAGE_POOLS = ("kp", "vp", "lat")


def kv_bytes_per_token(cache, pool_tokens: int) -> int:
    """Bytes of page pool one cached token takes, summed over the layers."""
    return sum(x.size * x.dtype.itemsize for path, x in jax.tree_util.tree_leaves_with_path(cache)
               if getattr(path[-1], "key", None) in PAGE_POOLS) // pool_tokens


def sample_tokens(logits, key, temperature: float, top_p: float, greedy: bool):
    """logits (B, V) -> (B,) int32. Nucleus sampling with temperature."""
    if greedy or temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits.astype(jnp.float32) / temperature
    sl, si = jax.lax.top_k(scaled, scaled.shape[-1])                  # descending sort
    p = jax.nn.softmax(sl, axis=-1)
    keep = (jnp.cumsum(p, axis=-1) - p) < top_p             # first always kept
    sl = jnp.where(keep, sl, -jnp.inf)
    g = jax.random.gumbel(key, sl.shape)
    choice = jnp.argmax(sl + g, axis=-1)
    return jnp.take_along_axis(si, choice[:, None], axis=-1)[:, 0].astype(jnp.int32)


class InferenceEngine:
    """Single-replica engine. Thread-safety is owned by core.replica."""

    def __init__(self, model: LM, params, cfg: EngineConfig, ctx: Optional[RunCtx] = None,
                 tracer: Optional[Tracer] = None):
        self.model = model
        self.params = params
        self.cfg = cfg
        self.tracer = tracer
        self.replica_id = ""              # set by the Replica serving it
        # the backend follows the platform (RunCtx resolves "auto"): the
        # compiled Pallas kernels on a TPU, the XLA references elsewhere
        self.ctx = ctx or RunCtx(moe_strategy="dropless", block_q=128, block_kv=128)
        # the engine runs where its params sit: the pool, the key and every
        # per-step upload are created on that device, so each jitted call of
        # this engine runs there (replicas pinned to different chips)
        devices = {d for x in jax.tree.leaves(params) for d in x.devices()}
        if len(devices) != 1:
            raise ValueError(f"an engine's params must sit on one device, not {devices}")
        self.device = devices.pop()
        cfgm = model.cfg
        self.pos_offset = cfgm.vision.n_patches if cfgm.vision is not None else 0
        self.chunk = min(cfg.prefill_chunk or max(cfg.prefill_bucket, 1), cfg.max_seq)
        self.token_budget = max(cfg.token_budget or (cfg.max_slots + 2 * self.chunk),
                                cfg.max_slots + 1)
        self.chunk_rows = max(1, min(self.token_budget // self.chunk, cfg.max_slots))
        self.allocator = PagedAllocator(cfg.num_pages, cfg.page_size, cfg.max_pages_per_seq)
        # prefix sharing is only sound when a page fully captures a token
        # range's state: SSM carries recurrent state, encdec carries cross-KV,
        # and VLM patch prefixes shift kv positions — all gated off.
        has_ssm = any("M" in g.pattern for g in cfgm.layer_groups)
        prefix_ok = (cfg.enable_prefix_cache and not has_ssm
                     and cfgm.encoder is None and cfgm.vision is None)
        self.prefix_cache = PrefixCache(self.allocator) if prefix_ok else None
        # speculative decoding rolls KV back by a pure length decrement —
        # sound for paged attention (pages are append-only and masked by
        # ``lengths``), unsound for SSM/hybrid conv + recurrent carry.
        self.spec_on = cfg.enable_speculative and cfg.spec_k > 0 and not has_ssm
        self.spec_kmax = cfg.spec_k
        self.draft_source = (PromptLookupDraft(cfg.spec_ngram_max, cfg.spec_ngram_min)
                             if self.spec_on else None)
        self.scheduler = ContinuousBatchScheduler(
            cfg.max_slots, self.allocator, policy=cfg.scheduler, max_seq=cfg.max_seq,
            kv_extra=self.pos_offset, prefix_cache=self.prefix_cache,
            tracer=tracer)
        with jax.default_device(self.device):
            self.cache = self._put(model.init_cache(
                cfg.max_slots, cfg.max_seq, cfg.cache_dtype,
                page_size=cfg.page_size, num_pages=cfg.num_pages))
            self._key = self._put(jax.random.PRNGKey(cfg.seed))
        self.kv_bytes_per_token = kv_bytes_per_token(self.cache, cfg.num_pages * cfg.page_size)
        self.page_table = np.zeros((cfg.max_slots, cfg.max_pages_per_seq), np.int32)
        self.extras: Dict[str, Any] = {}  # frames/patches per request (encdec/vlm)
        sampling = (cfg.temperature, cfg.top_p, cfg.greedy, cfg.page_size)
        self._prefill_jit = _cached_jit("prefill_step", model, self.ctx, sampling,
                                        self._step_fn, donate=1)
        self._decode_jit = _cached_jit("decode_step", model, self.ctx, sampling,
                                       self._step_fn, donate=1)
        self._cow_jit = _cached_jit("cow_copy", model, self.ctx, sampling,
                                    self._copy_pages_fn, donate=0)
        # spec-sweep width ladder: one compiled variant per chunk width
        # C = 1 + k for k in {1, 2, 4, ..., kmax}. The sweep picks the
        # smallest width covering the iteration's longest draft, so compute
        # (which scales with M*C regardless of how many rows carry drafts)
        # tracks actual draft volume instead of always paying 1 + kmax.
        self._spec_widths: List[int] = []
        if self.spec_on:
            k = 1
            while k < self.spec_kmax:
                self._spec_widths.append(1 + k)
                k *= 2
            self._spec_widths.append(1 + self.spec_kmax)
        self._sampling = sampling
        # resilience hooks (DESIGN.md §5): a FaultInjector evaluated at the
        # top of every step (None costs one attribute check; it matches
        # fault events against ``replica_id``), and the brown-out flag that
        # disables speculative drafting while degraded.
        self.injector = None
        self.degraded = False
        self.steps = 0
        self.decode_tokens = 0
        self.prefill_tokens = 0
        self.deadline_exceeded = 0        # requests cancelled past deadline
        self.spec_steps = 0               # iterations that ran the verify sweep
        self.drafted_tokens = 0           # draft tokens fed through verify
        self.accepted_tokens = 0          # draft tokens accepted (committed)
        self.prefix_cached_tokens = 0     # prefill tokens skipped via cache hits
        self.iter_token_counts: deque = deque(maxlen=4096)
        # iteration profiler: one StepRecord per step() in a bounded ring
        # (DESIGN.md §4); what the step ran, set by _step as it packs
        self.step_records: deque = deque(maxlen=cfg.step_records_cap)
        self._ran: Dict[str, Any] = {}

    # ------------------------------------------------------------- jitted fn
    def _step_fn(self, params, cache, tokens, starts, nvalid, slots, first,
                 page_table, key, frames=None, patches=None):
        """One fused iteration over a packed batch of per-sequence chunks
        (decode == chunk of 1). Returns (next_token (B,), experts reached
        per MoE layer, cache)."""
        counters: Dict[str, Any] = {}
        logits, cache = self.model.decode_chunk(
            params, tokens, cache, starts, nvalid, slots, first, self.ctx,
            page_table, frames=frames, patches=patches, counters=counters)
        nxt = sample_tokens(logits, key, self.cfg.temperature, self.cfg.top_p,
                            self.cfg.greedy)
        return jnp.where(nvalid > 0, nxt, 0), counters["experts"], cache

    def _spec_fn(self, params, cache, tokens, starts, nvalid, slots, first,
                 page_table, key):
        """Speculative decode sweep (DESIGN.md §3): every row feeds
        [last_token, d_1 .. d_k] in one chunk, the head scores all fed
        positions, and verify_draft turns the logits into (accepted-prefix
        length, next committed token) per row."""
        counters: Dict[str, Any] = {}
        logits, cache = self.model.decode_chunk(
            params, tokens, cache, starts, nvalid, slots, first, self.ctx,
            page_table, all_logits=True, counters=counters)
        n_acc, out = verify_draft(logits, tokens, nvalid, key,
                                  self.cfg.temperature, self.cfg.top_p,
                                  self.cfg.greedy)
        return n_acc, jnp.where(nvalid > 0, out, 0), counters["experts"], cache

    def _spec_jit_for(self, width: int):
        """Compiled spec sweep ``spec_step_<width>`` for chunk width C =
        ``width`` (lazy, cached process-wide like the step programs)."""
        return _cached_jit(f"spec_step_{width}", self.model, self.ctx,
                           self._sampling, self._spec_fn, donate=1)

    def lower_steps(self) -> Dict[str, Any]:
        """The two fixed-shape step programs, lowered for this engine's
        device with the operands ``step()`` passes them: ``"prefill"``
        (``prefill_step``, chunk_rows × chunk) and ``"decode"``
        (``decode_step``, max_slots × 1). Compiling one compiles what that
        shape's first step would; the step then reuses it."""
        cfg = self.cfg
        lowered = {}
        for name, program, (B, C) in (
                ("prefill", self._prefill_jit, (self.chunk_rows, self.chunk)),
                ("decode", self._decode_jit, (cfg.max_slots, 1))):
            ops = self._put((np.zeros((B, C), np.int32), np.zeros((B,), np.int32),
                             np.zeros((B,), np.int32), np.arange(B, dtype=np.int32),
                             np.zeros((B,), bool),
                             np.zeros((B, cfg.max_pages_per_seq), np.int32)))
            lowered[name] = program.lower(self.params, self.cache, *ops,
                                          self._key, None, None)
        return lowered

    def _copy_pages_fn(self, cache, src, dst):
        """Device-side page copy (the COW step): pool[:, dst] = pool[:, src]
        for every page pool (``PAGE_POOLS``) of every attention layer, in one
        fused call. Padding entries are 0->0 null-page self-copies (inert)."""
        def walk(c):
            if isinstance(c, dict):
                return {k: (v.at[:, dst].set(v[:, src]) if k in PAGE_POOLS
                            else walk(v)) for k, v in c.items()}
            if isinstance(c, (list, tuple)):
                return type(c)(walk(x) for x in c)
            return c
        return walk(cache)

    def _apply_copies(self, copies: List[Tuple[int, int]]) -> None:
        """Run queued COW page copies before the write that needed them.
        Copies are applied in order; a batch holds at most one copy per
        destination page so the gather-then-scatter semantics of a single
        call can never race two writes to one page."""
        with span("engine.cow", pages=len(copies)):
            while copies:
                batch, rest, seen = [], [], set()
                for s, d in copies:
                    (rest if d in seen else batch).append((s, d))
                    seen.add(d)
                for i in range(0, len(batch), COW_BUF):
                    sub = batch[i:i + COW_BUF]
                    src = np.zeros(COW_BUF, np.int32)
                    dst = np.zeros(COW_BUF, np.int32)
                    for j, (s, d) in enumerate(sub):
                        src[j], dst[j] = s, d
                    self.cache = self._cow_jit(self.cache, self._put(src),
                                               self._put(dst))
                copies = rest

    def _register_prefix(self, st: SlotState) -> None:
        """Insert the slot's newly completed full prompt pages into the
        prefix trie (content is final once fed: later writes to shared or
        cached pages always go through COW)."""
        if self.prefix_cache is None:
            return
        nb = min(st.fed, len(st.request.prompt_tokens)) // self.cfg.page_size
        if nb > st.registered_blocks:
            self.prefix_cache.insert(st.all_tokens,
                                     self.allocator.owned(st.slot), nb)
            st.registered_blocks = nb

    # ------------------------------------------------------------- helpers
    def _put(self, x):
        """Upload host operands (or move arrays) to this engine's device."""
        return jax.device_put(x, self.device)

    def _next_key(self):
        self._key, k = jax.random.split(self._key)
        return k

    def submit(self, request: Request) -> None:
        self.scheduler.add(request)

    def has_work(self) -> bool:
        return self.scheduler.has_work()

    def _row_extras(self, grants, B: int):
        """Per-row encoder frames / vision patches operands for a chunk call
        (fixed shapes; rows without extras get zeros, like the legacy dense
        prefill did)."""
        cfgm = self.model.cfg
        frames = patches = None
        if cfgm.encoder is not None:
            M = cfgm.encoder.cross_attn_memory
            fr = np.zeros((B, M, cfgm.d_model), np.float32)
            for i, (st, _) in enumerate(grants):
                v = self.extras.get((st.request.req_id, "frames"))
                if v is not None:
                    v = np.asarray(v)[0]
                    m = min(M, v.shape[0])
                    fr[i, :m] = v[:m]
            frames = self._put(fr)
        if cfgm.vision is not None:
            Np, Dp = cfgm.vision.n_patches, cfgm.vision.d_patch
            pa = np.zeros((B, Np, Dp), np.float32)
            for i, (st, _) in enumerate(grants):
                v = self.extras.get((st.request.req_id, "patches"))
                if v is not None:
                    pa[i] = np.asarray(v)[0]
            patches = self._put(pa)
        return frames, patches

    # ------------------------------------------------------------- step
    def step(self) -> List[TokenEvent]:
        """One token-budget iteration: admissions, the prefill chunk pack,
        then one decode sweep — at most ``token_budget`` tokens total.
        With ``profile_steps`` each iteration leaves one :class:`StepRecord`
        in the ``step_records`` ring buffer. The whole iteration is the
        profiler span ``engine.step`` (DESIGN.md §4)."""
        with span("engine.step", replica=self.replica_id, step=self.steps + 1) as sp:
            events = self._recorded_step()
            sp.set_metadata(prefill_rows=self._ran["prefill_rows"],
                            decode_rows=self._ran["decode_rows"])
        return events

    def _recorded_step(self) -> List[TokenEvent]:
        if self.injector is not None:
            self.injector.on_engine_step(self)
        if not self.cfg.profile_steps:
            return self._step()
        t0 = now()
        preempt0 = self.scheduler.n_preemptions
        cow0 = self.allocator.cow_copies
        prefill0, decode0 = self.prefill_tokens, self.decode_tokens
        drafted0, accepted0 = self.drafted_tokens, self.accepted_tokens
        events = self._step()
        alloc = self.allocator
        self.step_records.append(StepRecord(
            step=self.steps, t0=t0, t1=now(), budget=self.token_budget,
            tokens_packed=self.iter_token_counts[-1] if self.iter_token_counts else 0,
            prefill_tokens=self.prefill_tokens - prefill0,
            decode_tokens=self.decode_tokens - decode0,
            drafted_tokens=self.drafted_tokens - drafted0,
            accepted_tokens=self.accepted_tokens - accepted0,
            occupancy=len(self.scheduler.running),
            max_slots=self.cfg.max_slots,
            queue_depth=len(self.scheduler.waiting),
            kv_free_pages=alloc.free_pages,
            kv_total_pages=alloc.num_pages - 1,   # page 0 is the null page
            preemptions=self.scheduler.n_preemptions - preempt0,
            cow_pages=alloc.cow_copies - cow0, **self._ran))
        return events

    def _step(self) -> List[TokenEvent]:
        cfg = self.cfg
        tr = self.tracer
        events: List[TokenEvent] = []
        if cfg.host_overhead_s > 0:
            time.sleep(cfg.host_overhead_s)
        self.steps += 1
        iter_tokens = 0
        # what this iteration's device calls ran, for its StepRecord
        self._ran = dict(n_admitted=0, prefill_rows=0, prefill_work=(),
                         prefill_experts=(), decode_rows=0, decode_ctx=(),
                         decode_experts=())

        # deadline sweep (DESIGN.md §5): cancel requests past their absolute
        # cutoff before planning, so an expired request provably frees its
        # pages this iteration and never consumes budget again. The terminal
        # event carries error="deadline_exceeded" to the gateway/client.
        for slot, req in self.scheduler.expire_deadlines(now()):
            if slot is not None:
                self.page_table[slot] = 0
            self._drop_extras(req.req_id)
            t_exp = now()
            req.error = "deadline_exceeded"
            req.finished = True
            req.t3 = req.t3 or t_exp
            self.deadline_exceeded += 1
            if tr:
                tr.event(req.req_id, "deadline_exceeded", slot=slot)
            events.append(TokenEvent(req, -1, t_exp, True))

        with span("engine.plan"):
            plan = self.scheduler.plan_iteration(self.token_budget, self.chunk,
                                                 self.chunk_rows)
        self._ran["n_admitted"] = len(plan.admit)
        for st in plan.admit:
            r = st.request
            if r.t2 == 0.0:
                r.t2 = now()
            st.admitted_at = now()
            st.spec_k = self.spec_kmax if self.spec_on else 0
            self.prefix_cached_tokens += st.cached_tokens
            if tr:
                tr.end(r.req_id, "queue", cached_tokens=st.cached_tokens,
                       resumed=bool(r.generated))
            if st.feed_len + self.pos_offset >= cfg.max_seq:
                # prompt can never fit max_seq: fail fast with zero tokens
                # instead of spinning on page growth that cannot succeed.
                # The terminal event is what tells replica/gateway consumers
                # the request is over — without it they leak capacity.
                self._finish(st)
                events.append(TokenEvent(r, -1, now(), True))

        # ---- prefill chunk pack: grow pages, detach shared pages (COW),
        # then one fixed-shape call
        grants: List[Tuple[SlotState, int]] = []
        copies: List[Tuple[int, int]] = []
        for st, n in plan.prefill:
            if st.slot not in self.scheduler.running:      # preempted by an earlier grow
                continue
            if not self.scheduler.grow_for_tokens(st.slot, st.fed + n):
                continue                                   # pages exhausted: slot waits
            if self.prefix_cache is not None:
                # the chunk writes kv positions [fed, fed+n): any shared or
                # trie-registered page in that range must be detached first.
                # On failure the slot waits, but pairs for blocks already
                # detached stay queued in ``copies`` — their fresh pages need
                # the content before any later write or resume.
                lo = (self.pos_offset + st.fed) // cfg.page_size
                hi = (self.pos_offset + st.fed + n - 1) // cfg.page_size
                n_cow = len(copies)
                writable = self.scheduler.make_writable(st.slot, lo, hi, copies)
                if tr and len(copies) > n_cow:
                    tr.event(st.request.req_id, "cow",
                             n_pages=len(copies) - n_cow)
                if not writable:
                    continue                               # no page for the copy: wait
            grants.append((st, n))
        grants = [(st, n) for st, n in grants if st.slot in self.scheduler.running]
        if copies:
            self._apply_copies(copies)                     # before the chunk writes
        if grants:
            t_pack0 = now()
            self._ran.update(prefill_rows=len(grants),
                             prefill_work=tuple((st.fed, n) for st, n in grants))
            with span("engine.prefill.pack", rows=len(grants)):
                B, C = self.chunk_rows, self.chunk
                tokens = np.zeros((B, C), np.int32)
                starts = np.zeros((B,), np.int32)
                nvalid = np.zeros((B,), np.int32)
                slots = np.zeros((B,), np.int32)
                first = np.zeros((B,), bool)
                pt = np.zeros((B, cfg.max_pages_per_seq), np.int32)
                used = set()
                for i, (st, n) in enumerate(grants):
                    tokens[i, :n] = st.all_tokens[st.fed:st.fed + n]
                    starts[i] = st.fed
                    nvalid[i] = n
                    slots[i] = st.slot
                    first[i] = st.fed == 0
                    row = self.allocator.page_table_row(st.slot)
                    self.page_table[st.slot] = row
                    pt[i] = row
                    used.add(st.slot)
                # padding rows need distinct (unused) slots: their masked
                # cache writes must never collide with a live row's slot
                spare = [s for s in range(cfg.max_slots) if s not in used]
                for i in range(len(grants), B):
                    slots[i] = spare.pop()
                # encoder frames / vision patches only matter on a row's
                # FIRST chunk (cross-KV is persisted per slot; the patch
                # prefix KV is paged) — packs without first chunks skip the
                # prefix compute.
                frames, patches = (self._row_extras(grants, B) if first.any()
                                   else (None, None))
                ops = self._put((tokens, starts, nvalid, slots, first, pt))
                key = self._next_key()
            with span("engine.prefill.run"):
                nxt, experts, self.cache = self._prefill_jit(
                    self.params, self.cache, *ops, key, frames, patches)
            with span("engine.prefill.fetch"):
                nxt, experts = jax.device_get((nxt, experts))
            self._ran["prefill_experts"] = tuple(experts.tolist())
            t_emit = now()
            for i, (st, n) in enumerate(grants):
                st.fed += n
                iter_tokens += n
                self.prefill_tokens += n
                if tr:
                    tr.add(st.request.req_id, "prefill_chunk", t_pack0, t_emit,
                           n_tokens=n, fed=st.fed, rows=len(grants))
                self._register_prefix(st)
                if st.prefilling:
                    continue                               # more chunks to go
                if st.request.generated:                   # resumed mid-decode
                    st.last_token = st.all_tokens[-1]
                    continue
                tok = int(nxt[i])                          # first generated token
                st.last_token = tok
                st.all_tokens.append(tok)
                st.request.generated.append(tok)
                fin = self._check_finished(st, tok)
                events.append(TokenEvent(st.request, tok, t_emit, fin))
                if fin:
                    self._finish(st)

        # ---- decode sweep: the plan's decode-ready set plus slots whose feed
        # completed this iteration (same-step decode, budgeted as grant n+1)
        def _live(st):
            return self.scheduler.running.get(st.slot) is st
        decode_sts = [st for st in plan.decode if _live(st) and st.last_token >= 0]
        decode_sts += [st for st, _ in grants
                       if _live(st) and not st.prefilling and st.last_token >= 0]
        # prompt-lookup drafting for slots the plan granted draft tokens:
        # match the slot's recent suffix against its own prompt+output
        # history; cap so the draft tail never runs past max_seq.
        drafts: Dict[int, List[int]] = {}
        if self.spec_on and not self.degraded:   # brown-out disables drafting
            for st in decode_sts:
                g = min(plan.draft.get(st.slot, 0),
                        cfg.max_seq - 1 - self.pos_offset - st.fed)
                if g > 0:
                    d = self.draft_source.propose(st.all_tokens, g)
                    if d:
                        drafts[st.slot] = d
        dec_copies: List[Tuple[int, int]] = []
        for st in list(decode_sts):
            if st.slot not in self.scheduler.running:      # preempted by an earlier grow
                decode_sts.remove(st)
                continue
            k_i = len(drafts.get(st.slot, ()))
            grown = self.scheduler.grow_for_tokens(st.slot, st.fed + 1 + k_i)
            if not grown and k_i:
                drafts.pop(st.slot, None)                  # retry draft-free
                k_i = 0
                grown = self.scheduler.grow_for_decode(st.slot)
            if not grown:
                decode_sts.remove(st)                      # paused/unschedulable
                continue
            if self.prefix_cache is not None:
                lo = (self.pos_offset + st.fed) // cfg.page_size
                hi = (self.pos_offset + st.fed + k_i) // cfg.page_size
                n_cow = len(dec_copies)
                writable = self.scheduler.make_writable(st.slot, lo, hi,
                                                        dec_copies)
                if tr and len(dec_copies) > n_cow:
                    tr.event(st.request.req_id, "cow",
                             n_pages=len(dec_copies) - n_cow)
                if not writable:
                    decode_sts.remove(st)
                    continue
            self.page_table[st.slot] = self.allocator.page_table_row(st.slot)
        decode_sts = [st for st in decode_sts if st.slot in self.scheduler.running]
        live = {st.slot for st in decode_sts}
        drafts = {s: d for s, d in drafts.items() if s in live}
        if dec_copies:
            self._apply_copies(dec_copies)                 # before the decode writes
        if not decode_sts:
            self.iter_token_counts.append(iter_tokens)
            return events

        M = cfg.max_slots
        # inactive slots must point at the reserved null page 0: a stale row
        # would alias pages freed and reallocated to another sequence.
        for s in range(M):
            if s not in self.scheduler.running:
                self.page_table[s] = 0
        self._ran.update(decode_rows=len(decode_sts),
                         decode_ctx=tuple(st.fed for st in decode_sts))
        if drafts:
            iter_tokens = self._spec_sweep(decode_sts, drafts, events, iter_tokens)
            self.iter_token_counts.append(iter_tokens)
            return events
        t_dec0 = now()
        with span("engine.decode.pack", rows=len(decode_sts)):
            tokens = np.zeros((M, 1), np.int32)
            starts = np.zeros((M,), np.int32)
            nvalid = np.zeros((M,), np.int32)
            for st in decode_sts:
                tokens[st.slot, 0] = st.last_token
                starts[st.slot] = st.fed
                nvalid[st.slot] = 1
            ops = self._put((tokens, starts, nvalid, np.arange(M, dtype=np.int32),
                             np.zeros((M,), bool), self.page_table))
            key = self._next_key()
        with span("engine.decode.run"):
            nxt, experts, self.cache = self._decode_jit(
                self.params, self.cache, *ops, key, None, None)
        with span("engine.decode.fetch"):
            nxt, experts = jax.device_get((nxt, experts))
        self._ran["decode_experts"] = tuple(experts.tolist())
        t_emit = now()
        self.decode_tokens += len(decode_sts)
        iter_tokens += len(decode_sts)

        for st in decode_sts:
            st.fed += 1
            tok = int(nxt[st.slot])
            st.last_token = tok
            st.all_tokens.append(tok)
            st.request.generated.append(tok)
            if tr:
                # consecutive decode iterations coalesce into one span per
                # decode run (broken by preemption/spec/prefill spans)
                tr.add(st.request.req_id, "decode", t_dec0, t_emit,
                       merge=True, n_iters=1, tokens=1)
            fin = self._check_finished(st, tok)
            events.append(TokenEvent(st.request, tok, t_emit, fin))
            if fin:
                self._finish(st)
        self.iter_token_counts.append(iter_tokens)
        return events

    def _spec_sweep(self, decode_sts: List[SlotState], drafts: Dict[int, List[int]],
                    events: List[TokenEvent], iter_tokens: int) -> int:
        """One speculative decode iteration over all decode-ready slots
        (draft-free slots ride along as plain chunks of 1). Feeds
        [last_token, d_1 .. d_k] per row, commits the accepted prefix plus
        the bonus/corrected token, and rolls rejected KV back by truncating
        the slot's page tail (pages are append-only; positions at or past
        ``fed`` are never read and are overwritten by the next write)."""
        cfg = self.cfg
        tr = self.tracer
        t_sw0 = now()
        M = cfg.max_slots
        kcap = max(len(d) for d in drafts.values())
        C = next(w for w in self._spec_widths if w >= 1 + kcap)
        with span("engine.decode.pack", rows=len(decode_sts), width=C):
            tokens = np.zeros((M, C), np.int32)
            starts = np.zeros((M,), np.int32)
            nvalid = np.zeros((M,), np.int32)
            for st in decode_sts:
                d = drafts.get(st.slot, [])
                tokens[st.slot, 0] = st.last_token
                if d:
                    tokens[st.slot, 1:1 + len(d)] = d
                starts[st.slot] = st.fed
                nvalid[st.slot] = 1 + len(d)
            ops = self._put((tokens, starts, nvalid, np.arange(M, dtype=np.int32),
                             np.zeros((M,), bool), self.page_table))
            key = self._next_key()
        with span("engine.decode.run"):
            n_acc, out, experts, self.cache = self._spec_jit_for(C)(
                self.params, self.cache, *ops, key)
        with span("engine.decode.fetch"):
            n_acc, out, experts = jax.device_get((n_acc, out, experts))
        self._ran["decode_experts"] = tuple(experts.tolist())
        t_emit = now()
        self.spec_steps += 1
        for st in decode_sts:
            d = drafts.get(st.slot, [])
            k_i = len(d)
            na = int(n_acc[st.slot])
            committed = d[:na] + [int(out[st.slot])]
            iter_tokens += 1 + k_i         # all fed tokens count, rejected too
            self.drafted_tokens += k_i
            self.accepted_tokens += na
            if k_i:
                # adapt K additively: +1 on full acceptance, -1 only when
                # the whole draft was rejected, hold on partial acceptance.
                # Partial acceptance still amortizes the sweep (the verify
                # chunk is one batched call), so only a slot that keeps
                # drafting garbage shrinks toward k=1 — which also narrows
                # the sweep width via the compiled-width ladder.
                if na == k_i:
                    st.spec_k = min(self.spec_kmax, st.spec_k + 1)
                elif na == 0:
                    st.spec_k = max(1, st.spec_k - 1)
            fin = False
            n_committed = 0
            for tok in committed:
                st.fed += 1                # commits the KV of the PREVIOUS token
                st.last_token = tok
                st.all_tokens.append(tok)
                st.request.generated.append(tok)
                self.decode_tokens += 1
                n_committed += 1
                fin = self._check_finished(st, tok)
                events.append(TokenEvent(st.request, tok, t_emit, fin))
                if fin:
                    self._finish(st)       # frees every page, rollback included
                    break
            if tr:
                if k_i:
                    tr.add(st.request.req_id, "spec_verify", t_sw0, t_emit,
                           merge=True, n_iters=1, drafted=k_i, accepted=na,
                           tokens=n_committed)
                else:                      # draft-free row riding the sweep
                    tr.add(st.request.req_id, "decode", t_sw0, t_emit,
                           merge=True, n_iters=1, tokens=n_committed)
            if not fin and na < k_i:
                # rollback the rejected tail: keep pages through the next
                # decode write (position fed), drop pages grown only for
                # rejected drafts. Never touches registered prompt blocks —
                # they precede fed by construction.
                self.scheduler.shrink_to_tokens(st.slot,
                                                self.pos_offset + st.fed + 1)
        return iter_tokens

    def _check_finished(self, st: SlotState, tok: int) -> bool:
        r = st.request
        if len(r.generated) >= r.max_new_tokens:
            return True
        if self.cfg.eos_id >= 0 and tok == self.cfg.eos_id:
            return True
        if st.fed + 1 + self.pos_offset >= self.cfg.max_seq:
            return True                   # kv budget incl. any vision prefix
        return False

    def _drop_extras(self, req_id: str) -> None:
        self.extras.pop((req_id, "frames"), None)
        self.extras.pop((req_id, "patches"), None)

    def _finish(self, st: SlotState) -> None:
        st.request.finished = True
        st.request.t3 = now()
        self.scheduler.finish(st.slot)
        self._drop_extras(st.request.req_id)

    def stats(self) -> Dict[str, float]:
        """Cumulative engine counters (prefix cache, COW, eviction) for the
        observability sink and benchmark extras; sampled at TokenEvent
        granularity by replica/gateway consumers."""
        pc = self.prefix_cache
        return {
            "steps": float(self.steps),
            "prefill_tokens": float(self.prefill_tokens),
            "decode_tokens": float(self.decode_tokens),
            "prefix_cached_tokens": float(self.prefix_cached_tokens),
            "prefix_hit_pages": float(pc.hit_pages if pc else 0),
            "prefix_miss_pages": float(pc.miss_pages if pc else 0),
            "prefix_hit_rate": pc.hit_rate() if pc else 0.0,
            "prefix_nodes": float(len(pc) if pc else 0),
            "cow_copies": float(self.allocator.cow_copies),
            "evicted_pages": float(self.allocator.evicted_pages),
            "retired_pages": float(self.allocator.retired_pages),
            "preemptions": float(self.scheduler.n_preemptions),
            "deadline_exceeded": float(self.deadline_exceeded),
            "kv_utilization": self.allocator.utilization(),
            "spec_steps": float(self.spec_steps),
            "drafted_tokens": float(self.drafted_tokens),
            "accepted_tokens": float(self.accepted_tokens),
            "spec_acceptance_rate": (self.accepted_tokens / self.drafted_tokens
                                     if self.drafted_tokens else 0.0),
            "kv_bytes_per_token": float(self.kv_bytes_per_token),
        }

    def cancel(self, req_id: str) -> bool:
        """Drop a request (hedging loser / client disconnect). Frees its slot."""
        if self.tracer:
            self.tracer.discard(req_id)
        for i, r in enumerate(self.scheduler.waiting):
            if r.req_id == req_id:
                del self.scheduler.waiting[i]
                self._drop_extras(req_id)
                return True
        for slot, st in list(self.scheduler.running.items()):
            if st.request.req_id == req_id:
                self.scheduler.finish(slot)
                self.page_table[slot] = 0
                self._drop_extras(req_id)
                return True
        return False

    # ------------------------------------------------------------- sync api
    def generate(self, requests: List[Request], max_steps: int = 100_000) -> List[Request]:
        """Blocking helper for tests/benchmarks without the gateway stack."""
        for r in requests:
            r.t0 = r.t0 or now()
            r.t1 = r.t1 or now()
            self.submit(r)
        steps = 0
        while self.has_work() and steps < max_steps:
            for ev in self.step():
                if ev.request.t4 == 0.0:
                    ev.request.t4 = ev.t_emit
                    ev.request.t5 = now()
                if ev.finished:
                    ev.request.t6 = now()
            steps += 1
        return requests
