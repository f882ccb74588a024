"""Continuous-batching serving engine (vLLM-lite, pure JAX).

Fixed pool of `num_slots` decode slots sharing one stacked KV cache; every
slot advances at its OWN position.  When a sequence finishes (EOS or
max_new_tokens), its slot is recycled for the next queued request
mid-flight — no draining the batch.

Two prefill modes:

* ``"chunked"`` (default) — the fast path.  Prompts are ingested C tokens
  per dispatch through ``lm.prefill_chunk`` (ragged tails padded under a
  length mask), so a length-L prompt costs ceil(L/C) dispatches instead of
  L.  Slot state (positions, last token, done flags, output buffer) lives
  ON DEVICE and is advanced inside the jitted step with `jnp.where`
  masking; decode runs in jitted WINDOWS — a `lax.while_loop` of up to
  ``sync_every`` fused steps per dispatch that exits device-side the
  moment no slot is live, so a drained batch never pays for the rest of
  its window.  The Python loop syncs with the device only per window and
  on admit/retire boundaries.  Cache and state buffers are donated to the
  jitted programs, so XLA updates them in place instead of copying the KV
  cache every step.

* ``"decode"`` — the original prefill-as-decode path (one token, one
  dispatch, one host sync per engine step), kept as the measurable
  baseline for benchmarks/bench_serving.py and for equivalence tests.

Two KV-cache layouts (``kv_layout``):

* ``"contiguous"`` (default) — every slot owns a (max_len, ...) strip, so
  one short request reserves as much HBM as a long one.
* ``"paged"`` — per-position cache leaves are shared pools of
  ``page_size``-position pages addressed through per-slot page tables
  (repro.models.paging); a request reserves only
  ``ceil(min(prompt + max_new, max_len) / page_size)`` pages at admit and
  frees them at retire.  ``num_pages`` sizes the pool — below
  ``num_slots * ceil(max_len / page_size)`` it is an oversubscribed pool
  and admission waits (FIFO) for pages.  Paged reads gather the pool into
  the exact contiguous layout inside the jitted step, so outputs are
  bit-identical to the contiguous baseline (same masks, same reductions).

Two paged read paths (``kv_read``, paged layout only):

* ``"gather"`` (default) — materialize the contiguous view via
  ``gather_pages`` and run the stock attention reduction over it.
* ``"kernel"`` — the Pallas paged-attention kernel walks the page table
  IN-KERNEL for the stacked superblocks' GQA decode reads (no contiguous
  gather), bit-identical to the gather path (pinned in
  tests/test_paged_kernel.py).  Not every read is covered: MLA latents,
  the unstacked first-dense superblock, and every prefill read stay on
  gather — the engine warns LOUDLY about each fallback at construction
  (never silently), and ``stats["kv_read_execution_mode"]`` reports
  whether the kernel is compiled or CPU-interpreted.

Prefill/decode interleaving (``interleave``): 0 prefills every admitted
prompt to completion before decoding resumes (lowest time-to-first-token
for the admitted request, but running slots stall for the whole prompt);
k > 0 alternates one prefill chunk with up to k decode steps, bounding
how long running requests stall per admitted prompt at the cost of a
slower prefill.  The knob trades new-request TTFT against in-flight
inter-token latency; GREEDY outputs are unaffected without a codec
(rows are independent — with sampling the dispatch schedule changes the
RNG-key stream, so tokens differ), and the equivalence suite runs at
interleave=0.

Adaptive-R codecs (``codec="adaptive:c3sl:R=8,min_R=2|int8"``): the engine
pre-compiles one program set per R bucket and picks the bucket HOST-SIDE
at every dispatch, so the served R can change between windows/chunks with
zero recompiles.  ``stats["payload_wire_bytes"]`` accumulates the ACTUAL
cut-layer bytes shipped (scale/mask bytes included, sequence-grouped 3-D
prefill payloads accounted at their true row count) and ``r_served``
counts the served schedule per bucket; feed the controller between dispatches via
``observe_snr`` or pin it (``engine.codec.pin(R)``).

Per-direction link specs (``codec="c3sl:R=8|int8 >> bwd:c3sl:R=4"``, see
``repro.transport``) resolve to the FORWARD channel — no gradient crosses
the cut at inference — with the per-direction stats keys
(``wire_bytes_fwd`` == ``payload_wire_bytes``, ``wire_bytes_bwd`` == 0)
kept aligned with the train-side protocol.

Paged-pool utilization: when the page pool is starving the head of the
queue, decode windows exit device-side the moment ANY slot finishes
(``stats["eos_early_exits"]``) and the finished slot is retired FROM THAT
HOST SYNC — outputs captured at their actual emitted length, the whole
worst-case ``prompt + max_new`` page reservation freed — instead of the
reservation being held until the next boundary's retire sweep;
``pool_accounting()`` exposes the free/in-use split the tests pin.

Slot preemption (``preemption=True``, chunked mode): when the head of the
queue is blocked on pages (or on a free slot) and outranks running work
(``Request.priority``), the boundary EVICTS strictly-lower-priority slots
— least progress first — frees their reservations, and re-queues the
evicted requests right behind the preempting head.  A re-admitted request
re-prefills its prompt plus the tokens it had already emitted, so greedy
output is bit-identical to an uninterrupted run (pinned in
tests/test_preemption.py); the price is the re-prefill compute.  This
replaces the pure FIFO-blocking reservation policy under oversubscription:
free pages no longer sit idle behind a blocked high-priority head.

``tick()`` is the incremental form of ``run()`` — one boundary + one
prefill/decode iteration + one boundary — for callers that interleave
engine work with other activity (the ``repro.frontdoor`` server's asyncio
loop, open-loop arrival benchmarks).

Measurement, always in place and cheap when nobody looks:

* host spans in the profiler's trace (``jax.profiler.TraceAnnotation``,
  constant names, nested on the host thread): ``engine.tick`` (one
  ``tick()`` / one ``run()`` iteration), ``engine.boundary`` (a boundary
  that reads state), ``engine.prefill_chunk``, ``engine.decode_window``,
  and ``engine.device`` around every stretch that hands work to the
  device or waits on it (program calls with the read of their result,
  state reads and uploads);
* counters in ``stats``: ``admitted`` and ``queue_wait_s`` (seconds from
  ``submit``, or the re-queue after an eviction, to admission),
  ``prefill_tokens`` and ``prefill_rows`` (prompt tokens and rows each
  prefill chunk dispatched), and for a paged pool ``kv_written_page_s``,
  ``kv_reserved_page_s`` and ``kv_pool_page_s`` (page-seconds between
  boundary state reads, each interval weighted by the pages written,
  reserved and in the pool at its start);
* the C3-SL dispatch record (``record_dispatches()``, off by default):
  which request sat in which slot at which positions in every codec call,
  the grouping that decided each request's superposed cut features.

The C3-SL codec applies to each step's cut-layer features across the
active slots; on the chunked path the features are grouped PER POSITION
(`sequence_group_encode` layout), the same group shape as the decode
path's batch-wise groups.  Outputs match the decode path token-for-token
when slot occupancy matches too (full batch, equal-length prompts,
lockstep admission); empty slots or ragged prompts contribute different
padding features to the superposition on the two paths, so there outputs
agree only up to codec cross-talk — the price batch-wise compression
always puts on occupancy changes.  The same caveat applies to paged vs
contiguous under a codec: non-live rows read (masked-out but
codec-visible) stale pages instead of zeroed strips.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from collections import Counter, deque

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro import codecs as codecs_lib
from repro.configs.base import ModelConfig
from repro.models import lm as lm_lib
from repro.models import stack as stack_lib
from repro.models.paging import PagedLayout
from repro.serving import spec as spec_lib
from repro.serving.paging import PageAllocator
from repro.serving.spec import AdaptiveK, SpecConfig


def _codec_execution_mode(codec) -> str:
    """How the codec's transform ACTUALLY executes on this host ("none"
    without a codec).  Unwraps the Adaptive-R scheduler (``.current``) and
    wire-stage chains (``.transform``) down to the transform codec, whose
    ``execution_mode()`` distinguishes pallas-compiled / pallas-interpret /
    fft-fallback from the canonical ``spec()`` backend tag."""
    if codec is None:
        return "none"
    codec = getattr(codec, "current", codec)   # Adaptive-R wrapper
    codec = getattr(codec, "transform", codec)  # Chain of wire stages
    if hasattr(codec, "execution_mode"):
        return codec.execution_mode()
    return "unknown"


@dataclasses.dataclass
class Request:
    uid: int
    prompt: list            # token ids
    max_new_tokens: int = 16
    priority: int = 0       # higher preempts lower (engine preemption=True)
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    t_submit: float = 0.0   # set by submit()
    # entered the queue: submit(), or the re-queue after an eviction
    t_queued: float = 0.0
    # first token read back by the host (TTFT = t_first - t_submit): the
    # state read that first finds it, the moment it can be streamed
    t_first: float | None = None
    evictions: int = 0      # times this request was preempted mid-flight
    # speculative-decoding per-request stats (0 unless the engine ran with
    # spec_decode): tokens emitted through verify rounds, draft positions
    # the verify rejected, and rounds that truncated (accepted < k)
    accepted: int = 0
    rejected: int = 0
    rollbacks: int = 0


@dataclasses.dataclass
class _Slot:
    req: Request | None = None
    pos: int = 0             # next cache position to write (legacy mode)
    in_prompt: int = 0       # tokens of the prompt already ingested (legacy)
    ingested: int = 0        # tokens of the feed already ingested (chunked)
    # what this residency must ingest before decoding: the prompt, plus —
    # after an eviction — the tokens already emitted, so a re-admitted
    # request re-prefills its full generated-so-far context and greedy
    # decode continues exactly where it left off
    feed: list = dataclasses.field(default_factory=list)
    pages: list = dataclasses.field(default_factory=list)  # owned linear pages


class BatchedEngine:
    def __init__(self, params, cfg: ModelConfig, *, num_slots: int = 8,
                 max_len: int = 256, eos_id: int | None = None,
                 codec=None, codec_params=None, greedy: bool = True,
                 seed: int = 0, prefill_mode: str = "chunked",
                 chunk_size: int = 16, sync_every: int = 8,
                 kv_layout: str = "contiguous", page_size: int = 16,
                 num_pages: int | None = None, interleave: int = 0,
                 preemption: bool = False, kv_read: str = "gather",
                 spec_decode: SpecConfig | bool | None = None):
        # `codec` may be a ready codec object, a registry spec string
        # (e.g. "c3sl:R=4|int8"), or a per-direction link spec/SplitLink
        # ("c3sl:R=8|int8 >> bwd:c3sl:R=4").  Serving is forward-only —
        # no gradient crosses the cut — so the engine compresses with the
        # link's FORWARD channel and accounts the backward direction as 0
        # (stats["wire_bytes_bwd"]).  Specs are built against the decode cut
        # layer (D = d_model) and clamped to the slot count.  "none" means
        # codec off, matching the launch CLIs.
        from repro import transport
        self.link_spec = None
        # a link spec's "draft:" segment is the speculative feedback
        # channel's codec — captured here, consumed by the spec_decode
        # resolution below (its presence auto-enables speculation)
        draft_codec = draft_params = None
        if isinstance(codec, str):
            if codec == "none":
                codec = codec_params = None
            else:
                if transport.is_link_spec(codec):
                    link = transport.build_link(codec, D=cfg.d_model)
                    self.link_spec = link.spec()
                    if codec_params is not None:
                        # caller-supplied params follow the LINK's tree;
                        # the engine serves the forward channel only
                        codec_params = link.fwd_params(codec_params)
                    if link.draft is not None:
                        draft_codec = codecs_lib.clamp_R(link.draft.codec,
                                                         num_slots)
                    codec = link.fwd.codec
                codec = codecs_lib.clamp_R(
                    codecs_lib.build(codec, D=cfg.d_model)
                    if isinstance(codec, str) else codec, num_slots)
                if codec_params is None:
                    codec_params = codec.init(jax.random.PRNGKey(seed))
        elif isinstance(codec, transport.SplitLink):
            # link OBJECT: caller owns clamping/init (as for codec objects);
            # slice the forward channel's params out of the link tree
            self.link_spec = codec.spec()
            if codec.draft is not None:
                draft_codec = codec.draft.codec
                if codec_params is not None:
                    draft_params = codec.draft_params(codec_params)
            if codec_params is not None:
                codec_params = codec.fwd_params(codec_params)
            codec = codec.fwd.codec
        if prefill_mode not in ("chunked", "decode"):
            raise ValueError(f"unknown prefill_mode {prefill_mode!r} "
                             "(expected 'chunked' | 'decode')")
        if kv_layout not in ("contiguous", "paged"):
            raise ValueError(f"unknown kv_layout {kv_layout!r} "
                             "(expected 'contiguous' | 'paged')")
        if kv_read not in ("gather", "kernel"):
            raise ValueError(f"unknown kv_read {kv_read!r} "
                             "(expected 'gather' | 'kernel')")
        if kv_read == "kernel" and kv_layout != "paged":
            raise ValueError(
                "kv_read='kernel' requires kv_layout='paged': the Pallas "
                "paged-attention kernel is a page-table walk, and a "
                "contiguous cache has no table to walk")
        if preemption and prefill_mode != "chunked":
            raise ValueError("preemption requires prefill_mode='chunked' "
                             "(eviction re-queues the request for chunked "
                             "re-prefill of its generated context)")
        # ---- speculative decoding (repro.serving.spec) -------------------
        # spec_decode may be a SpecConfig, True (defaults), or None; a link
        # spec carrying a "draft:" segment auto-enables it with defaults.
        if spec_decode is True:
            spec_decode = SpecConfig()
        if spec_decode is None and draft_codec is not None:
            spec_decode = SpecConfig()
        self.spec_cfg: SpecConfig | None = spec_decode
        if spec_decode is not None:
            if prefill_mode != "chunked":
                raise ValueError(
                    "spec_decode requires prefill_mode='chunked': the verify "
                    "round is a k-position chunk dispatch")
            if not greedy:
                raise ValueError(
                    "spec_decode requires greedy=True: greedy verification "
                    "is what makes speculative output bit-identical to "
                    "vanilla decode (sampled verification would need the "
                    "rejection-sampling correction, which this engine does "
                    "not implement)")
            if cfg.sliding_window and spec_decode.ladder[-1] > cfg.sliding_window:
                raise ValueError(
                    f"spec_decode ladder max k={spec_decode.ladder[-1]} "
                    f"exceeds sliding_window={cfg.sliding_window}: a verify "
                    f"round must not write any ring slot twice; use a "
                    f"smaller ladder")
            if spec_decode.draft is not None:
                # SpecConfig's draft spec overrides a link's draft: segment
                draft_codec = codecs_lib.clamp_R(
                    codecs_lib.build(spec_decode.draft, D=cfg.d_model),
                    num_slots)
                draft_params = None
            if draft_codec is not None and draft_params is None:
                # a distinct key: the draft channel's superposition basis
                # must not collide with the forward channel's
                draft_params = draft_codec.init(jax.random.PRNGKey(seed + 1))
            self._k_ctl = AdaptiveK(spec_decode)
        else:
            draft_codec = draft_params = None
            self._k_ctl = None
        self.draft_codec = draft_codec
        self.draft_params = draft_params
        self.preemption = preemption
        self.codec = codec
        self.codec_params = codec_params
        self.params = params
        self.cfg = cfg
        self.num_slots = num_slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.greedy = greedy
        self.prefill_mode = prefill_mode
        self.kv_layout = kv_layout
        self.interleave = max(0, interleave)
        # each ring slot must be written at most once per chunk (SWA caches
        # are rings of length sliding_window)
        if cfg.sliding_window:
            chunk_size = min(chunk_size, cfg.sliding_window)
        self.chunk_size = max(1, min(chunk_size, max_len))
        self.sync_every = max(1, sync_every)
        self.rng = jax.random.PRNGKey(seed)

        self.paged: PagedLayout | None = None
        self.allocator: PageAllocator | None = None
        # which cache class actually backs full-length pages: MLA latents
        # always; attn only without a sliding window (SWA attn lives in the
        # statically-owned ring pools).  A pure-SWA or attention-free model
        # must not gate admission on a pool no leaf is allocated from.
        kinds = {k for layer in cfg.block_pattern for k in layer}
        self._linear_backed = ("mla" in kinds
                               or ("attn" in kinds and not cfg.sliding_window))
        self.kv_read = kv_read
        if kv_read == "kernel":
            if "attn" not in kinds:
                raise ValueError(
                    "kv_read='kernel' covers GQA ('attn') decode reads only, "
                    f"but block_pattern {cfg.block_pattern!r} has no attn "
                    "sublayer — every cache read would silently stay on the "
                    "gather path; use kv_read='gather'")
            fallbacks = []
            if "mla" in kinds:
                fallbacks.append("MLA latent reads")
            if cfg.first_dense_layers:
                fallbacks.append("the unstacked first-dense superblock")
            if prefill_mode == "chunked":
                fallbacks.append("chunked-prefill reads")
            if self.spec_cfg is not None:
                fallbacks.append("speculative verify/commit reads")
            if fallbacks:
                # loud by design: the silent-fallback bug class this tier
                # fixes.  The uncovered reads stay on gather_pages and are
                # still bit-identical — but the operator must know the
                # kernel is not serving them.
                warnings.warn(
                    "kv_read='kernel': " + ", ".join(fallbacks) + " stay on "
                    "the gather read path (kernel tier covers stacked GQA "
                    "decode only)", stacklevel=2)
        if kv_layout == "paged":
            len_swa = min(max_len, cfg.sliding_window) if cfg.sliding_window else 0
            pps = -(-max_len // page_size)
            pps_swa = -(-len_swa // page_size) if len_swa else 0
            if num_pages is None:
                num_pages = num_slots * pps      # fully provisioned pool
            # SWA rings are window-bounded already; each slot keeps its ring
            # pages for its lifetime (static table), only full-length pages
            # are allocated per request.
            self.paged = PagedLayout(page_size, max_len, num_pages,
                                     len_swa, num_slots * pps_swa)
            self.allocator = PageAllocator(num_pages)
            self._table = np.zeros((num_slots, pps), np.int32)
        self.cache = lm_lib.init_decode_cache(params, cfg, num_slots, max_len,
                                              paged=self.paged)
        if self.paged is not None:
            self.cache["pages"] = jnp.asarray(self._table)
            if self.paged.len_swa:
                self.cache["pages_swa"] = jnp.asarray(
                    np.arange(num_slots * self.paged.pages_per_slot_swa,
                              dtype=np.int32)
                    .reshape(num_slots, self.paged.pages_per_slot_swa))
        self.slots = [_Slot() for _ in range(num_slots)]
        self.queue: deque[Request] = deque()
        self.finished: list[Request] = []
        self._tokens_decoded = 0
        self._dirty = True            # force the first boundary to run
        # payload_wire_bytes accumulates the ACTUAL cut-layer bytes shipped
        # (per executed decode step / prefill chunk, scale+mask bytes
        # included) — under an Adaptive-R codec this follows the R schedule.
        # Per-direction accounting (repro.transport): serving is forward-
        # only, so wire_bytes_fwd == payload_wire_bytes and wire_bytes_bwd
        # stays 0 — the keys exist so engine stats line up with the train
        # logs' fwd/bwd protocol.  eos_early_exits counts decode windows cut
        # short because a slot finished while the page pool was starved
        # (the boundary then frees its pages immediately instead of holding
        # them for the rest of the window).
        # speculative counters (0 while spec_decode is off): wire_bytes_draft
        # is the draft channel's total — the server->client feedback payload
        # plus the client->server draft token ids, per verify round; fwd
        # bytes stay at the ONE _account_fwd_bytes entry (a verify round
        # ships NO forward payload — decode-time token ids are already
        # server-visible, so the server replays the bottom stack itself).
        # spec_accepted counts tokens emitted through verify rounds,
        # spec_rejected the draft positions the verify threw away, and
        # spec_rollbacks the rounds that truncated (accepted < k).
        self.stats = {"dispatches": 0, "decode_steps": 0, "prefill_chunks": 0,
                      "payload_wire_bytes": 0, "wire_bytes_fwd": 0,
                      "wire_bytes_bwd": 0, "wire_bytes_draft": 0,
                      "eos_early_exits": 0, "evictions": 0, "withdrawn": 0,
                      "spec_windows": 0, "spec_rounds": 0, "spec_accepted": 0,
                      "spec_rejected": 0, "spec_rollbacks": 0,
                      "admitted": 0, "queue_wait_s": 0.0,
                      "prefill_tokens": 0, "prefill_rows": 0,
                      "kv_written_page_s": 0.0, "kv_reserved_page_s": 0.0,
                      "kv_pool_page_s": 0.0}
        # the engine's clock: submit/first-token stamps, queue wait and
        # page-seconds (tests inject a fake one)
        self.clock = time.monotonic
        # (time, pages written, pages reserved) at the last boundary read
        self._page_mark: tuple | None = None
        # the C3-SL dispatch record (record_dispatches); None = off.  A
        # decode window's steps are completed at the next state read
        # (_pending_window), from the positions it returned.
        self.dispatch_record: list | None = None
        self._pending_window: tuple | None = None
        self._rec_pos = None
        # effective-execution-mode surfacing (the silent-fallback fix):
        # kv_read_execution_mode says how the paged read ACTUALLY runs on
        # this host ("gather" | "pallas-compiled" | "pallas-interpret") and
        # codec_execution_mode the same for the HRR codec ("none" without
        # one) — benchmarks must record these tags, and bench_roofline
        # refuses interpret-mode rows labeled as compiled kernels.
        if kv_read == "kernel":
            from repro.kernels import circconv
            self.stats["kv_read_execution_mode"] = circconv.execution_mode()
        else:
            self.stats["kv_read_execution_mode"] = "gather"
        self.stats["kv_read"] = kv_read
        self.stats["codec_execution_mode"] = _codec_execution_mode(self.codec)
        # the served R schedule under an adaptive codec, as {R: count} with
        # one count per EXECUTED decode step + one per prefill chunk, so
        # total() == decode_steps + prefill_chunks (not dispatches — a
        # window dispatch adds up to sync_every counts).  A Counter, not a
        # log: a long-lived engine serves millions of steps.  Kept out of
        # stats so stats stay scalar-valued.
        self.r_served: Counter[int] = Counter()
        # the served k schedule under spec_decode, as {k: verify rounds}
        # (k=1 windows are vanilla decode and counted by decode_steps only)
        self.k_served: Counter[int] = Counter()
        # streamed-token harvest: (uid, start, [tokens]) bursts collected
        # at host syncs the engine already performs (boundaries, early
        # retires) — drained by pop_stream_events() for the frontdoor's
        # TOKENS frames
        self.stream_events: list[tuple[int, int, list[int]]] = []
        self._stream_mark: dict[int, int] = {}
        self._adaptive = isinstance(self.codec, codecs_lib.AdaptiveC3SL)
        self.state = self._init_state()
        self._build_programs()
        # opt-in runtime invariant checks (repro.analysis.sanitize); None
        # in production — every check costs host syncs or extra dispatches
        self._sanitizer = None

    # ------------------------------------------------------------------
    # compiled programs
    # ------------------------------------------------------------------

    def _init_state(self):
        """Device-resident slot state: advanced inside the jitted step, read
        back only at admit/retire boundaries."""
        B = self.num_slots
        z = lambda dt: jnp.zeros((B,), dt)  # noqa: E731
        st = {
            "pos": z(jnp.int32),         # next cache position to write
            "last_tok": z(jnp.int32),    # decode input for the next step
            "active": z(bool),           # prompt fully ingested, generating
            "done": z(bool),             # finished, awaiting retire
            "out_len": z(jnp.int32),     # generated tokens so far
            "max_new": jnp.ones((B,), jnp.int32),
            "out_buf": jnp.zeros((B, self.max_len + 1), jnp.int32),
        }
        if self.spec_cfg is not None:
            # the draft head's feedback feature (the cut-layer feature at
            # each slot's last verified position, as the draft channel
            # delivered it) + the per-slot speculative counters the retire
            # path folds into Request.accepted/rejected/rollbacks
            st["draft_feat"] = jnp.zeros((B, self.cfg.d_model), jnp.float32)
            st["accepted"] = z(jnp.int32)
            st["rejected"] = z(jnp.int32)
            st["rollbacks"] = z(jnp.int32)
        return st

    def _build_programs(self):
        """Compile the engine's programs.  With an Adaptive-R codec this
        builds ONE program set per R bucket (each a separate compiled
        branch over that bucket's static codec + params); dispatch picks the
        bucket HOST-SIDE per window/chunk, so an R switch never retraces —
        pinned by the compile-counter test in tests/test_adaptive_codec.py."""
        paged = self.paged
        self._window_len = max(self.sync_every, self.interleave, 1)
        self._programs = codecs_lib.build_program_table(
            self.codec, self.codec_params, self._make_programs)
        # speculative verify/commit programs, one per (engine R bucket,
        # draft R bucket, k > 1) — jit is lazy, so unvisited combinations
        # cost nothing until first dispatch, and a HOST-side (R, draft-R, k)
        # switch lands on a pre-built entry: zero post-warmup recompiles,
        # same contract the vanilla bucket table pins.  k = 1 IS the
        # vanilla window program (speculation off) and has no entry here.
        self._spec_programs: dict = {}
        if self.spec_cfg is not None:
            for dkey, dc, dp in self._draft_buckets():
                for key, c, cp in self._codec_buckets():
                    for k in self.spec_cfg.ladder:
                        if k > 1:
                            self._spec_programs[(key, dkey, k)] = \
                                self._make_spec_program(c, cp, dc, dp, k)

        def reset_fn(cache, mask):
            """Layout-aware zeroing of the rows `mask` marks.  The cache
            layout is known by KEY: "stack" leaves carry (num_superblocks,
            B, ...), "first" leaves (B, ...), "memory" (encoder output) is
            never per-slot state — no shape guessing against dims that
            happen to equal num_slots (heads, cache length, ...).  Paged
            pools (attn/mla leaves) are left alone: reads past a slot's
            written positions are masked, so stale pages are invisible;
            only per-slot recurrent state needs zeroing."""
            def zero(subtree, axis):
                def z(leaf):
                    m = mask.reshape((1,) * axis + (-1,)
                                     + (1,) * (leaf.ndim - axis - 1))
                    return jnp.where(m, 0, leaf)
                return jax.tree.map(z, subtree)

            def zero_block(block, axis):
                return {key: (sub if stack_lib.is_pool(key.rsplit("_", 1)[-1],
                                                       paged)
                              else zero(sub, axis))
                        for key, sub in block.items()}

            new = dict(cache)
            new["stack"] = zero_block(cache["stack"], 1)
            if "first" in cache:
                new["first"] = zero_block(cache["first"], 0)
            return new

        self._reset = jax.jit(reset_fn, donate_argnums=(0,))

    def _make_programs(self, codec, codec_params) -> dict:
        """One codec's compiled program set: the fused decode window, the
        chunked-prefill dispatch, and the legacy prefill-as-decode step."""
        cfg = self.cfg
        greedy, eos_id, max_len = self.greedy, self.eos_id, self.max_len
        paged, kv_read = self.paged, self.kv_read

        def pick(logits, key):
            if greedy:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)

        def finish_check(state, nxt, out_len, pos):
            fin = (out_len >= state["max_new"]) | (pos >= max_len)
            if eos_id is not None:
                fin |= nxt == eos_id
            return fin

        def step_fn(params, cache, state, key):
            """One fused decode step: model forward + ALL slot bookkeeping.
            Cache/state writes are masked to `live` rows, so decoding can
            run while other slots are empty or mid-prefill (interleaving)
            without stomping their cache pages or recurrent state."""
            live = state["active"] & ~state["done"]
            logits, cache = lm_lib.decode_step(
                params, cache, state["last_tok"][:, None], state["pos"], cfg,
                codec=codec, codec_params=codec_params, paged=paged, live=live,
                kv_read=kv_read)
            nxt = jnp.where(live, pick(logits[:, -1], key), state["last_tok"])
            B, cap = state["out_buf"].shape
            col = jnp.where(live, jnp.minimum(state["out_len"], cap - 1), cap)
            out_buf = state["out_buf"].at[jnp.arange(B), col].set(nxt, mode="drop")
            out_len = state["out_len"] + live.astype(jnp.int32)
            pos = state["pos"] + live.astype(jnp.int32)
            done = state["done"] | (live & finish_check(state, nxt, out_len, pos))
            return cache, {**state, "pos": pos, "last_tok": nxt, "done": done,
                           "out_len": out_len, "out_buf": out_buf}

        def window_fn(params, cache, state, keys, n, stop_on_done):
            """Up to n (<= W) fused decode steps in ONE dispatch; exits
            device-side as soon as no slot is live, so a drained batch
            pays nothing for the rest of its window.  ``stop_on_done``
            (traced bool — no retrace when it flips) additionally exits the
            moment ANY slot finishes: the host sets it while the page pool
            is starving a queued request, so the finished slot's pages are
            freed at the next boundary instead of being held for the rest
            of the window (boundaries retire every done slot, so entry
            state always has done == False)."""
            def cond(carry):
                i, _, state = carry
                live = jnp.any(state["active"] & ~state["done"])
                eos_cut = stop_on_done & jnp.any(state["done"])
                return (i < n) & live & ~eos_cut

            def body(carry):
                i, cache, state = carry
                cache, state = step_fn(params, cache, state, keys[i])
                return i + 1, cache, state

            return jax.lax.while_loop(cond, body, (jnp.int32(0), cache, state))

        def prefill_fn(params, cache, state, tokens, valid, completes, key):
            """Ingest one prompt chunk for the rows `valid` marks; rows whose
            prompt ends in this chunk (`completes`) commit their first
            generated token from the last prompt position's logits."""
            logits, cache = lm_lib.prefill_chunk(
                params, cache, tokens, state["pos"], cfg,
                codec=codec, codec_params=codec_params, valid=valid,
                paged=paged)
            nxt = jnp.where(completes, pick(logits, key), state["last_tok"])
            B, cap = state["out_buf"].shape
            col = jnp.where(completes, jnp.minimum(state["out_len"], cap - 1), cap)
            out_buf = state["out_buf"].at[jnp.arange(B), col].set(nxt, mode="drop")
            out_len = state["out_len"] + completes.astype(jnp.int32)
            pos = state["pos"] + valid.sum(-1).astype(jnp.int32)
            done = state["done"] | (completes
                                    & finish_check(state, nxt, out_len, pos))
            return cache, {**state, "pos": pos, "last_tok": nxt, "done": done,
                           "active": state["active"] | completes,
                           "out_len": out_len, "out_buf": out_buf}

        def legacy_step_fn(params, cache, tokens, pos, key, live):
            logits, cache = lm_lib.decode_step(params, cache, tokens, pos, cfg,
                                               codec=codec,
                                               codec_params=codec_params,
                                               paged=paged, live=live,
                                               kv_read=kv_read)
            return pick(logits[:, -1], key), cache

        return {"window": jax.jit(window_fn, donate_argnums=(1, 2)),
                "prefill": jax.jit(prefill_fn, donate_argnums=(1, 2)),
                "legacy": jax.jit(legacy_step_fn)}

    # ------------------------------------------------------------------
    # speculative verify/commit programs (repro.serving.spec)
    # ------------------------------------------------------------------

    def _codec_buckets(self):
        """(program key, concrete codec, params) per engine R bucket —
        the same host-side keying ``_bucket()`` dispatches on."""
        if self._adaptive:
            return [(R, self.codec.buckets[R],
                     self.codec.params_for(self.codec_params, R))
                    for R in self.codec.ladder]
        return [(None, self.codec, self.codec_params)]

    def _draft_buckets(self):
        """Same, for the draft channel's codec (one (None, None, None)
        entry when feedback ships raw / the head needs none)."""
        dc = self.draft_codec
        if isinstance(dc, codecs_lib.AdaptiveC3SL):
            return [(R, dc.buckets[R], dc.params_for(self.draft_params, R))
                    for R in dc.ladder]
        return [(None, dc, self.draft_params)]

    def _make_spec_program(self, codec, codec_params, d_codec, d_params,
                           k: int):
        """One (codec bucket, draft bucket, k) speculative window program:
        a while_loop of verify/commit rounds, each advancing every live
        slot by 1..k tokens in-graph.

        Round shape (see repro.serving.spec for the invariants):

        1. round-trip each slot's feedback feature through the DRAFT codec
           and propose k-1 draft tokens (exactly what the client computes
           from the feedback payload — drafts are deterministic argmax, so
           simulating the client in-graph is bit-exact);
        2. VERIFY: k-position chunk forward over [last_tok, drafts] on the
           committed cache — per-position greedy targets; the cache this
           phase writes is DISCARDED (lm.verify_chunk never returns it);
        3. accept the longest matching prefix, group-lockstep under the
           batch-wise codec, capped at EOS/budget (spec.accept_lengths);
        4. COMMIT: re-ingest only the accepted tokens through the
           valid-masked chunk_forward write path — rollback is pure
           position truncation, rejected positions write nothing anywhere.

        Greedy verification makes the emitted stream bit-identical to the
        vanilla window program's (pinned in tests/test_spec_decode.py).
        """
        cfg = self.cfg
        eos_id, max_len = self.eos_id, self.max_len
        paged = self.paged
        group = getattr(codec, "R", 1) if codec is not None else 1
        head_mode = self.spec_cfg.draft_head
        needs_feedback = self.spec_cfg.needs_feedback

        def round_fn(params, cache, state):
            live = state["active"] & ~state["done"]
            B = live.shape[0]
            rows = jnp.arange(B)
            feat = state["draft_feat"]
            if needs_feedback and d_codec is not None:
                # the feedback payload crosses the draft channel: dead rows
                # contribute zero to its superposition (same hygiene as the
                # forward channel), live rows come back with the draft R's
                # cross-talk — which can only cost acceptance, not
                # correctness (the verify consumes raw tokens, never the
                # lossy feature)
                feat = jnp.where(live[:, None], feat, 0.0)
                feat = d_codec.decode(d_params,
                                      d_codec.encode(d_params, feat))
            drafts = spec_lib.propose_drafts(params, feat,
                                             state["last_tok"], k, head_mode)
            toks_v = jnp.concatenate([state["last_tok"][:, None], drafts],
                                     axis=1)
            valid_v = live[:, None] & jnp.ones((1, k), bool)
            logits, feat_seq = lm_lib.verify_chunk(
                params, cache, toks_v, state["pos"], cfg, codec=codec,
                codec_params=codec_params, valid=valid_v, paged=paged)
            g = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            e = spec_lib.accept_lengths(
                toks_v, g, live, group=group, eos_id=eos_id,
                rem_new=state["max_new"] - state["out_len"],
                rem_pos=max_len - state["pos"])
            out_buf = state["out_buf"]
            cap = out_buf.shape[1]
            for j in range(k):
                write = live & (j < e)
                col = jnp.where(write,
                                jnp.minimum(state["out_len"] + j, cap - 1),
                                cap)
                out_buf = out_buf.at[rows, col].set(g[:, j], mode="drop")
            e_live = jnp.where(live, e, 0)
            out_len = state["out_len"] + e_live
            pos = state["pos"] + e_live
            toks_c = jnp.concatenate([state["last_tok"][:, None],
                                      g[:, :k - 1]], axis=1)
            valid_c = live[:, None] & (jnp.arange(k)[None, :] < e[:, None])
            _, cache, _ = lm_lib.chunk_forward(
                params, cache, toks_c, state["pos"], cfg, codec=codec,
                codec_params=codec_params, valid=valid_c, paged=paged)
            last_emitted = g[rows, e - 1]
            last_tok = jnp.where(live, last_emitted, state["last_tok"])
            new_feat = jnp.where(live[:, None], feat_seq[rows, e - 1],
                                 state["draft_feat"])
            fin = (out_len >= state["max_new"]) | (pos >= max_len)
            if eos_id is not None:
                fin |= last_emitted == eos_id
            done = state["done"] | (live & fin)
            rej = jnp.where(live, k - e, 0)
            roll = (live & (e < k)).astype(jnp.int32)
            state = {**state, "pos": pos, "last_tok": last_tok, "done": done,
                     "out_len": out_len, "out_buf": out_buf,
                     "draft_feat": new_feat,
                     "accepted": state["accepted"] + e_live,
                     "rejected": state["rejected"] + rej,
                     "rollbacks": state["rollbacks"] + roll}
            return cache, state, (e_live.sum(), rej.sum(), roll.sum())

        def spec_window_fn(params, cache, state, n_rounds):
            def cond(carry):
                i, _, _, _, _, state = carry
                return ((i < n_rounds)
                        & jnp.any(state["active"] & ~state["done"]))

            def body(carry):
                i, acc, rej, rol, cache, state = carry
                cache, state, (a, r, ro) = round_fn(params, cache, state)
                return i + 1, acc + a, rej + r, rol + ro, cache, state

            z = jnp.int32(0)
            return jax.lax.while_loop(cond, body, (z, z, z, z, cache, state))

        return jax.jit(spec_window_fn, donate_argnums=(1, 2))

    # ------------------------------------------------------------------
    # codec-schedule dispatch + wire accounting
    # ------------------------------------------------------------------

    def _bucket(self):
        """Host-side program-set key for this dispatch: the adaptive codec's
        current R bucket, or None for a static (or absent) codec."""
        return codecs_lib.program_key(self.codec)

    def _current_codec(self):
        """The codec actually applied by the next dispatch (the bucket codec
        under Adaptive-R — never the wrapper, which must stay out of jit)."""
        if self.codec is None:
            return None
        return self.codec.current if self._adaptive else self.codec

    def observe_snr(self, snr_db, loss_slack=None):
        """Feed the Adaptive-R controller between dispatches (no-op for
        static codecs).  The serving path has no in-graph SNR probe, so the
        signal comes from outside — the training side's schedule, an SLA
        monitor, or a pinned R."""
        if self._adaptive:
            self.codec.observe(snr_db, loss_slack)

    def _account_fwd_bytes(self, nbytes: int):
        """The ONE place cut-layer bytes enter the stats: serving ships the
        forward direction only, so the legacy total and the per-direction
        fwd counter advance together by definition."""
        self.stats["payload_wire_bytes"] += nbytes
        self.stats["wire_bytes_fwd"] += nbytes

    def _step_wire_bytes(self) -> int:
        """Cut-layer bytes ONE decode step ships across the active batch."""
        c = self._current_codec()
        if c is None:
            return 0
        return codecs_lib.payload_wire_bytes(c, c.payload_shape(self.num_slots))

    def _chunk_wire_bytes(self) -> int:
        """Cut-layer bytes ONE prefill chunk ships (the sequence-grouped 3-D
        payload: chunk_size positions x num_slots/R groups x D)."""
        c = self._current_codec()
        if c is None:
            return 0
        shape = codecs_lib.chunk_payload_shape(c, self.num_slots,
                                               self.chunk_size)
        return codecs_lib.payload_wire_bytes(c, shape)

    def _draft_round_wire_bytes(self, k: int) -> int:
        """Draft-channel bytes ONE verify round ships, both ways: the
        server->client feedback payload (the cut-layer feature batch at
        the draft codec's R; zero for the "copy" head, raw f32 without a
        draft codec) plus the client->server draft token ids (k-1 per
        slot at the smallest dtype covering the vocab).  The FORWARD
        channel ships nothing during a verify round — the server already
        knows every decode-time token id and replays the bottom stack
        itself — which is exactly the amortization being bought."""
        tok_b = spec_lib.token_wire_bytes(self.cfg.vocab_size)
        ids = (k - 1) * self.num_slots * tok_b
        if not self.spec_cfg.needs_feedback:
            return ids
        dc = self.draft_codec
        if dc is None:
            return ids + self.num_slots * self.cfg.d_model * 4
        c = dc.current if isinstance(dc, codecs_lib.AdaptiveC3SL) else dc
        return ids + codecs_lib.payload_wire_bytes(
            c, c.payload_shape(self.num_slots))

    def wire_per_token(self) -> dict:
        """Wire bytes per GENERATED token across the serving channels —
        the speculative amortization metric (satellite: first-class
        per-token accounting, cross-checked in bench_serving).  Counts
        tokens of RETIRED requests (the denominator the engine can attest
        to); call after draining for exact totals."""
        n = self._tokens_decoded
        fwd = self.stats["wire_bytes_fwd"]
        draft = self.stats["wire_bytes_draft"]
        return {"generated_tokens": n,
                "wire_bytes_fwd": fwd,
                "wire_bytes_draft": draft,
                "wire_bytes_per_token": (fwd + draft) / max(n, 1)}

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    def submit(self, req: Request):
        if not req.prompt:
            raise ValueError(f"request {req.uid}: empty prompt")
        if len(req.prompt) >= self.max_len:
            # a full cache leaves no position for the decode loop to write:
            # the request would be admitted, prefilled, and cut off after the
            # single prefill-predicted token regardless of max_new_tokens
            raise ValueError(
                f"request {req.uid}: prompt length {len(req.prompt)} leaves "
                f"no decode positions in the engine's max_len={self.max_len} "
                f"cache (need prompt length <= max_len - 1); truncate the "
                f"prompt or build the engine with a larger max_len")
        if self.paged is not None and self._linear_backed:
            need = self.paged.pages_for(len(req.prompt) + req.max_new_tokens)
            if need > self.paged.num_pages:
                raise ValueError(
                    f"request {req.uid}: needs {need} cache pages but the "
                    f"pool only has {self.paged.num_pages}; shorten the "
                    f"request or build the engine with more num_pages")
        req.t_submit = req.t_queued = self.clock()
        self.queue.append(req)
        self._dirty = True            # a later run() must re-check admission

    def withdraw(self, uid: int):
        """Pull a queued or running request OUT of the engine (front-door
        disconnect handling): its slot/pages free immediately and the
        returned ``Request`` carries the tokens emitted so far, so a later
        ``submit`` of the same object re-prefills prompt + emitted tokens
        and greedy decode resumes bit-identically (the same machinery slot
        preemption uses).  Returns None when the uid is finished or
        unknown — finished results flow through the normal retire path."""
        for k, req in enumerate(self.queue):
            if req.uid == uid:
                del self.queue[k]
                self.stats["withdrawn"] += 1
                return req
        for i, slot in enumerate(self.slots):
            if slot.req is None or slot.req.uid != uid:
                continue
            req = slot.req
            self.stats["withdrawn"] += 1
            if self.prefill_mode == "chunked":
                with TraceAnnotation("engine.device"):
                    st = {k: np.array(v)
                          for k, v in jax.device_get(self.state).items()}
                if self.dispatch_record is not None:
                    self._close_window_record(st)
                n = int(st["out_len"][i])
                req.out = [int(t) for t in st["out_buf"][i, :n]]
                self._fold_spec_counters(i, req, st)
                st["active"][i] = st["done"][i] = False
                st["pos"][i] = st["last_tok"][i] = st["out_len"][i] = 0
                st["out_buf"][i, :] = 0
                with TraceAnnotation("engine.device"):
                    self.state = jax.device_put(st)
            self._stream_mark.pop(uid, None)
            req.evictions += 1
            req.done = False
            slot.req = None
            slot.feed = []
            slot.ingested = 0
            slot.pos = slot.in_prompt = 0
            self._free_slot_pages(i)
            self._dirty = True
            return req
        return None

    @property
    def active(self) -> int:
        return sum(s.req is not None for s in self.slots)

    @property
    def cache_bytes(self) -> int:
        """RESIDENT device bytes held by the KV cache (pools + tables +
        states) — the paged-vs-contiguous benchmark's memory metric.
        Excludes per-step transients (the paged read's gathered view of
        one layer's cache; see benchmarks/README.md)."""
        return sum(leaf.nbytes for leaf in jax.tree.leaves(self.cache))

    def attach_sanitizer(self, sanitizer) -> None:
        """Install per-tick invariant checks (an object with an
        ``on_tick(engine)`` method — see
        :class:`repro.analysis.sanitize.EngineSanitizer`).  A violated
        invariant raises out of tick()/run(); pass None to detach."""
        self._sanitizer = sanitizer

    def run(self, max_steps: int = 10_000) -> list[Request]:
        if self.prefill_mode == "decode":
            return self._run_legacy(max_steps)
        steps = 0
        while steps < max_steps:
            with TraceAnnotation("engine.tick"):
                self._boundary()
                if not (self.queue or self.active):
                    break
                steps += self._tick_body(max_steps - steps)
                if self._sanitizer is not None:
                    self._sanitizer.on_tick(self)
        self._boundary()
        return self.finished

    def tick(self) -> bool:
        """One admission/compute iteration — the incremental form of
        :meth:`run` for callers that interleave engine work with other
        activity (the front-door server's asyncio loop, open-loop arrival
        benchmarks).  Runs one boundary, then at most one prefill pass /
        decode window, then a second boundary so finished requests land in
        ``self.finished`` before control returns.  Returns False when the
        engine is idle (no queued or resident work) — the caller's cue to
        sleep instead of spinning."""
        with TraceAnnotation("engine.tick"):
            if self.prefill_mode == "decode":
                return bool(self.step())
            self._boundary()
            if not (self.queue or self.active):
                return False
            self._tick_body(self.sync_every)
            if self._sanitizer is not None:
                # before the trailing boundary: done-but-unretired slots
                # are still resident, so the dead/live cut probe sees the
                # mix
                self._sanitizer.on_tick(self)
            self._boundary()
            return True

    def _tick_body(self, budget: int) -> int:
        """One scheduler iteration (between boundaries): prefill according
        to the interleave policy, then decode.  Returns executed decode
        steps (0 for a pure-prefill iteration)."""
        if self._pending_prefill():
            self._prefill_one_chunk()
            if self.interleave != 0:
                # the host knows which slots have finished their prompt —
                # don't dispatch a window that would exit at step 0
                if any(s.req is not None and s.ingested >= len(s.feed)
                       for s in self.slots):
                    return self._decode_window(min(self.interleave, budget))
                return 0
            # PR2 behavior: admitted prompts prefill to completion
            while self._pending_prefill():
                self._prefill_one_chunk()
        return self._decode_window(min(self.sync_every, budget))

    # ------------------------------------------------------------------
    # fast path internals
    # ------------------------------------------------------------------

    def _spec_k(self) -> int:
        """The k the NEXT decode window speculates at (1 = vanilla).  A
        starved page pool drops to vanilla windows: they support the
        per-token EOS early exit that frees a finished slot's reservation
        mid-window, which matters more than amortization right then."""
        if self.spec_cfg is None or self._pool_starved():
            return 1
        return self._k_ctl.current_k

    def _spec_window(self, n: int, k: int) -> int:
        """Dispatch one speculative window: ceil(n/k) verify/commit rounds
        in ONE jitted while_loop; returns tokens emitted.  The host reads
        four scalars at the window end (rounds + the three counters) —
        the same per-window sync cadence as the vanilla path's
        ``executed = int(i)``, no per-round syncs."""
        n_rounds = -(-min(n, self._window_len) // k)
        bucket = self._bucket()
        dkey = codecs_lib.program_key(self.draft_codec)
        with TraceAnnotation("engine.device"):
            i, acc, rej, rol, self.cache, self.state = \
                self._spec_programs[(bucket, dkey, k)](
                    self.params, self.cache, self.state, jnp.int32(n_rounds))
            rounds, acc, rej, rol = (int(v) for v in
                                     jax.device_get((i, acc, rej, rol)))
        self.stats["dispatches"] += 1
        self.stats["decode_steps"] += acc
        self.stats["spec_windows"] += 1
        self.stats["spec_rounds"] += rounds
        self.stats["spec_accepted"] += acc
        self.stats["spec_rejected"] += rej
        self.stats["spec_rollbacks"] += rol
        # forward channel: ZERO bytes (server-side bottom-stack replay);
        # the draft channel carries the round's feedback + draft ids
        self.stats["wire_bytes_draft"] += rounds * \
            self._draft_round_wire_bytes(k)
        if bucket is not None:
            # keep r_served.total() == decode_steps + prefill_chunks: one
            # count per token served through the bucket's codec
            self.r_served[bucket] += acc
        self.k_served[k] += rounds
        if acc + rej:
            self._k_ctl.observe(acc / (acc + rej))
        if acc:
            self._dirty = True
        return acc

    def _decode_window(self, n: int) -> int:
        """Dispatch one jitted decode window of up to n steps; returns the
        number of steps the device actually executed before draining.
        Under spec_decode with current k > 1, the window is a speculative
        verify/commit loop instead (bit-identical greedy outputs)."""
        if n <= 0:
            return 0
        with TraceAnnotation("engine.decode_window"):
            k = self._spec_k()
            if k > 1:
                return self._spec_window(n, k)
            n = min(n, self._window_len)
            bucket = self._bucket()
            stop_on_done = self._pool_starved()
            if self.dispatch_record is not None:
                self._open_window_record()
            with TraceAnnotation("engine.device"):
                keys = jax.random.split(self.rng, self._window_len + 1)
                self.rng = keys[0]
                i, self.cache, self.state = self._programs[bucket]["window"](
                    self.params, self.cache, self.state, keys[1:],
                    jnp.int32(n), jnp.bool_(stop_on_done))
                executed = int(i)
            self.stats["dispatches"] += 1
            self.stats["decode_steps"] += executed
            self._account_fwd_bytes(executed * self._step_wire_bytes())
            if stop_on_done and executed < n:
                # a slot finished while the page pool was starving the
                # head of the queue.  Retire it from THIS host sync: its
                # outputs are captured at their actual emitted length and
                # its whole PageAllocator reservation is freed right here,
                # instead of the worst-case prompt+max_new pages staying
                # held until the next retire sweep.  The extra device
                # round-trip only happens on the already-rare starved-pool
                # early exit.
                with TraceAnnotation("engine.device"):
                    st = {k: np.array(v)
                          for k, v in jax.device_get(self.state).items()}
                if self.dispatch_record is not None:
                    self._close_window_record(st)
                if bool(np.any(st["active"] & ~st["done"])):
                    # the early exit actually cut short a window that still
                    # had live slots (vs the batch simply draining)
                    self.stats["eos_early_exits"] += 1
                self._collect_stream(st)
                if self._retire_done(st):
                    with TraceAnnotation("engine.device"):
                        self.state = jax.device_put(st)
            if bucket is not None:
                self.r_served[bucket] += executed
            if executed:
                self._dirty = True
            return executed

    def _pool_starved(self) -> bool:
        """True when the head-of-queue request is blocked on pages — the
        condition under which a mid-window EOS is worth exiting early for."""
        if self.paged is None or not self._linear_backed or not self.queue:
            return False
        head = self.queue[0]
        need = self.paged.pages_for(len(head.prompt) + head.max_new_tokens)
        return need > self.allocator.free_pages

    def pool_accounting(self) -> dict:
        """Page-pool occupancy snapshot: every page is either on the free
        list or owned by exactly one slot (the invariant the EOS-free test
        pins).  Zeros for the contiguous layout."""
        if self.paged is None:
            return {"free": 0, "in_use": 0, "total": 0}
        in_use = sum(len(s.pages) for s in self.slots)
        return {"free": self.allocator.free_pages, "in_use": in_use,
                "total": self.paged.num_pages}

    def _pending_prefill(self) -> bool:
        return any(s.req is not None and s.ingested < len(s.feed)
                   for s in self.slots)

    def _prefill_one_chunk(self):
        """One chunk of up to chunk_size prompt tokens for EVERY slot still
        prefilling, in a single dispatch (ragged tails padded under the
        length mask; rows not prefilling are fully masked)."""
        with TraceAnnotation("engine.prefill_chunk"):
            B, C = self.num_slots, self.chunk_size
            tokens = np.zeros((B, C), np.int32)
            valid = np.zeros((B, C), bool)
            completes = np.zeros((B,), bool)
            fed = []                       # (slot, tokens fed) per row
            for i, slot in enumerate(self.slots):
                if slot.req is None or slot.ingested >= len(slot.feed):
                    continue
                seg = slot.feed[slot.ingested:slot.ingested + C]
                tokens[i, :len(seg)] = seg
                valid[i, :len(seg)] = True
                slot.ingested += len(seg)
                completes[i] = slot.ingested >= len(slot.feed)
                fed.append((i, len(seg)))
            if not fed:
                return
            if self.dispatch_record is not None:
                self.dispatch_record.append((self.clock(), "P", [
                    (i, self.slots[i].req.uid, self.slots[i].ingested - n, n)
                    for i, n in fed]))
            bucket = self._bucket()
            with TraceAnnotation("engine.device"):
                self.rng, key = jax.random.split(self.rng)
                self.cache, self.state = self._programs[bucket]["prefill"](
                    self.params, self.cache, self.state, jnp.asarray(tokens),
                    jnp.asarray(valid), jnp.asarray(completes), key)
            self.stats["dispatches"] += 1
            self.stats["prefill_chunks"] += 1
            self.stats["prefill_tokens"] += sum(n for _, n in fed)
            self.stats["prefill_rows"] += B * C
            self._account_fwd_bytes(self._chunk_wire_bytes())
            if bucket is not None:
                self.r_served[bucket] += 1
            if completes.any():
                # the completing dispatch commits the row's first token; the
                # next boundary reads it back (and stamps t_first there)
                self._dirty = True

    def _retire_done(self, st, now: float | None = None) -> bool:
        """Retire every slot whose done flag is set in the host state copy
        ``st``: capture its outputs at their ACTUAL emitted length and free
        its whole page reservation.  Called from the boundary sweep and —
        so a starved pool gets the pages at the earliest host-visible
        instant — from the decode window's EOS early exit."""
        if now is None:
            now = self.clock()
        touched = False
        for i, slot in enumerate(self.slots):
            if slot.req is None:
                continue
            if slot.req.t_first is None and st["out_len"][i] > 0:
                slot.req.t_first = now
            if st["done"][i]:
                n = int(st["out_len"][i])
                slot.req.out = [int(t) for t in st["out_buf"][i, :n]]
                slot.req.done = True
                self.finished.append(slot.req)
                self._tokens_decoded += n
                self._fold_spec_counters(i, slot.req, st)
                self._stream_mark.pop(slot.req.uid, None)
                slot.req = None
                slot.feed = []
                self._free_slot_pages(i)
                st["active"][i] = st["done"][i] = False
                st["pos"][i] = st["last_tok"][i] = st["out_len"][i] = 0
                st["out_buf"][i, :] = 0
                touched = True
        return touched

    def _fold_spec_counters(self, i: int, req: Request, st):
        """Fold slot i's device-side speculative counters into the request
        (retire/evict/withdraw — totals survive preemption) and zero the
        slot's speculative state so the next resident starts clean."""
        if "accepted" not in st:
            return
        req.accepted += int(st["accepted"][i])
        req.rejected += int(st["rejected"][i])
        req.rollbacks += int(st["rollbacks"][i])
        st["accepted"][i] = st["rejected"][i] = st["rollbacks"][i] = 0
        st["draft_feat"][i, :] = 0

    def _collect_stream(self, st):
        """Harvest tokens emitted since each resident request's stream
        watermark into ``stream_events`` — piggybacks on host state copies
        the engine already makes (boundaries, early retires), so streaming
        costs no extra device round trips.  Drain with
        :meth:`pop_stream_events`."""
        for i, slot in enumerate(self.slots):
            if slot.req is None:
                continue
            uid = slot.req.uid
            n = int(st["out_len"][i])
            mark = self._stream_mark.get(uid, 0)
            if n > mark:
                self.stream_events.append(
                    (uid, mark, [int(t) for t in st["out_buf"][i, mark:n]]))
                self._stream_mark[uid] = n

    def pop_stream_events(self) -> list[tuple[int, int, list[int]]]:
        """Drain the (uid, start, tokens) bursts collected since the last
        call — the frontdoor turns each into one incremental TOKENS frame.
        ``start`` is the burst's absolute offset in the request's output:
        a receiver that missed a burst (dropped on a dying connection)
        detects the gap instead of silently splicing."""
        ev, self.stream_events = self.stream_events, []
        return ev

    def _evict(self, i: int, st):
        """Preempt slot ``i`` mid-flight: capture the tokens it has emitted
        so far, free its page reservation, and re-queue the request right
        behind the preempting head (position 1 — it resumes before other
        queued work, so a single high-priority arrival cannot starve it).
        On re-admission the request re-prefills prompt + emitted tokens
        (``slot.feed``), so greedy decode resumes bit-identically."""
        slot = self.slots[i]
        req = slot.req
        n = int(st["out_len"][i])
        req.out = [int(t) for t in st["out_buf"][i, :n]]
        req.evictions += 1
        req.t_queued = self.clock()
        self.stats["evictions"] += 1
        self._fold_spec_counters(i, req, st)
        slot.req = None
        slot.feed = []
        slot.ingested = 0
        self._free_slot_pages(i)
        st["active"][i] = st["done"][i] = False
        st["pos"][i] = st["last_tok"][i] = st["out_len"][i] = 0
        st["out_buf"][i, :] = 0
        self.queue.insert(1, req)

    def _preempt_for(self, st, head: Request) -> bool:
        """Try to make room for the blocked head-of-queue request by
        evicting strictly-lower-priority running slots (least progress
        first — the cheapest re-prefill).  Evicts nothing when even the
        full victim set cannot cover the head's page reservation.  Returns
        True when at least one eviction happened (admission should retry)."""
        if not self.preemption:
            return False
        victims = [i for i, s in enumerate(self.slots)
                   if s.req is not None and s.req.priority < head.priority]
        if not victims:
            return False
        victims.sort(key=lambda i: (self.slots[i].req.priority,
                                    int(st["pos"][i])))
        paged = self.paged is not None and self._linear_backed
        if paged:
            need = self.paged.pages_for(len(head.prompt)
                                        + head.max_new_tokens)
            if need > self.allocator.free_pages + sum(
                    len(self.slots[i].pages) for i in victims):
                return False       # hopeless: keep the victims running
        evicted = False
        for i in victims:
            have_slot = any(s.req is None for s in self.slots)
            have_pages = not paged or need <= self.allocator.free_pages
            if have_slot and have_pages:
                break
            self._evict(i, st)
            evicted = True
        return evicted

    def _boundary(self):
        """Admit/retire boundary: the ONLY place the fast path syncs with
        the device outside the per-window cadence.  In paged mode this is
        also where pages move: retire frees a slot's pages, admission
        waits (FIFO — no overtaking) until the head request's reservation
        fits the pool — unless ``preemption`` is on and the head outranks
        running slots, in which case low-priority slots are evicted (pages
        freed, request re-queued for re-prefill) to admit it.  Skipped
        entirely while the host knows nothing could have changed (no
        decode steps executed, no prompt completed, no new submissions
        since the last boundary) — interleaved prefill of a long prompt
        must not pay a blocking device_get per chunk."""
        if not self._dirty:
            return
        with TraceAnnotation("engine.boundary"):
            self._dirty = False
            with TraceAnnotation("engine.device"):
                st = {k: np.array(v)
                      for k, v in jax.device_get(self.state).items()}
            now = self.clock()
            if self.dispatch_record is not None:
                self._close_window_record(st)
            self._collect_stream(st)
            touched = self._retire_done(st, now)
            admitted: list[int] = []
            while self.queue:
                head = self.queue[0]
                i = next((j for j, s in enumerate(self.slots)
                          if s.req is None), None)
                if i is None or not self._alloc_slot_pages(i, head):
                    if not self._preempt_for(st, head):
                        break              # FIFO: wait for pages to free
                    touched = True
                    continue               # room was made — retry the head
                slot = self.slots[i]
                slot.req = self.queue.popleft()
                self._count_admission(slot.req, now)
                slot.ingested = 0
                # re-admitted (evicted) requests re-prefill their emitted
                # tokens too, and resume with out_len/out_buf pre-seeded so
                # the prefill-completing dispatch commits token k+1
                slot.feed = list(slot.req.prompt) + list(slot.req.out)
                k = len(slot.req.out)
                st["active"][i] = st["done"][i] = False
                st["pos"][i] = st["last_tok"][i] = 0
                st["out_len"][i] = k
                st["max_new"][i] = slot.req.max_new_tokens
                st["out_buf"][i, :] = 0
                if k:
                    st["out_buf"][i, :k] = slot.req.out
                # stream watermark: tokens in req.out were already
                # delivered (or re-prefilled after eviction) — only NEW
                # emissions stream
                self._stream_mark.setdefault(slot.req.uid, k)
                admitted.append(i)
                touched = True
            if self.paged is not None and self._linear_backed:
                self._integrate_pages(st, now)
            if touched or admitted:
                with TraceAnnotation("engine.device"):
                    if touched:
                        self.state = jax.device_put(st)
                    if admitted:
                        if self.paged is not None:
                            self.cache = {**self.cache,
                                          "pages": jnp.asarray(self._table)}
                        mask = np.zeros((self.num_slots,), bool)
                        mask[admitted] = True
                        self.cache = self._reset(self.cache,
                                                 jnp.asarray(mask))

    def _count_admission(self, req: Request, now: float):
        self.stats["admitted"] += 1
        self.stats["queue_wait_s"] += now - req.t_queued

    def _integrate_pages(self, st, now: float):
        """Add the page-seconds since the previous boundary read, weighted
        by the pages at that read, and note the pages at this one (``st``
        as the boundary leaves it): pages holding a written position,
        pages reserved by resident slots, and the whole pool.  Admission
        waits on the reserved pages.  The written ones read low by the
        prompts prefilled in an interval: a slot admitted at its start
        counts there with none."""
        if self._page_mark is not None:
            t, written, reserved = self._page_mark
            dt = now - t
            self.stats["kv_written_page_s"] += dt * written
            self.stats["kv_reserved_page_s"] += dt * reserved
            self.stats["kv_pool_page_s"] += dt * self.paged.num_pages
        ps = self.paged.page_size
        written = reserved = 0
        for i, s in enumerate(self.slots):
            if s.req is not None:
                written += -(-int(st["pos"][i]) // ps)
                reserved += len(s.pages)
        self._page_mark = (now, written, reserved)

    # ------------------------------------------------------------------
    # the C3-SL dispatch record
    # ------------------------------------------------------------------

    def record_dispatches(self, on: bool = True) -> list | None:
        """Turn the dispatch record on (returns the fresh list it fills)
        or off (returns None).

        One entry per codec call of the chunked path, in dispatch order:
        a prefill chunk is ``(t, "P", [(slot, uid, start, n), ...])`` —
        the ``n`` prompt positions from ``start`` that request ``uid`` fed
        through ``slot`` — and each decode step of a window is ``(t, "D",
        [(slot, uid, pos), ...])`` for the slots live in it; ``t`` is the
        dispatch time on :attr:`clock`.  Slots ``R*g .. R*g+R-1`` form
        C3-SL group ``g``, so an entry says which requests were
        superposed together.  Prefill rows come from the host-side chunk
        the engine packs; a window's steps are completed at the next
        state read the engine makes anyway (a boundary, an early exit or
        a withdraw), from the positions it returns, so recording adds no
        host sync past the one read of positions made here.  Speculative
        windows and the legacy ``prefill_mode="decode"`` path are not
        recorded.  The list grows by one entry per step: an operator
        turns it on for an audit, not for the life of a server."""
        self._pending_window = None
        if not on:
            self.dispatch_record = None
            return None
        with TraceAnnotation("engine.device"):
            self._rec_pos = np.array(jax.device_get(self.state["pos"]))
        self.dispatch_record = []
        return self.dispatch_record

    def _open_window_record(self):
        """Note which requests the window about to be dispatched decodes,
        and from which position (the position the last state read found,
        or the end of the feed for a prefill completed since)."""
        starts = [(i, s.req.uid, max(int(self._rec_pos[i]), len(s.feed)))
                  for i, s in enumerate(self.slots)
                  if s.req is not None and s.ingested >= len(s.feed)]
        self._pending_window = (len(self.dispatch_record), self.clock(),
                                starts)

    def _close_window_record(self, st):
        """Complete the pending window's steps from the host state copy
        ``st`` (before the caller retires or admits anything): each slot
        decoded from its noted start up to its returned position.  The
        caller's later edits of ``st["pos"]`` (retire, admit) are what the
        next window's starts read, so it is kept by reference."""
        pend, self._pending_window = self._pending_window, None
        self._rec_pos = st["pos"]
        if pend is None:
            return
        idx, t, starts = pend
        steps: list[list] = []
        for i, uid, start in starts:
            for k in range(int(st["pos"][i]) - start):
                if k == len(steps):
                    steps.append([])
                steps[k].append((i, uid, start + k))
        self.dispatch_record[idx:idx] = [(t, "D", rows) for rows in steps]

    # ------------------------------------------------------------------
    # page bookkeeping (host side; no-ops for the contiguous layout)
    # ------------------------------------------------------------------

    def _alloc_slot_pages(self, i: int, req: Request) -> bool:
        if self.paged is None or not self._linear_backed:
            return True           # no leaf draws from the full-length pool
        need = self.paged.pages_for(len(req.prompt) + req.max_new_tokens)
        got = self.allocator.alloc(need)
        if got is None:
            return False
        self.slots[i].pages = got
        self._table[i, :] = 0
        self._table[i, :len(got)] = got
        return True

    def _free_slot_pages(self, i: int):
        if self.paged is None:
            return
        self.allocator.free(self.slots[i].pages)
        self.slots[i].pages = []
        self._table[i, :] = 0

    # ------------------------------------------------------------------
    # legacy path (prefill-as-decode, one host sync per token) — kept as
    # the benchmark baseline and for equivalence tests
    # ------------------------------------------------------------------

    def _reset_slot_cache(self, idx: int):
        """Zero one slot's cache rows so a recycled slot starts clean."""
        mask = np.zeros((self.num_slots,), bool)
        mask[idx] = True
        self.cache = self._reset(self.cache, jnp.asarray(mask))

    def _admit(self):
        for i, slot in enumerate(self.slots):
            if slot.req is None and self.queue:
                if not self._alloc_slot_pages(i, self.queue[0]):
                    break
                slot.req = self.queue.popleft()
                self._count_admission(slot.req, self.clock())
                slot.pos = 0
                slot.in_prompt = 0
                slot.feed = list(slot.req.prompt) + list(slot.req.out)
                if self.paged is not None:
                    self.cache = {**self.cache,
                                  "pages": jnp.asarray(self._table)}
                self._reset_slot_cache(i)

    def step(self):
        """One legacy engine step: every active slot ingests/decodes one
        token ("prefill as decode"), then a host sync."""
        self._admit()
        if self.active == 0:
            return False
        tokens = np.zeros((self.num_slots, 1), np.int32)
        pos = np.zeros((self.num_slots,), np.int32)
        occupied = np.zeros((self.num_slots,), bool)
        for i, s in enumerate(self.slots):
            if s.req is None:
                continue
            occupied[i] = True
            if s.in_prompt < len(s.feed):
                tokens[i, 0] = s.feed[s.in_prompt]
            else:
                tokens[i, 0] = s.req.out[-1]
            pos[i] = s.pos
        self.rng, key = jax.random.split(self.rng)
        # contiguous: unmasked writes (empty rows scribble on their own
        # zeroed strip, exactly the PR2 baseline the equivalence tests pin);
        # paged: empty rows hold no pages, so their writes MUST be masked
        live = jnp.asarray(occupied) if self.paged is not None else None
        bucket = self._bucket()
        with TraceAnnotation("engine.device"):
            nxt, self.cache = self._programs[bucket]["legacy"](
                self.params, self.cache, jnp.asarray(tokens),
                jnp.asarray(pos), key, live)
            nxt = np.asarray(nxt)
        self.stats["dispatches"] += 1
        # one fused batch step per dispatch — same unit as the chunked
        # path's decode_steps (NOT per-slot generated tokens)
        self.stats["decode_steps"] += 1
        self._account_fwd_bytes(self._step_wire_bytes())
        if bucket is not None:
            self.r_served[bucket] += 1
        for i, s in enumerate(self.slots):
            if s.req is None:
                continue
            s.pos += 1
            fed_prompt = s.in_prompt < len(s.feed)
            if fed_prompt:
                s.in_prompt += 1
            # the prediction counts once the WHOLE prompt is in: the last
            # prompt token's logits give the first generated token
            if not fed_prompt or s.in_prompt == len(s.feed):
                tok = int(nxt[i])
                s.req.out.append(tok)
                if s.req.t_first is None:
                    s.req.t_first = self.clock()
                self._tokens_decoded += 1
                if (self.eos_id is not None and tok == self.eos_id) \
                        or len(s.req.out) >= s.req.max_new_tokens \
                        or s.pos >= self.max_len:
                    s.req.done = True
            if s.req.done:
                self.finished.append(s.req)
                s.req = None
                self._free_slot_pages(i)
        return True

    def _run_legacy(self, max_steps: int) -> list[Request]:
        steps = 0
        while (self.queue or self.active) and steps < max_steps:
            with TraceAnnotation("engine.tick"):
                self.step()
            steps += 1
        return self.finished
