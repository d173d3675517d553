"""Continuous-batching inference engine (reference-era analog: vLLM's
`LLMEngine.step()` loop — Orca-style iteration-level scheduling over a
PagedAttention cache, here driving `models/gpt.py`'s paged decode path).

One `step()` is one model iteration:

    1. `Scheduler.schedule()` re-forms the working set — admits queued
       prompts the moment the KV budget (free + reclaimable cached blocks)
       covers them, preempts on exhaustion (finished sequences were already
       retired and their blocks freed at the END of the previous step).
       Admission allocates by PREFIX-CACHE lookup first: a prompt whose
       leading full blocks are already resident skips straight to the first
       cold token.
    2. Prefill advances in CHUNKS under a per-step token budget (one jitted
       program per (chunk, width) bucket): each step lands at most
       `prefill_chunk_tokens` of one prompt, so a long prompt never stalls
       the decode streams for a monolithic prefill. The final chunk emits
       the first token — that's TTFT, decoupled from everything else in
       flight.
    3. All fully-prefilled sequences advance one token through ONE jitted
       `decode_step_paged` call — batch padded to a power-of-two lane
       bucket and block-table width bucket, so XLA compiles a bounded set
       of programs no matter how the working set churns.
    4. New tokens stream to per-request output queues; sequences hitting
       their stop condition retire immediately, returning their blocks for
       the NEXT step's admissions.

Steps chain on the device. The programs end in the sampler and hand back
ids; each also leaves its ids in a device buffer of last ids, one entry a
lane slot, from which the next decode program gathers its input. So the
engine dispatches step n+1 BEFORE it reads step n's ids, and reads them
one step behind (`_collect`), for the streams, the finish test and the
books: the blocking read overlaps a running program and the device goes
from program to program without the host in between. Step n+1 is planned
from counts (`Sequence.unread`); a finish by `max_new_tokens` is known at
dispatch (`Scheduler.retire`), an `eos` is not: such a lane rides one step
too many and that sample is dropped. Where token VALUES are needed the step
in flight is read first and the engine runs drained: a verify step, the
preemption of a lane whose newest id is unread (`NeedsValues`), a KV
export, `shutdown`. `step()` is one iteration of that one loop.

The engine owns a dedicated driver thread (all JAX compute on one thread);
`submit()`/`stream()` are called from any thread — replica actor method
threads under Serve (`LLMDeployment` runs with max_concurrency > 1 so a
blocked `generate` never gates another request's `submit`).
"""

from __future__ import annotations

import dataclasses
import gc
import queue
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

from collections import deque

from ...util import flight, metrics as _metrics
from ...util.metrics import quantile as _quantile
from .kv_manager import KVBlockManager
from .scheduler import (
    FINISHED, NeedsValues, Scheduler, Sequence, SchedulerOutput, _next_pow2,
)

_FINISH = object()  # stream sentinel

# A step is slow when the host's part of its span (the span less `fetch_ns`,
# the wait for the device) passes this many times the running mean of that
# part, once this many steps have run; the mean then follows the last steps,
# each moving it by that share of its distance (`_book_step`).
_SLOW_FACTOR = 4
_SLOW_AFTER = 64

# The engine's books (`InferenceEngine.stats`): integer totals it adds to
# on every step that writes a record, whether or not the recorder is on.
_STEP_BOOKS = (
    "steps", "steps_chunk", "steps_decode", "steps_decode_only", "steps_slow",
    "step_ns", "step_chunk_ns", "step_decode_only_ns", "host_ns", "slow_ns",
    "loop_ns", "waited_ns", *flight.SERVE_STEP_PHASES,
    # The span less its six phases: what a step spends between them, which
    # no phase books.
    "between_ns",
    "decode_lanes", "decode_bucket_lanes", "decode_lanes_beside_chunk",
    "prefill_tokens", "prefill_tokens_padded",
    # A model with state a sequence (`KVLayout.state`), else 0: tokens its
    # scans ran over as the programs were shaped and those of them the mask
    # held still (a chunk's padding, padding lanes); state bytes the decode
    # programs' real lanes read and wrote; slot-nanoseconds held, and slots x
    # nanoseconds they could have been (`_tick_slots`).
    "ssm_tokens_scanned", "ssm_tokens_masked", "ssm_state_bytes",
    "state_slot_held_ns", "state_slot_cap_ns",
    # The pool over TIME (`_tick_slots`): block-nanoseconds live sequences
    # held, of them those in window groups' tables, and the pool's blocks x
    # the nanoseconds (held over it: the step record's `kv_util` over time).
    "kv_block_held_ns", "kv_window_block_ns", "kv_block_cap_ns",
    # What a decode program hands back beside its ids, which rides the step
    # record as a float (`_decode_facts`): the steps that read it, and its
    # sum in thousandths (an expert model's `experts_touched`, a looped
    # model's `exit_step_mean`) or millionths (`expert_load_max`).
    "moe_steps_read", "moe_experts_touched_milli", "moe_load_max_ppm",
    "ut_steps_read", "ut_exit_step_milli",
    # Query heads x keys the dispatched programs' attention covered, summed
    # over the layers, each under its own window and head count
    # (`ops.paged_attention.paged_attn_cover`), and of them the window layers'.
    "attn_head_keys", "attn_head_keys_window", "blocks_run", "blocks_ssm", "blocks_moe", "blocks_attn", "shared_kv_read_bytes", "kv_read_bytes", "cross_decoder_tokens", "decode_width_fixed",     # `_book_shared`, `_decode_tables`
)


class _GcPauses:
    """The process's garbage collections, timed by a `gc.callbacks` hook: a
    collection holds the interpreter lock, so it stops the step thread
    whichever thread's allocation set it off, and lands in whichever phase
    it interrupts. One hook a process, put in by the first engine and
    taken out when the last one shuts down."""

    def __init__(self):
        self.ns = 0
        self.collections = 0
        self._t0 = 0
        self._engines = 0
        self._lock = threading.Lock()

    def _hook(self, phase: str, info: dict):
        if phase == "start":
            self._t0 = time.monotonic_ns()
        elif self._t0:      # collections do not nest: one writer at a time
            self.ns += time.monotonic_ns() - self._t0
            self.collections += 1
            self._t0 = 0

    def acquire(self):
        with self._lock:
            self._engines += 1
            if self._engines == 1:
                gc.callbacks.append(self._hook)

    def release(self):
        with self._lock:
            self._engines -= 1
            if self._engines == 0:
                gc.callbacks.remove(self._hook)


_GC = _GcPauses()


class _Delivery:
    """Books of a token's path from `_emit` to whoever iterates its
    `RequestOutput`: [tokens taken, ns from emission to the consumer holding
    the token (the queue hop and the interpreter lock), ns from handing a
    token over to being asked for the next (the consumer shipping it on),
    tokens that were already queued when asked for]. Each output sums its
    own in its one consumer thread; the sums of streams that have ended are
    folded under a lock, and a reading adds the open streams' as they stand,
    so nothing is lost among consumers that run at once."""

    def __init__(self):
        self._lock = threading.Lock()
        self._ended = [0, 0, 0, 0]
        self._open: set = set()

    def opened(self, out: "RequestOutput"):
        with self._lock:
            self._open.add(out)

    def ended(self, out: "RequestOutput"):
        with self._lock:
            self._open.discard(out)
            self._ended = [a + b for a, b in zip(self._ended, out.taken)]
            out.taken[:] = [0, 0, 0, 0]     # iterated again, it starts anew

    def read(self) -> List[int]:
        with self._lock:
            return [sum(col) for col in zip(
                self._ended, *(out.taken for out in self._open))]

# Jitted paged programs are process-wide singletons: every engine (and every
# replica in local-mode tests) shares one XLA program cache, keyed by the
# (cfg, shape-bucket) signature jax.jit already tracks. Re-wrapping per
# engine would recompile identical programs per instance.
_JITS = None


def init_sampler(max_num_seqs: int, seed: int, temperature: float):
    """(last, sampling) as the sampled programs take them. `last` is carried
    and donated like the pool: `ids` [max_num_seqs + 1] int32, the newest
    sampled id of every lane slot (the spare entry takes what padding lanes
    and chunks that end no prompt sample), and `draws`, the programs
    dispatched so far, folded into the key. `sampling` never changes: (the
    key of `seed`, the temperature as a float32 scalar). The key is an
    `unsafe_rbg` one: its draws are one `rng_bit_generator` operation,
    where threefry's unrolled rounds, lowered anew into every one of a
    replica's programs, cost a third of a second of set-up a program
    (PERF.md §6, PR 33)."""
    import jax
    import jax.numpy as jnp

    last = {"ids": jnp.zeros((max_num_seqs + 1,), jnp.int32),
            "draws": jnp.zeros((), jnp.int32)}
    return last, (jax.random.key(seed, impl="unsafe_rbg"),
                  jnp.asarray(temperature, jnp.float32))


def _paged_jits():
    """What the engine dispatches: `models/gpt.py`'s three paged entry
    points, each ending in the sampler (`models.gpt.sample_ids`), so that a
    step hands back ids and not logits, and the ids stay on the device for
    the next step. One program a shape bucket, chained or not: whether a
    lane's input id comes from the device's buffer or from the host is
    DATA (`known`), temperature and the draw counter are traced scalars.
    The names keep `decode_step_paged` / `prefill_paged` /
    `verify_step_paged`: the benchmark finds the programs in the device
    trace by them. Defined here, inside, so that a `_JITS` reset to None
    (tests tracing under another `_ATTN_TILE_KEYS`) gets functions, and
    with them traces, of its own."""
    global _JITS
    if _JITS is not None:
        return _JITS
    import jax
    import jax.numpy as jnp

    from ...models import gpt

    def draw(logits, slot, last, sampling):
        key, temperature = sampling
        ids = gpt.sample_ids(
            logits, temperature, jax.random.fold_in(key, last["draws"]))
        return ids, {"ids": last["ids"].at[slot].set(ids),
                     "draws": last["draws"] + 1}

    def prefill_paged_sampled(params, tokens, meta, block_table, kv, last,
                              sampling, cfg):
        """`prefill_paged`, then the chunk's next id [1] into `last` at
        `slot`: meta = (real_len, pos_offset, slot) int32; a chunk that
        does not end its prompt names the spare slot. A model with state
        brings a fourth entry, the sequence's state slot."""
        real_len, pos_offset, slot, *state_slot = meta
        logits, kv = gpt.prefill_paged(
            params, tokens, real_len, pos_offset, block_table, kv, cfg, *state_slot)
        ids, last = draw(logits[None], slot[None], last, sampling)
        return (ids,), kv, last

    def decode_step_paged_sampled(params, lanes, block_tables, kv, last,
                                  sampling, cfg):
        """`decode_step_paged` over ids the device holds: lanes [4, B]
        int32 = (slot, position, host id, known). A lane's input id is
        `last["ids"][slot]` unless the host knows it (`known`); its sample
        goes back to the same slot. Returns ((ids [B], *facts), kv, last),
        `facts` what `decode_step_paged` hands back beside its logits. A
        model with state brings a fifth row, the lanes' state slots."""
        slot, positions, host_ids, known, *state_slots = lanes
        token = jnp.where(known > 0, host_ids, last["ids"][slot])
        out, kv = gpt.decode_step_paged(
            params, token, positions, block_tables, kv, cfg, *state_slots)
        logits, *facts = out if isinstance(out, tuple) else (out,)
        ids, last = draw(logits, slot, last, sampling)
        return (ids, *facts), kv, last

    def verify_step_paged_sampled(params, tokens, positions, valid_len,
                                  block_tables, kv, cfg):
        """`verify_step_paged`, handing back the greedy ids [B, K1]: all
        the accept rule reads."""
        logits, kv = gpt.verify_step_paged(
            params, tokens, positions, valid_len, block_tables, kv, cfg)
        return gpt.sample_ids(logits, 0.0, None), kv

    _JITS = (
        jax.jit(prefill_paged_sampled, static_argnums=(7,), donate_argnums=(4, 5)),
        jax.jit(decode_step_paged_sampled, static_argnums=(6,), donate_argnums=(3, 4)),
        jax.jit(verify_step_paged_sampled, static_argnums=(6,), donate_argnums=(5,)),
    )
    return _JITS


@dataclasses.dataclass(frozen=True)
class EngineOptions:
    num_blocks: int = 64          # physical KV blocks (incl. null block 0)
    block_size: int = 16          # token slots per block
    max_num_seqs: int = 8         # decode-batch lane ceiling
    max_prefills_per_step: int = 1
    # Chunked prefill: per-step token budget (decode lanes cost 1 each,
    # prefill chunks spend the rest) and the per-chunk length cap — a long
    # prompt lands `prefill_chunk_tokens` per step instead of stalling every
    # decode stream for one monolithic prefill.
    max_step_tokens: int = 256
    prefill_chunk_tokens: int = 64
    # Automatic prefix caching: full KV blocks are content-hashed and
    # shared; a prompt whose prefix is cached skips straight to the first
    # cold block. Freed blocks are retained (reclaimable, LRU-evicted).
    enable_prefix_caching: bool = True
    # Speculative decoding (greedy only): per-lane draft length k proposed
    # by n-gram prompt lookup (spec.py) and scored in ONE verify forward
    # (`verify_step_paged`) — up to k+1 tokens emitted per step per lane.
    # 0 disables. Draft tokens are funded inside `max_step_tokens`.
    spec_tokens: int = 0
    spec_ngram: int = 2
    temperature: float = 0.0      # 0 = greedy
    seed: int = 0
    # Disaggregated serving (serve/README.md "Disaggregated serving"):
    # "mixed" (default — exactly the pre-disagg engine), "prefill" (step
    # budget biased toward prefill chunks: this replica computes prompts,
    # emits the first token, and hands decode off), or "decode" (prefill's
    # per-step share capped at max_step_tokens/4 so recompute tails can't
    # crowd the decode lanes).
    role: str = "mixed"
    # Host-RAM KV tier budget (bytes, per replica; 0 disables): HBM-evicted
    # registered blocks are SAVED here instead of dying, stay advertised in
    # the hot-prefix digest, serve allocate_cached on an HBM miss, and are
    # exportable to other replicas over the bulk plane.
    host_kv_bytes: int = 32 << 20
    # Deadline for one KV export/import (span fetch + handoff plumbing).
    kv_transfer_timeout_s: float = 30.0


@dataclasses.dataclass
class _InFlight:
    """One step's programs on the device, their ids not read yet."""

    # (sequences, the program's (ids, *facts) on the device, whether the
    # ids are first tokens), one entry a sampling program
    entries: List[tuple] = dataclasses.field(default_factory=list)
    # The step's `engine.step` record (t0_ns, t1_ns, attrs), written when
    # its ids are read: what came back with them belongs on it.
    record: Optional[tuple] = None


class RequestOutput:
    """Per-request stream endpoint: the engine thread feeds it, any
    consumer thread drains it."""

    def __init__(self, request_id: str, delivery: _Delivery):
        self.request_id = request_id
        self._q: "queue.Queue" = queue.Queue()    # (token, emitted at, ns)
        self._delivery = delivery
        self.taken = [0, 0, 0, 0]       # this stream's row of `_Delivery`
        self.finish_reason: Optional[str] = None
        # Registry-cleanup handshake (under the engine lock): the engine
        # drops the registry entry once the request is BOTH finished and
        # retrieved, whichever happens first — a fast request may finish
        # before its caller ever reaches stream().
        self.finished = False
        self.retrieved = False

    def __iter__(self) -> Iterator[int]:
        taken = self.taken
        self._delivery.opened(self)
        try:
            while True:
                behind = not self._q.empty()
                item = self._q.get()
                if item is _FINISH:
                    return
                if isinstance(item, Exception):
                    raise item
                tok, emitted_ns = item
                held_ns = time.monotonic_ns()
                taken[0] += 1
                taken[1] += held_ns - emitted_ns
                taken[3] += behind
                yield tok
                taken[2] += time.monotonic_ns() - held_ns
        finally:
            self._delivery.ended(self)


class InferenceEngine:
    def __init__(
        self,
        cfg,
        params=None,
        options: Optional[EngineOptions] = None,
    ):
        import jax

        from ...models.gpt import (attn_heads_by_window, hold_served, init_paged_cache,
                                   init_params, kv_head_rows, kv_layout)
        from ...ops import paged_attention

        self.cfg = dataclasses.replace(cfg, remat=False, remat_policy=None)
        self.opts = options or EngineOptions()
        if self.opts.role not in ("mixed", "prefill", "decode"):
            raise ValueError(
                f"role must be mixed|prefill|decode, got {self.opts.role!r}"
            )
        # One block table a KV group (`models/gpt.py` kv_layout). The host
        # tier, the disaggregated roles and block export/import move blocks
        # of ONE row shape (a K row and a V row) and one table: a model
        # whose layers form several groups, or whose layers keep one latent
        # row and no V (`KVLayout.value_row` 0), or whose layers keep a state
        # a sequence that no blob carries (`KVLayout.state`), is refused
        # here, not served wrongly (ROADMAP D9). Such a state is not rolled
        # back past a rejected draft either: no speculation.
        self._layout = kv_layout(self.cfg)
        self._groups = len(self._layout.windows)
        self._stateful = bool(self._layout.state)
        self._unmoved = (
            f"a model of {self._groups} KV groups" if self._groups > 1
            else "a model whose layers cache one latent row (no V rows)"
            if not self._layout.value_row
            else "a model whose layers keep a state a sequence (no snapshot)"
            if self._stateful else None)
        if self._unmoved:
            if self.opts.host_kv_bytes > 0 and self.opts.enable_prefix_caching:
                raise ValueError(
                    f"{self._unmoved} runs with the host KV tier off "
                    "(host_kv_bytes=0)")
            if self.opts.role != "mixed":
                raise ValueError(
                    f"{self._unmoved} serves role='mixed' only: the "
                    "prefill/decode hand-off exports K and V blocks of one group")
            if self._stateful and self.opts.spec_tokens > 0:
                raise ValueError(
                    f"{self._unmoved} runs without speculation (spec_tokens=0): "
                    "a verify step would have to roll the state back past "
                    "rejected drafts")
        self._jnp = jax.numpy
        self._jax = jax
        # Where the kernels run, as JAX reports it — benches and the chip
        # smoke read the platform from here, never from a flag.
        dev = jax.devices()[0]
        self._device = {"platform": dev.platform, "device_kind": dev.device_kind}
        if params is None:
            params = init_params(jax.random.PRNGKey(self.opts.seed), cfg)
        # The paged programs' own tree: the fused q/k/v stack re-formed ONCE, as they read it.
        self.params, self.weights_reformed_bytes = hold_served(params)
        # A model with state: one slot a lane behind the null slot.
        state_slots = self.opts.max_num_seqs if self._stateful else 0
        self.kv = init_paged_cache(
            self.cfg, self.opts.num_blocks, self.opts.block_size, state_slots
        )
        # One block's bytes, from what the layout declares a layer keeps.
        self.kv_block_bytes = self._layout.block_bytes(
            self.opts.block_size, jax.numpy.dtype(self.cfg.dtype).itemsize)
        self.host_tier = None
        if self.opts.host_kv_bytes > 0 and self.opts.enable_prefix_caching:
            from .kv_tier import HostKVTier

            self.host_tier = HostKVTier(self.opts.host_kv_bytes)
        self.block_manager = KVBlockManager(
            self.opts.num_blocks,
            self.opts.block_size,
            enable_prefix_caching=self.opts.enable_prefix_caching,
            host_tier=self.host_tier,
            group_windows=self._layout.windows,
            state_slots=state_slots,
        )
        proposer = None
        if self.opts.spec_tokens > 0:
            if self.opts.temperature > 0.0:
                # The greedy accept rule (longest matching draft prefix +
                # one corrective token) only reproduces GREEDY decode;
                # sampled decode would need rejection sampling.
                raise ValueError(
                    "speculative decoding requires temperature=0 (greedy)"
                )
            from .spec import NGramProposer

            proposer = NGramProposer(
                k=self.opts.spec_tokens, n=self.opts.spec_ngram
            )
        # Role biasing: a prefill-pool replica runs several chunks per step
        # (its decode lanes are single-token handoff stubs); a decode-pool
        # replica caps prefill's per-step share so recompute tails (import
        # misses, degraded handoffs) can't crowd the decode lanes.
        mpps = self.opts.max_prefills_per_step
        prefill_cap = None
        if self.opts.role == "prefill":
            mpps = max(mpps, 4)
        elif self.opts.role == "decode":
            prefill_cap = max(
                self.opts.prefill_chunk_tokens, self.opts.max_step_tokens // 4
            )
        self.scheduler = Scheduler(
            self.block_manager,
            max_num_seqs=self.opts.max_num_seqs,
            max_prefills_per_step=mpps,
            max_step_tokens=self.opts.max_step_tokens,
            prefill_chunk=self.opts.prefill_chunk_tokens,
            draft_proposer=proposer,
            prefill_budget_cap=prefill_cap,
        )
        # cfg is static (hashable frozen dataclass); the pool and the last
        # ids are donated — each call consumes them and hands back their
        # successors.
        self._prefill, self._decode, self._verify = _paged_jits()
        self._last, self._sampling = init_sampler(
            self.opts.max_num_seqs, self.opts.seed, self.opts.temperature)
        self._spare = self.opts.max_num_seqs    # the slot nobody reads
        # The previous step's samples, unread; this step's, as it dispatches.
        self._inflight: Optional[_InFlight] = None
        self._cur = _InFlight()
        import numpy as np

        self._np = np
        self._lock = threading.Lock()          # scheduler + queues
        self._work = threading.Condition(self._lock)
        self._outputs: Dict[str, RequestOutput] = {}
        self._next_id = 0
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        # Rolling throughput/latency accounting (host-side, cheap). The
        # latency windows are bounded — a long-lived replica must not
        # accumulate one float per request forever.
        self.total_tokens = 0
        self.total_preemptions = 0
        self.total_finished = 0
        self.total_spec_proposed = 0
        self.total_spec_accepted = 0
        self.total_blocks_imported = 0
        self.total_blocks_exported = 0
        # Decode and verify programs dispatched; of them, those dispatched
        # while a lane's newest id was still unread, taken from the device.
        self.total_decode_dispatched = 0
        self.total_decode_chained = 0
        self._step_chained = 0
        # Keys the dispatched programs' attention covered in a global layer
        # and keys of their padded tables, counted in the form the programs'
        # own rule gives their shapes (`ops/paged_attention.py`, `_count_attn`).
        self._paged_attention = paged_attention
        self._attn_heads = attn_heads_by_window(self.cfg)
        self._attn_shapes = (    # what the rule asks beside tokens and width
            self.opts.block_size, *kv_head_rows(self.cfg)[1:], self.cfg.dtype)
        self.total_attn_covered = [0, 0]
        # Prefill chunk programs dispatched and, of them, those whose form is
        # the chunk kernel; decode programs whose form is the decode kernel
        # (each lane's own blocks through its table).
        self.total_attn_chunks = [0, 0]
        self.total_attn_decodes_kernel = 0
        self._step_attn = [0, 0]
        # Expert routing: (experts touched, busiest expert's share) of the
        # decode step whose ids this step read, which came back with them;
        # for a program that holds a range of the experts also [assignments
        # that fell on held experts, all] and [expert layers whose routing
        # left none here (no expert read), all], summed over layers and steps.
        self._step_moe = None
        self.total_moe_assign = [0, 0]
        self.total_moe_layers = [0, 0]
        # Tokens (as the program is shaped) x expert layers of every paged
        # program an expert model dispatched: each takes the grouped form.
        self.total_moe_tokens = 0
        # Passes of the layer stack over the decode steps' real lanes: [run,
        # from the length of what came back; what `ut_steps` would be].
        self.total_ut_passes = [0, 0]
        # Side work serviced by the driver thread at step boundaries, where
        # self.kv is stable (kernel donation invalidates old buffers, so no
        # other thread may ever read the KV arrays): ("export", digests,
        # Future) entries from export_prompt_kv.
        self._side_work: "deque" = deque()
        self._ttfts: "deque[float]" = deque(maxlen=1024)
        self._tpots: "deque[float]" = deque(maxlen=1024)
        self._step_ttfts: List[float] = []     # reset each step()
        self._step_tpots: List[float] = []
        self._step_spec = [0, 0]               # [proposed, accepted]
        self._tok_window: "deque[float]" = deque()     # token-emit stamps, oldest first
        # (t, hits, misses) snapshots — fleet_state's RECENT hit-rate
        # window, the autoscaler's cache-cold signal.
        self._hit_snaps: "deque" = deque(maxlen=64)
        # request_id -> {trace, submit_ns, admit_ns, first_ns} (monotonic):
        # per-request span bookkeeping for traced (Serve) submissions —
        # untraced submits (engine unit tests, direct callers) skip it.
        self._trace_info: Dict[str, Dict[str, Any]] = {}
        self._lane = f"serve/engine-{self.opts.role or 'colocated'}"
        self._phases: Dict[str, int] = {}       # this step's, see _step
        self._idle = {"waited_ns": 0}   # _loop's wait, until a step records it
        # The books: what the step records carry, summed as they are made
        # (`_book_step`), beside the tokens' delivery and the process's GC.
        self._books: Dict[str, int] = dict.fromkeys(_STEP_BOOKS, 0)
        self._host_mean = [0, 0]    # [steps seen, running mean host ns a step]
        self._step_chunks = [0, 0]      # this step's chunk tokens: [real, padded]
        self._slots_t_ns = time.monotonic_ns()      # `_tick_slots`' last reading
        self._delivery = _Delivery()
        self._gc_hooked = True
        _GC.acquire()
        self._init_metrics()

    # ------------------------------------------------------------- metrics
    def _init_metrics(self):
        try:
            from ...util.metrics import Counter, Gauge, Histogram

            self._m_queue = Gauge(
                "serve_engine_queue_depth", "prompts waiting for KV admission"
            )
            self._m_running = Gauge(
                "serve_engine_running_seqs", "sequences in the decode batch"
            )
            self._m_kv = Gauge(
                "serve_engine_kv_utilization", "allocated fraction of KV blocks"
            )
            self._m_tps = Gauge(
                "serve_engine_tokens_per_s", "generated tokens/s (10s window)"
            )
            self._m_tokens = Counter(
                "serve_engine_tokens_total", "tokens generated"
            )
            self._m_preempt = Counter(
                "serve_engine_preemptions_total", "recompute preemptions"
            )
            self._m_ttft = Histogram(
                "serve_engine_ttft_s", "time to first token"
            )
            self._m_tpot = Histogram(
                "serve_engine_tpot_s", "time per output token after the first"
            )
            self._m_pc_hits = Counter(
                "serve_engine_prefix_cache_hits_total",
                "KV blocks served from the prefix cache",
            )
            self._m_pc_misses = Counter(
                "serve_engine_prefix_cache_misses_total",
                "cacheable KV blocks that had to be computed",
            )
            self._m_pc_evict = Counter(
                "serve_engine_prefix_cache_evictions_total",
                "cached KV blocks reclaimed for new allocations",
            )
            self._m_step_tokens = Histogram(
                "serve_engine_step_budget_tokens",
                "tokens scheduled per engine step "
                "(decode lanes + prefill chunk tokens)",
                boundaries=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024),
            )
            self._m_spec_prop = Counter(
                "serve_engine_spec_proposed_total",
                "speculative draft tokens scored by the verify step",
            )
            self._m_spec_acc = Counter(
                "serve_engine_spec_accepted_total",
                "speculative draft tokens accepted (emitted without a "
                "dedicated decode step)",
            )
            self._m_host_hits = Counter(
                "serve_engine_host_tier_hits_total",
                "prefix-cache hits served from the host-RAM KV tier",
            )
            self._m_host_bytes = Gauge(
                "serve_engine_host_tier_bytes",
                "bytes resident in the host-RAM KV tier",
            )
            self._m_kv_import = Counter(
                "serve_engine_kv_blocks_imported_total",
                "KV blocks imported from other replicas (disagg handoff / "
                "cluster-wide prefix cache)",
            )
            self._m_kv_export = Counter(
                "serve_engine_kv_blocks_exported_total",
                "KV blocks exported as bulk-plane span segments",
            )
            # Counters export monotonic increments; the KV manager keeps
            # lifetime totals — ship deltas since the last step.
            self._kv_exported = {"hits": 0, "misses": 0, "evictions": 0,
                                 "host_hits": 0, "imported": 0, "exported": 0}
            try:
                # Under Serve, tag every series with its replica so scrapes
                # distinguish replicas and the controller can prune a
                # drained replica's series (serve/controller._drain).
                from ..context import get_replica_context

                ctx = get_replica_context()
                tags = {"app": ctx.app_name, "deployment": ctx.deployment,
                        "replica": ctx.replica_tag,
                        "role": self.opts.role}
                for m in (self._m_queue, self._m_running, self._m_kv,
                          self._m_tps, self._m_tokens, self._m_preempt,
                          self._m_ttft, self._m_tpot, self._m_pc_hits,
                          self._m_pc_misses, self._m_pc_evict,
                          self._m_step_tokens, self._m_spec_prop,
                          self._m_spec_acc, self._m_host_hits,
                          self._m_host_bytes, self._m_kv_import,
                          self._m_kv_export):
                    m.set_default_tags(tags)
            except Exception:  # noqa: BLE001 — engine used outside Serve
                pass
        except Exception:  # noqa: BLE001 — metrics are never load-bearing
            self._m_queue = None

    def _export_metrics(self, stats: Dict[str, Any]):
        if self._m_queue is None:
            return
        try:
            self._m_queue.set(stats["queue_depth"])
            self._m_running.set(stats["running"])
            self._m_kv.set(stats["kv_utilization"])
            self._m_tps.set(stats["tokens_per_s"])
            if stats["step_tokens"]:
                self._m_tokens.inc(stats["step_tokens"])
            if stats["step_preemptions"]:
                self._m_preempt.inc(stats["step_preemptions"])
            for t in stats["step_ttfts"]:
                self._m_ttft.observe(t)
            for t in stats["step_tpots"]:
                self._m_tpot.observe(t)
            for key, stat_key, counter in (
                ("hits", "prefix_cache_hits", self._m_pc_hits),
                ("misses", "prefix_cache_misses", self._m_pc_misses),
                ("evictions", "prefix_cache_evictions", self._m_pc_evict),
                ("host_hits", "host_tier_hits", self._m_host_hits),
                ("imported", "blocks_imported", self._m_kv_import),
                ("exported", "blocks_exported", self._m_kv_export),
            ):
                delta = stats[stat_key] - self._kv_exported[key]
                if delta > 0:
                    counter.inc(delta)
                    self._kv_exported[key] += delta
            self._m_host_bytes.set(stats["host_tier_bytes"])
            if stats["step_budget_tokens"]:
                self._m_step_tokens.observe(stats["step_budget_tokens"])
            if stats["step_spec_proposed"]:
                self._m_spec_prop.inc(stats["step_spec_proposed"])
            if stats["step_spec_accepted"]:
                self._m_spec_acc.inc(stats["step_spec_accepted"])
        except Exception:  # noqa: BLE001 — no runtime in unit tests
            pass

    # -------------------------------------------------------------- intake
    def submit(
        self,
        prompt: List[int],
        max_new_tokens: int,
        request_id: Optional[str] = None,
        eos_token: Optional[int] = None,
    ) -> str:
        """Enqueue a request; returns its id immediately. Raises ValueError
        for requests that could NEVER run (too long for the model window or
        the whole KV pool) — transient fullness just queues."""
        if self._stop.is_set():
            raise RuntimeError("engine is shut down")
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) + max_new_tokens > self.cfg.max_seq:
            raise ValueError(
                f"prompt {len(prompt)} + max_new_tokens {max_new_tokens} "
                f"exceeds max_seq {self.cfg.max_seq}"
            )
        if not self.block_manager.fits_ever(len(prompt) + max_new_tokens):
            raise ValueError(
                f"request needs {len(prompt) + max_new_tokens} KV slots; pool "
                f"holds {(self.opts.num_blocks - 1) * self.opts.block_size}"
            )
        try:
            from ...util.tracing import get_trace_id

            trace_id = get_trace_id()
        except Exception:  # noqa: BLE001
            trace_id = None
        with self._work:
            if request_id is None:
                request_id = f"req-{self._next_id}"
                self._next_id += 1
            seq = Sequence(
                request_id=request_id,
                prompt=prompt,
                max_new_tokens=max_new_tokens,
                eos_token=eos_token,
            )
            self.scheduler.add(seq)
            self._outputs[request_id] = RequestOutput(request_id, self._delivery)
            if trace_id:
                self._trace_info[request_id] = {
                    "trace": trace_id, "submit_ns": time.monotonic_ns(),
                }
            self._work.notify_all()
        return request_id

    def stream(self, request_id: str) -> RequestOutput:
        """Claim a request's output stream (single consumer). Valid until
        claimed no matter how fast the request finished; unknown/already-
        claimed ids raise KeyError."""
        with self._lock:
            out = self._outputs[request_id]
            out.retrieved = True
            if out.finished:
                del self._outputs[request_id]
            return out

    def generate(
        self,
        prompt: List[int],
        max_new_tokens: int,
        eos_token: Optional[int] = None,
    ) -> List[int]:
        """Blocking convenience: submit + drain (the driver thread must be
        running — `start()` — or another thread must call `step()`)."""
        rid = self.submit(prompt, max_new_tokens, eos_token=eos_token)
        return list(self.stream(rid))

    # ---------------------------------------------------------------- step
    def _emit(self, seq: Sequence, tok: int):
        seq.append_token(tok)
        now_ns = time.monotonic_ns()    # the token's emission, for its consumer
        out = self._outputs.get(seq.request_id)
        if out is not None:
            out._q.put((tok, now_ns))
        self.total_tokens += 1
        self._tok_window.append(now_ns * 1e-9)

    def _maybe_finish(self, seq: Sequence) -> bool:
        reason = seq.should_stop()
        if reason is None:
            return False
        with self._lock:
            self.scheduler.finish(seq, reason)
            out = self._outputs.get(seq.request_id)
            if out is not None:
                out.finish_reason = reason
                out.finished = True
                if out.retrieved:
                    del self._outputs[seq.request_id]
        if out is not None:
            out._q.put(_FINISH)
        self.total_finished += 1
        if seq.first_token_t is not None:
            ttft = seq.first_token_t - seq.arrival_t
            self._ttfts.append(ttft)
            self._step_ttfts.append(ttft)
            n = seq.num_generated  # survives preemption's output fold
            if n > 1 and seq.finish_t is not None:
                tpot = (seq.finish_t - seq.first_token_t) / (n - 1)
                self._tpots.append(tpot)
                self._step_tpots.append(tpot)
        self._emit_request_spans(seq)
        return True

    def _emit_request_spans(self, seq: Sequence):
        """Queue-wait/admission/prefill/first-token/completion spans of a
        finished traced request, into the flight ring: the driver thread
        sends nothing, the ring's flusher ships them."""
        rec = self._trace_info.pop(seq.request_id, None)
        if rec is None:
            return
        now = time.monotonic_ns()
        submit = rec["submit_ns"]
        admit = rec.get("admit_ns", now)
        first = rec.get("first_ns", admit)
        attrs = {"request_id": seq.request_id, "tokens": seq.num_generated}
        for name, t0, t1, more in (
            ("engine.queue_wait", submit, admit, {}),
            ("engine.admission", admit, admit, {}),
            ("engine.prefill", admit, first, {}),
            ("engine.first_token", first, first, {}),
            ("engine.completion", first, now,
             {"finish_reason": seq.finish_reason}),
        ):
            flight.record(name, t0, t1, trace=rec["trace"],
                          lane=self._lane + "/requests",
                          attrs={**attrs, **more})

    def _apply_cow(self):
        """Land queued copy-on-write block copies (shared block forked by
        the scheduler) on the physical KV arrays before any kernel reads
        them. Rare — only fork-shared partial blocks ever trigger it."""
        copies = self.block_manager.drain_cow()
        if not copies:
            return
        jnp = self._jnp
        src = jnp.asarray([s for s, _ in copies])
        dst = jnp.asarray([d for _, d in copies])
        self.kv = {
            name: arr.at[:, dst].set(arr[:, src])
            for name, arr in self.kv.items()
        }

    # -------------------------------------------- tiered KV / KV transfer
    #
    # Step-top drain order is a correctness contract (kv_manager header):
    # SAVES read evicted blocks' HBM bytes before anything overwrites them,
    # then COW copies, then LOADS land tier/import bytes, then kernels run.
    # Everything below executes on the driver thread only.

    def _block_blobs(self, blocks: List[int]):
        """The given blocks' KV bytes as contiguous host arrays [2(k/v),
        pool depth, BS, H*Dh] each — the unit of the host tier and the transfer
        plane. Batched: ONE device read per KV array (then per-block host
        copies), not two blocking transfers per block — saves/exports sit
        at the top of the hot step path."""
        np = self._np
        jdx = self._jnp.asarray(blocks)
        ks = np.asarray(self.kv["k"][:, jdx])   # [L, n, BS, H*Dh]
        vs = np.asarray(self.kv["v"][:, jdx])
        return [
            np.ascontiguousarray(np.stack([ks[:, i], vs[:, i]]))
            for i in range(len(blocks))
        ]

    def _apply_host_saves(self):
        """Copy evicted registered blocks' bytes into the host tier (FIRST
        drain: the blocks are already reallocated, and COW/loads/kernels
        may overwrite them later this step)."""
        with self._lock:
            saves = self.block_manager.drain_saves()
        if not saves or self.host_tier is None:
            return
        blobs = self._block_blobs([b for _, b in saves])
        with self._lock:
            for (h, _), blob in zip(saves, blobs):
                self.host_tier.put(h, blob)

    def _apply_host_loads(self):
        """Land tier-hit and imported block bytes on the HBM arrays before
        any kernel reads them (after saves + COW)."""
        with self._lock:
            loads = self.block_manager.drain_loads()
        if not loads:
            return
        jnp = self._jnp
        np = self._np
        idx = jnp.asarray([b for _, b, _, _ in loads])
        ks = np.stack([np.asarray(blob[0]) for _, _, blob, _ in loads])
        vs = np.stack([np.asarray(blob[1]) for _, _, blob, _ in loads])
        dt = self.kv["k"].dtype
        self.kv = {
            "k": self.kv["k"].at[:, idx].set(
                jnp.asarray(ks.swapaxes(0, 1), dt)
            ),
            "v": self.kv["v"].at[:, idx].set(
                jnp.asarray(vs.swapaxes(0, 1), dt)
            ),
        }
        # Local host-tier re-admissions are NOT imports (host_hits counts
        # them) — the import counter tracks only remotely-computed blocks.
        self.total_blocks_imported += sum(
            1 for _, _, _, remote in loads if remote
        )

    def _kv_sig(self) -> str:
        """Layout signature guarding imports: block bytes only interchange
        between engines with identical model geometry, block size, dtype
        and block layout ("rows": a block is [BS, H*Dh], one row a token —
        a blob of the older head-major blocks has the same bytes in
        another order, so it must not be adopted). The depth is the pool's
        (`kv_layout`: cache layers, passes x layers for a looped model)
        and the passes are named, so that a one-pass engine and a looped
        one of equal widths never adopt each other's blocks. The row kind
        is the layout's: "rows" for a K row and a V row a token, "latent<n>"
        for one latent row of n and no V, so a latent engine and a K/V
        engine refuse each other's blocks whatever their widths."""
        c, lay = self.cfg, self._layout
        kind = "rows" if lay.value_row else f"latent{lay.key_row}"
        return (
            f"{lay.depth}/{lay.passes}:{c.kv_heads}:{c.d_head}:"
            f"{self.opts.block_size}:{self._jnp.dtype(c.dtype).str}:{kind}"
        )

    def prompt_digests(self, prompt: List[int]) -> List[bytes]:
        """Chain digests of EVERY full block of `prompt` (the kv_manager's
        content address). Unlike admission's cacheable cap this includes a
        block ending exactly at the prompt tail — after a completed prefill
        `register_computed` has registered all of them."""
        from .kv_manager import _chain_hash

        bs = self.opts.block_size
        out: List[bytes] = []
        prev = b""
        for i in range(len(prompt) // bs):
            prev = _chain_hash(prev, prompt[i * bs:(i + 1) * bs])
            out.append(prev)
        return out

    def export_prompt_kv(
        self, prompt: List[int], timeout_s: Optional[float] = None
    ) -> Optional[Dict[str, Any]]:
        """Publish `prompt`'s computed full-block KV as a span descriptor
        (kv_transfer.export_descriptor) any replica can import. Runs on the
        driver thread at a step boundary (the only safe point to read the
        donated KV arrays); this caller blocks until serviced. Returns None
        when there is nothing exportable (short prompt, blocks already
        evicted everywhere, engine stopped)."""
        self._refuse_unmoved("export_prompt_kv")
        digests = self.prompt_digests(prompt)
        if not digests or self._stop.is_set():
            return None
        from concurrent.futures import Future, TimeoutError as _FutTimeout

        from ...util.tracing import get_trace_id

        # Captured HERE (the replica RPC thread carries the request's task
        # context); _do_export runs on the driver thread, which has none.
        # The trace rides the descriptor so the importing replica's spans
        # join the same x-request-id forest.
        trace = get_trace_id()
        t0 = flight.now_ns()
        fut: "Future" = Future()
        with self._work:
            self._side_work.append(("export", digests, fut))
            self._work.notify_all()
        try:
            desc = fut.result(
                timeout_s if timeout_s is not None
                else self.opts.kv_transfer_timeout_s
            )
        except _FutTimeout:
            return None
        except Exception:  # noqa: BLE001 — export is best-effort: an arena
            # put or controller RPC failing mid-export must degrade the
            # handoff to colocated recompute, not fail the caller's request.
            return None
        if desc is not None:
            if trace:
                desc["trace"] = trace
            flight.record(
                "kv.export", t0, flight.now_ns(), trace=trace,
                lane="serve/engine", flow=f"disagg/{trace}" if trace else None,
                attrs={"blocks": len(desc.get("digests") or ())})
        return desc

    def _do_export(self, digests: List[bytes]) -> Optional[Dict[str, Any]]:
        """Driver-thread half of export_prompt_kv: gather block bytes (HBM
        blocks at a step boundary; host-tier/pending blobs as-is) and store
        them as one span-addressed arena segment. Digests no longer held
        anywhere are dropped from the descriptor — the importer recomputes
        exactly those blocks."""
        from . import kv_transfer

        with self._lock:
            srcs = self.block_manager.export_sources(digests)
        present: List[bytes] = []
        kept: List[Tuple] = []
        for h, src in zip(digests, srcs):
            if src is None:
                # A chain hole makes every later block unreachable to the
                # importer's walk — stop at the first gap.
                break
            present.append(h)
            kept.append(src)
        if not present:
            return None
        hbm_at = [i for i, s in enumerate(kept) if s[0] == "hbm"]
        hbm_blobs = (
            self._block_blobs([kept[i][1] for i in hbm_at]) if hbm_at else []
        )
        blobs: List = [None] * len(kept)
        for i, blob in zip(hbm_at, hbm_blobs):
            blobs[i] = blob
        for i, s in enumerate(kept):
            if s[0] != "hbm":
                blobs[i] = self._np.asarray(s[1])
        desc = kv_transfer.export_descriptor(
            present, blobs, self._kv_sig(), self.opts.block_size
        )
        if desc is not None:
            self.total_blocks_exported += len(present)
        return desc

    def import_blocks(self, desc: Optional[Dict[str, Any]]) -> int:
        """Adopt a remote replica's exported KV blocks into the local cache
        (called from any thread — the replica RPC thread during a handoff).
        Fetches bytes over the fallback ladder (same-node arena read ->
        bulk span pull -> whole-object get), ALL OR NOTHING, then registers
        each block as a cached entry whose bytes the driver thread lands
        before its next kernel. Returns the number adopted; 0 means the
        importer simply recomputes (degraded mode is the pre-disagg path)."""
        self._refuse_unmoved("import_blocks")
        if not desc or not self.opts.enable_prefix_caching \
                or self._stop.is_set():
            return 0
        trace = desc.get("trace")
        t0 = flight.now_ns()

        def _span(n: int, needed: int) -> int:
            flight.record(
                "kv.import", t0, flight.now_ns(), trace=trace,
                lane="serve/engine",
                flow=f"disagg/{trace}" if trace else None,
                attrs={"blocks": n, "needed": needed})
            return n

        if desc.get("sig") != self._kv_sig():
            return _span(0, 0)
        from . import kv_transfer

        with self._lock:
            # A digest already registered in HBM OR resident in the local
            # host tier needs no network fetch — allocate_cached serves the
            # tier copy as a host->HBM memcpy at admission.
            needed = [
                h for h in desc.get("digests") or []
                if self.block_manager.holds(bytes.fromhex(h)) is None
                and not (
                    self.host_tier is not None
                    and self.host_tier.contains(bytes.fromhex(h))
                )
            ]
        if not needed:
            return _span(0, 0)
        blobs = kv_transfer.fetch_blocks(
            desc, needed, timeout_s=self.opts.kv_transfer_timeout_s
        )
        if not blobs:
            return _span(0, len(needed))
        n = 0
        with self._lock:
            for hx, blob in blobs:
                h = bytes.fromhex(hx)
                if self.block_manager.holds(h) is not None:
                    # Raced in since `needed` was computed (a concurrent
                    # import of a shared prefix) — skip, keep adopting the
                    # rest: later digests may still be unique to us.
                    continue
                if self.block_manager.adopt_block(h, blob) is None:
                    break  # pool has nothing to give — the rest recompute
                n += 1
        return _span(n, len(needed))

    def _refuse_unmoved(self, what: str):
        if self._unmoved:
            raise NotImplementedError(
                f"{what}: blocks of {self._unmoved} are not exported or imported")

    def _tables_into(self, arr, seq: Sequence):
        """A sequence's block table(s) into a zeroed [W] / [G, W] row."""
        if self._groups == 1:
            table = self.block_manager.block_table(seq.request_id)
            arr[: len(table)] = table
        else:
            for g, table in enumerate(
                    self.block_manager.block_tables(seq.request_id)):
                arr[g, : len(table)] = table

    def _table_shape(self, *lead) -> tuple:
        return lead if self._groups == 1 else (*lead[:-1], self._groups, lead[-1])

    def _service_side_work(self):
        """Run queued export requests at the step boundary (after loads:
        freshly imported bytes are already exportable onward)."""
        while True:
            with self._lock:
                if not self._side_work:
                    return
                kind, payload, fut = self._side_work.popleft()
            try:
                result = self._do_export(payload) if kind == "export" else None
                fut.set_result(result)
            except Exception as e:  # noqa: BLE001 — fail the waiter, not the loop
                fut.set_exception(e)

    def _count_attn(self, tokens: int, width: int, last_pos, real, first_pos=None) -> str:
        """Ask the programs' own rule, once, for the form of the attention of a program of `tokens`
        tokens a lane over tables `width` blocks wide, add its (keys run, keys padded) to the step's
        and the engine's counts and its heads x keys by layer kind to the books (`_book_shared`: the
        rows that several layers read), from the arithmetic its own bounds come from, and return the
        form. `first_pos`: each lane's first query, where it is not its last (a chunk)."""
        form = self._paged_attention.paged_attn_form(
            tokens, width, *self._attn_shapes)
        run, padded, window, every = self._paged_attention.paged_attn_cover(
            form, self._attn_heads, width, self.opts.block_size,
            last_pos if first_pos is None else first_pos, last_pos, real)
        for count in (self._step_attn, self.total_attn_covered):
            count[0] += run
            count[1] += padded
        self._books["attn_head_keys_window"] += window
        self._books["attn_head_keys"] += every
        _book_shared(self, first_pos is None, tokens, self._np.size(last_pos), run, every)
        return form

    def _count_state(self, tokens: int, real: int, decode: bool):
        """Add one program of a model with state to its books: `tokens` the scan ran over as the
        program is shaped, `real` unmasked; a decode lane reads and writes its slot's state; its blocks by kind."""
        if self._stateful:
            b = self._books
            for kind, n in self._layout.kinds: b["blocks_" + kind] += n     # a `block_pattern` model's
            b["ssm_tokens_scanned"] += tokens
            b["ssm_tokens_masked"] += tokens - real
            if decode:
                b["ssm_state_bytes"] += 2 * real * self._layout.state_bytes

    def _tick_slots(self):
        """Add the time since the last reading to the books of what is held
        over time (the pool's blocks, a model with state's slots), at the
        number held NOW: called at a step's start (the count stood since the
        last step's end) and at its end."""
        now = time.monotonic_ns()
        dt, self._slots_t_ns = now - self._slots_t_ns, now
        kv, b = self.block_manager, self._books
        b["kv_block_held_ns"] += dt * kv.blocks_held
        b["kv_window_block_ns"] += dt * kv.window_blocks_held
        b["kv_block_cap_ns"] += dt * (kv.num_blocks - 1)    # block 0 is the null block
        if self._stateful:
            b["state_slot_held_ns"] += dt * kv.state_slots_held
            b["state_slot_cap_ns"] += dt * kv.state_slots

    def _state_slot(self, seq: Sequence) -> List[int]:
        """[] or [the sequence's state slot]: the extra entry a lane of a
        model with state carries into its program beside its block table."""
        return [self.block_manager.state_slot(seq.request_id)] if self._stateful else []

    def _count_moe(self, tokens: int):
        """Add one program of `tokens` tokens (lanes x tokens a lane, padding
        and all) to the expert layers' count."""
        if self.cfg.mlp_type == "moe":
            self.total_moe_tokens += tokens * self.cfg.moe_layers

    def _run_prefill(self, chunk):
        """Dispatch one prefill chunk: compute prompt[start : start+n] into
        the paged cache. Every chunk's program samples; only the FINAL
        chunk's id is the sequence's first token (TTFT), written to its
        slot and read one step behind."""
        seq = chunk.seq
        ph = self._phases
        rec = self._trace_info.get(seq.request_id)
        if rec is not None and "admit_ns" not in rec:
            rec["admit_ns"] = time.monotonic_ns()
        jnp = self._jnp
        np = self._np
        with flight.phase("engine.build", ph, "build_ns"):
            L = chunk.num_tokens
            # Same bucketing primitive as the scheduler's decode shapes —
            # agreement between the two is what bounds the XLA program set.
            Sp = _next_pow2(L)
            W = _next_pow2(self.block_manager.blocks_for(
                self.block_manager.seq_len(seq.request_id)))
            tokens = np.zeros((1, Sp), np.int32)
            tokens[0, :L] = seq.prompt[chunk.start:chunk.start + L]
            bt = np.zeros(self._table_shape(W), np.int32)
            self._tables_into(bt, seq)
            form = self._count_attn(Sp, W, np.asarray([chunk.start + L - 1]), True,
                                    np.asarray([chunk.start]))
            self.total_attn_chunks[0] += 1
            self.total_attn_chunks[1] += form == self._paged_attention.CHUNK_KERNEL
            self._count_moe(Sp)
            self._count_state(Sp, L, decode=False)
            self._step_chunks[0] += L
            self._step_chunks[1] += Sp
            meta = np.asarray(
                [L, chunk.start, seq.slot if chunk.last else self._spare,
                 *self._state_slot(seq)], np.int32)
            args = (jnp.asarray(tokens), jnp.asarray(meta), jnp.asarray(bt))
        with flight.phase("engine.dispatch", ph, "dispatch_ns"):
            out, self.kv, self._last = self._prefill(
                self.params, *args, self.kv, self._last, self._sampling,
                self.cfg)
            del args    # the input buffers are released here, not at return
        with flight.phase("engine.schedule", ph, "sched_ns"):
            seq.num_computed = chunk.start + L
            # The chunk's KV is landed — its newly-FULL blocks are now safe
            # to serve as prefix-cache hits for later prompts. Under the
            # engine lock: registration touches the hot-hash digest that
            # telemetry (`fleet_state`, actor RPC thread) iterates.
            with self._lock:
                self.block_manager.register_computed(
                    seq.request_id, seq.prompt, seq.num_computed
                )
            if chunk.last:
                self._sampled([seq], out, first=True)

    def _sampled(self, seqs: List[Sequence], out, first: bool = False):
        """Book a dispatched program's samples: tokens by COUNT now
        (`unread`), by value when `_collect` reads them. A sequence whose
        last token this is gives its lane and blocks back at once."""
        for a in out:
            a.copy_to_host_async()
        self._cur.entries.append((seqs, out, first))
        done = []
        for seq in seqs:
            seq.unread += 1
            if seq.num_remaining <= 0:
                done.append(seq)
        if done:
            with self._lock:
                for seq in done:
                    self.scheduler.retire(seq)

    def _collect(self):
        """Read the ids of the step in flight (dispatched by the previous
        `_step`, or drained early by this one) and do what waited for their
        values: the streams, the finish tests, the books, the step's
        record. An id of a sequence that finished meanwhile (it rode one
        step past its `eos`) is dropped."""
        fl, self._inflight = self._inflight, None
        if fl is None:
            return
        ph = self._phases
        tok0 = self.total_tokens
        facts = {}
        # One program at a time, in dispatch order: a first token goes out
        # when its chunk's id is there, not when the decode program
        # dispatched behind the chunk has ended too.
        for seqs, out, first in fl.entries:
            with flight.phase("engine.fetch_ids", ph, "fetch_ns"):
                ids, *more = self._jax.device_get(out)
            with flight.phase("engine.sample", ph, "sample_ns"):
                if more:
                    facts = self._decode_facts(len(seqs), more)
                for seq, tok in zip(seqs, ids.tolist()):
                    seq.unread -= 1
                    if seq.state == FINISHED:
                        continue
                    self._emit(seq, tok)
                    rec = first and self._trace_info.get(seq.request_id)
                    if rec:
                        rec.setdefault("first_ns", time.monotonic_ns())
                    self._maybe_finish(seq)
        if fl.record is not None:
            t0_ns, t1_ns, attrs = fl.record
            flight.record(
                "engine.step", t0_ns, t1_ns, lane=self._lane,
                attrs={**attrs, **facts, "tokens": self.total_tokens - tok0})

    def _decode_facts(self, lanes: int, facts) -> Dict[str, Any]:
        """What a decode program handed back beside its ids (the step's
        routing, the exit gate), as attributes of its step's record."""
        attrs = {}
        b = self._books
        if self.cfg.mlp_type == "moe":
            self._step_moe = facts.pop(0)
            attrs["experts_touched"] = float(self._step_moe[0])
            attrs["expert_load_max"] = float(self._step_moe[1])
            b["moe_steps_read"] += 1
            b["moe_experts_touched_milli"] += round(1e3 * attrs["experts_touched"])
            b["moe_load_max_ppm"] += round(1e6 * attrs["expert_load_max"])
            if self.cfg.moe_held:   # means over the expert layers -> their sums
                layers = self.cfg.moe_layers
                held, total, empty = (
                    int(round(float(v) * layers)) for v in self._step_moe[2:])
                attrs["assign_held"], attrs["assign_total"] = held, total
                self.total_moe_assign[0] += held
                self.total_moe_assign[1] += total
                self.total_moe_layers[0] += empty
                self.total_moe_layers[1] += layers
        if self.cfg.ut_steps > 1:
            pdf = facts.pop(0).tolist()     # one entry a pass the program ran
            self.total_ut_passes[0] += lanes * len(pdf)
            self.total_ut_passes[1] += lanes * self.cfg.ut_steps
            attrs["ut_passes"] = len(pdf)
            attrs["exit_step_mean"] = sum(t * p for t, p in enumerate(pdf, 1))
            attrs["exit_cdf_early"] = sum(pdf[:-1])
            b["ut_steps_read"] += 1
            b["ut_exit_step_milli"] += round(1e3 * attrs["exit_step_mean"])
        return attrs

    def _run_verify(self, out: SchedulerOutput):
        """Speculative step: every decode lane rides ONE `verify_step_paged`
        call — lane i scores its current token plus its funded draft (other
        lanes ride along with an empty draft: their slot 0 is exactly a
        plain decode). Greedy acceptance: the longest draft prefix matching
        the model's own argmax is emitted, then one corrective (or, on full
        acceptance, bonus) token — token-for-token identical to plain
        greedy decode, just fewer dispatches. Drained: the drafts and the
        lanes' current tokens are host values, and how many tokens a lane
        gains is known only from the ids, so they are read at once."""
        jnp = self._jnp
        np = self._np
        ph = self._phases
        seqs = out.decodes
        with flight.phase("engine.build", ph, "build_ns"):
            B = out.batch_bucket
            W = out.width_bucket
            K1 = self.opts.spec_tokens + 1
            tokens = np.zeros((B, K1), np.int32)
            positions = np.zeros((B,), np.int32)
            valid_len = np.zeros((B,), np.int32)  # 0 for padding lanes
            tables = np.zeros(self._table_shape(B, W), np.int32)   # padding lanes -> null block
            lane_drafts: List[List[int]] = []
            for i, seq in enumerate(seqs):
                d = out.drafts.get(seq.request_id, [])
                lane_drafts.append(d)
                tokens[i, 0] = seq.output[-1]
                if d:
                    tokens[i, 1:1 + len(d)] = d
                positions[i] = seq.num_tokens - 1
                valid_len[i] = 1 + len(d)
                self._tables_into(tables[i], seq)
            self._count_attn(K1, W, positions + valid_len - 1, valid_len > 0, positions)
            self._count_moe(B * K1)
            args = (
                jnp.asarray(tokens),
                jnp.asarray(positions),
                jnp.asarray(valid_len),
                jnp.asarray(tables),
            )
        with flight.phase("engine.dispatch", ph, "dispatch_ns"):
            greedy, self.kv = self._verify(
                self.params, *args, self.kv, self.cfg
            )
            del args    # the input buffers are released here, not at return
        with flight.phase("engine.fetch_ids", ph, "fetch_ns"):
            greedy = np.asarray(greedy)
        with flight.phase("engine.sample", ph, "sample_ns"):
            self._accept_drafts(seqs, lane_drafts, greedy)

    def _accept_drafts(self, seqs, lane_drafts, greedy_ids):
        """Greedy acceptance of a verify step's drafts (see `_run_verify`)."""
        for i, seq in enumerate(seqs):
            d = lane_drafts[i]
            greedy = greedy_ids[i]
            emitted: List[int] = []
            accepted = 0
            for j, dt in enumerate(d):
                g = int(greedy[j])
                if g == dt:
                    emitted.append(dt)
                    accepted += 1
                else:
                    emitted.append(g)  # the corrective token
                    break
            if accepted == len(d):
                emitted.append(int(greedy[len(d)]))  # bonus token
            self.total_spec_proposed += len(d)
            self.total_spec_accepted += accepted
            self._step_spec[0] += len(d)
            self._step_spec[1] += accepted
            for tok in emitted:
                self._emit(seq, tok)
                if self._maybe_finish(seq):
                    # eos mid-span: later landed KV is garbage ABOVE the
                    # watermark — never registered, freed with the seq.
                    break

    def _run_decode(self, out: SchedulerOutput):
        """Dispatch the step's decode program (or its verify program, which
        is also read). A lane whose newest id is still unread takes it from
        the device's buffer by its slot: the program is then CHAINED to the
        one that sampled it, and the host was not in between."""
        self.total_decode_dispatched += 1
        if out.drafts:
            return self._run_verify(out)
        jnp = self._jnp
        np = self._np
        ph = self._phases
        seqs = out.decodes
        with flight.phase("engine.build", ph, "build_ns"):
            B = out.batch_bucket
            W = out.width_bucket
            # rows: slot, position, the id where the host knows it, known
            # (a model with state: and the lane's state slot); padding lanes:
            # the spare slot, token 0 at position 0 (the null state slot)
            lanes = np.zeros((4 + self._stateful, B), np.int32)
            lanes[0] = self._spare
            lanes[3] = 1
            lanes[1, :len(seqs)] = [seq.num_tokens - 1 for seq in seqs]     # where this token's KV lands
            form = self._count_attn(1, W, lanes[1], np.arange(B) < len(seqs))
            tables = _decode_tables(self, form, B, W)             # padding lanes -> null block
            for i, seq in enumerate(seqs):
                lanes[0, i] = seq.slot
                if seq.unread:
                    lanes[3, i] = 0
                else:
                    lanes[2, i] = seq.output[-1]
                lanes[4:, i] = self._state_slot(seq)
                self._tables_into(tables[i], seq)
            self._step_chained = int(not lanes[3].all())
            self.total_decode_chained += self._step_chained
            self.total_attn_decodes_kernel += form == self._paged_attention.DECODE_KERNEL
            self._count_moe(B)
            self._count_state(B, len(seqs), decode=True)
            args = (jnp.asarray(lanes), jnp.asarray(tables))
        with flight.phase("engine.dispatch", ph, "dispatch_ns"):
            sampled, self.kv, self._last = self._decode(
                self.params, *args, self.kv, self._last, self._sampling,
                self.cfg)
            del args    # the input buffers are released here, not at return
        with flight.phase("engine.schedule", ph, "sched_ns"):
            self._sampled(seqs, sampled)

    def step(self) -> Dict[str, Any]:
        """One engine iteration; safe to drive manually (tests) or from the
        driver thread. Returns a stats snapshot."""
        with flight.phase("engine.step"):
            return self._step()

    def _try_schedule(self) -> Optional[SchedulerOutput]:
        with self._lock:
            try:
                return self.scheduler.schedule()
            except NeedsValues:
                return None

    def _step(self) -> Dict[str, Any]:
        t0 = time.monotonic()
        # Host phases of the step, nanoseconds summed over it and put on
        # its ONE `engine.step` flight record (flight.SERVE_STEP_PHASES):
        # a dozen monotonic_ns reads and inactive profiler annotations; the
        # record itself only happens on steps that did work. The span is
        # the first six phases; `export_ns` follows it and `waited_ns` (the
        # driver thread's idle wait, `_loop`) precedes it. `fetch_ns` is
        # the wait for the PREVIOUS step's ids (and a verify step's own),
        # `sample_ns` their delivery: emission and finish tests. Budgeted
        # ≤5% of decode-step time (test_flight_perf_smoke).
        fl_on = flight.enabled()
        ph = self._phases = dict.fromkeys(flight.SERVE_STEP_PHASES, 0)
        self._tick_slots()
        t0_ns = time.monotonic_ns()
        with flight.phase("engine.schedule", ph, "sched_ns"):
            self._step_ttfts, self._step_tpots = [], []
            self._step_spec = [0, 0]  # [proposed, accepted]
            self._step_attn = [0, 0]  # [keys run, keys padded]
            self._step_moe = None
            self._step_chained = 0
            self._step_chunks = [0, 0]
            gc0 = _GC.ns
            tok0 = self.total_tokens
            had_flight = self._inflight is not None
            # An export is served drained; so is a plan that needs token
            # VALUES (`NeedsValues`: drafts, the fold of a preempted lane):
            # read the step in flight, then plan. Never after planning: an
            # `eos` read then would finish a lane the plan still holds.
            out = None if self._side_work else self._try_schedule()
        if out is None:
            self._collect()
        with flight.phase("engine.schedule", ph, "sched_ns"):
            if out is None:
                with self._lock:
                    out = self.scheduler.schedule()
            self.total_preemptions += len(out.preempted)
            for seq in out.preempted:
                # Recompute preemption re-queues the request: its admission,
                # prefill, and first-token spans restart at the next
                # schedule (keeping first_ns would put first_token BEFORE
                # admission).
                rec = self._trace_info.get(seq.request_id)
                if rec is not None:
                    rec.pop("admit_ns", None)
                    rec.pop("first_ns", None)
        # Drain order is load-bearing (kv_manager header): eviction SAVES
        # read their blocks' bytes before COW copies or tier/import LOADS
        # can overwrite them, and everything lands before kernels run.
        with flight.phase("engine.side_work", ph, "side_ns"):
            self._apply_host_saves()
            self._apply_cow()
            self._apply_host_loads()
            self._service_side_work()
        cur = self._cur = _InFlight()
        for chunk in out.prefills:
            self._run_prefill(chunk)
        own_tokens = self.total_tokens
        if out.decodes:
            self._run_decode(out)
        own_tokens = self.total_tokens - own_tokens   # a verify step's, read at once
        # Step n+1 is on the device: now read step n, one step behind.
        self._collect()
        self._inflight = cur if cur.entries else None
        # The span ends where the step's own work does; building `stats`
        # and the metrics export follow it as `export_ns`.
        t1_ns = time.monotonic_ns()
        self._tick_slots()
        with flight.phase("engine.export_metrics", ph, "export_ns"):
            now = time.monotonic()
            _expire_stamps(self._tok_window, now, 10.0)
            kv_stats = self.block_manager.stats()
            stats = {
                "queue_depth": self.scheduler.queue_depth,
                "running": self.scheduler.num_running,
                "kv_utilization": kv_stats.utilization,
                "kv_free_blocks": kv_stats.free_blocks,
                "kv_cached_blocks": kv_stats.cached_blocks,
                "prefix_cache_hits": kv_stats.hits,
                "prefix_cache_misses": kv_stats.misses,
                "prefix_cache_evictions": kv_stats.evictions,
                "host_tier_hits": kv_stats.host_hits,
                "host_tier_bytes": kv_stats.host_bytes,
                "blocks_imported": self.total_blocks_imported,
                "blocks_exported": self.total_blocks_exported,
                "step_budget_tokens": out.step_tokens,
                "tokens_per_s": (
                    len(self._tok_window) / max(now - self._tok_window[0], 1e-3)
                    if self._tok_window
                    else 0.0
                ),
                "step_tokens": self.total_tokens - tok0,
                "step_preemptions": len(out.preempted),
                "step_prefills": len(out.prefills),
                "step_decodes": len(out.decodes),
                "step_spec_proposed": self._step_spec[0],
                "step_spec_accepted": self._step_spec[1],
                "step_ttfts": list(self._step_ttfts),
                "step_tpots": list(self._step_tpots),
                "step_s": now - t0,
            }
            self._export_metrics(stats)
        if out.prefills or out.decodes or had_flight:
            idle, self._idle = self._idle, {"waited_ns": 0}
            times = {**idle, **ph}
            load = {"queue_depth": stats["queue_depth"],
                    "running": stats["running"]}
            self._book_step(out, t0_ns, t1_ns, times, {
                "bucket": out.batch_bucket, **load, "gc_ns": _GC.ns - gc0})
            if fl_on:
                attrs = {"prefills": len(out.prefills),
                         "decodes": len(out.decodes),
                         "chained": self._step_chained,
                         "tokens": own_tokens,
                         "attn_keys_run": self._step_attn[0],
                         "attn_keys_padded": self._step_attn[1],
                         **times, **load,
                         "kv_util": stats["kv_utilization"]}
                if self._inflight is not None:
                    # its tokens and what comes back with its ids are not
                    # here yet: `_collect` writes the record when it reads them
                    self._inflight.record = (t0_ns, t1_ns, attrs)
                else:
                    flight.record("engine.step", t0_ns, t1_ns,
                                  lane=self._lane, attrs=attrs)
        return stats

    def _book_step(self, out: SchedulerOutput, t0_ns: int, t1_ns: int,
                   times: Dict[str, int], load: Dict[str, int]):
        """Add a step that writes a record to the books: the record's own
        values, summed, the step's kind what it carried (a prefill chunk |
        decode lanes and no chunk). Then the test for a slow step, on the
        host's part of the span: `fetch_ns` is the device's program where
        steps chain, the rest is this thread's own work and whatever held
        it up. A slow step is booked (`steps_slow`, `slow_ns`: its excess
        over the mean) and leaves ONE `engine.stall` flight span with its
        phases and the GC pauses inside it, so that a stall of a run nobody
        traced has a name afterwards."""
        b = self._books
        span = t1_ns - t0_ns
        host = span - times["fetch_ns"]
        lanes = len(out.decodes)
        b["steps"] += 1
        b["step_ns"] += span
        b["host_ns"] += host
        for key, ns in times.items():
            b[key] += ns
        b["between_ns"] += span - sum(
            times[k] for k in flight.SERVE_STEP_PHASES[:-1])
        b["prefill_tokens"] += self._step_chunks[0]
        b["prefill_tokens_padded"] += self._step_chunks[1]
        if lanes:
            b["steps_decode"] += 1
            b["decode_lanes"] += lanes
            b["decode_bucket_lanes"] += out.batch_bucket
            if out.prefills:
                b["decode_lanes_beside_chunk"] += lanes
        kind = "chunk" if out.prefills else "decode_only" if lanes else None
        if kind is not None:
            b[f"steps_{kind}"] += 1
            b[f"step_{kind}_ns"] += span
        seen = self._host_mean
        seen[0] += 1
        n, mean = seen
        if n > _SLOW_AFTER and host > _SLOW_FACTOR * mean:
            b["steps_slow"] += 1
            b["slow_ns"] += host - mean
            flight.record("engine.stall", t0_ns, t1_ns, lane=self._lane, attrs={
                "mean_ns": mean, **load,
                **{k: times[k] for k in flight.SERVE_STEP_PHASES[:-1]}})
            host = _SLOW_FACTOR * mean    # moves the mean as a step at the edge
        seen[1] = mean + (host - mean) // min(n, _SLOW_AFTER)

    def stats(self, include_raw: bool = False) -> Dict[str, Any]:
        """Engine counters + latency summaries. `include_raw=True` adds the
        bounded raw TTFT/TPOT windows so a fleet bench can pool percentiles
        ACROSS replicas instead of averaging per-replica medians."""
        np = self._np
        # Under the engine lock: called from actor RPC threads while the
        # driver thread mutates the block manager (same race fleet_state
        # guards against — _evictable() iterates the cached dict).
        with self._lock:
            kv_stats = self.block_manager.stats()
            ttfts = list(self._ttfts)
            tpots = list(self._tpots)
        extra = ({"ttft_recent": ttfts, "tpot_recent": tpots}
                 if include_raw else {})
        ring = flight.recorder()
        return {
            **extra,
            **self._device,
            "queue_depth": self.scheduler.queue_depth,
            "running": self.scheduler.num_running,
            "kv_utilization": kv_stats.utilization,
            "kv_cached_blocks": kv_stats.cached_blocks,
            "prefix_cache_hits": kv_stats.hits,
            "prefix_cache_misses": kv_stats.misses,
            "prefix_cache_evictions": kv_stats.evictions,
            "role": self.opts.role,
            "host_tier_hits": kv_stats.host_hits,
            "host_tier_blocks": kv_stats.host_blocks,
            "host_tier_bytes": kv_stats.host_bytes,
            "blocks_imported": self.total_blocks_imported,
            "blocks_exported": self.total_blocks_exported,
            "weights_reformed_bytes": self.weights_reformed_bytes,
            "window_blocks_released": self.block_manager.window_released,
            "attn_keys_run": self.total_attn_covered[0],
            "attn_keys_padded": self.total_attn_covered[1],
            "attn_chunks": self.total_attn_chunks[0],
            "attn_chunks_kernel": self.total_attn_chunks[1],
            "attn_decodes_kernel": self.total_attn_decodes_kernel,
            "ut_passes_run": self.total_ut_passes[0],
            "ut_passes_full": self.total_ut_passes[1],
            "moe_assign_held": self.total_moe_assign[0],
            "moe_assign_total": self.total_moe_assign[1],
            "moe_layers_empty": self.total_moe_layers[0],
            "moe_layers_routed": self.total_moe_layers[1],
            # one served form: the two read alike (`moe_grouped_token_share`)
            "moe_tokens_grouped": self.total_moe_tokens,
            "moe_tokens_expert": self.total_moe_tokens,
            "decode_dispatched": self.total_decode_dispatched,
            "decode_chained": self.total_decode_chained,
            "state_slots": kv_stats.state_slots,
            "state_slots_held": kv_stats.state_slots_held,
            "state_slots_claimed": self.block_manager.states_claimed,
            "state_slots_released": self.block_manager.states_released,
            **self._books,
            # This PROCESS's ring (util/flight.py), since it started.
            "flight_spans_recorded": ring.recorded_total,
            "flight_spans_dropped": ring.dropped_total,
            **dict(zip(("stream_tokens", "stream_wake_ns", "stream_send_ns",
                        "stream_behind"), self._delivery.read())),
            "gc_ns": _GC.ns,
            "gc_collections": _GC.collections,
            "total_tokens": self.total_tokens,
            "total_finished": self.total_finished,
            "total_preemptions": self.total_preemptions,
            "spec_proposed": self.total_spec_proposed,
            "spec_accepted": self.total_spec_accepted,
            # This PROCESS's totals (util/metrics.py): records made against
            # messages the flusher handed to the control plane for them.
            "metric_records": _metrics.records_total,
            "metric_sends": _metrics.sends_total,
            "spec_acceptance_rate": (
                round(self.total_spec_accepted / self.total_spec_proposed, 4)
                if self.total_spec_proposed
                else None
            ),
            "ttft_p50_s": float(np.median(ttfts)) if ttfts else None,
            "ttft_p99_s": _quantile(ttfts, 0.99),
            "tpot_p50_s": float(np.median(tpots)) if tpots else None,
        }

    def fleet_state(self) -> Dict[str, Any]:
        """Bounded telemetry the controller piggybacks on its health probes
        and routers steer by (`serve/fleet/`): load (queue/running/free
        blocks), the hot-prefix digest, the TTFT tail, the RECENT prefix-
        hit rate (30s window — the autoscaler's cache-cold signal), and the
        spec-decode acceptance rate."""
        # Under the engine lock: telemetry runs on the actor RPC thread
        # while the driver thread mutates the block manager (the digest's
        # hot-hash OrderedDict would otherwise be iterated mid-mutation).
        with self._lock:
            kv_stats = self.block_manager.stats()
            digest = self.block_manager.prefix_digest(64)
            queue_depth = self.scheduler.queue_depth
            running = self.scheduler.num_running
            ttfts = list(self._ttfts)
        now = time.monotonic()
        self._hit_snaps.append((now, kv_stats.hits, kv_stats.misses))
        while self._hit_snaps and now - self._hit_snaps[0][0] > 30.0:
            self._hit_snaps.popleft()
        t0, h0, m0 = self._hit_snaps[0]
        dh, dm = kv_stats.hits - h0, kv_stats.misses - m0
        return {
            "queue_depth": queue_depth,
            "running": running,
            "free_blocks": kv_stats.free_blocks,
            "block_size": self.opts.block_size,
            "kv_utilization": kv_stats.utilization,
            "digest": digest,
            # Disaggregated pools: the fleet router splits replicas into
            # prefill/decode pools on this, and the controller autoscales
            # the two pools on their own signals.
            "role": self.opts.role,
            "host_tier_hits": kv_stats.host_hits,
            "host_tier_blocks": kv_stats.host_blocks,
            "host_tier_bytes": kv_stats.host_bytes,
            "ttft_p99_s": _quantile(ttfts, 0.99),
            "prefix_hit_rate": (
                round(dh / (dh + dm), 4) if (dh + dm) > 0 else None
            ),
            "spec_acceptance_rate": (
                round(self.total_spec_accepted / self.total_spec_proposed, 4)
                if self.total_spec_proposed
                else None
            ),
        }

    # -------------------------------------------------------- driver thread
    def start(self):
        if self._thread is not None:
            return
        self._stop.clear()
        if not self._gc_hooked:     # started again after a shutdown
            self._gc_hooked = True
            _GC.acquire()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="llm-engine"
        )
        self._thread.start()

    def shutdown(self):
        self._stop.set()
        with self._work:
            self._work.notify_all()
        stuck = False
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            stuck, self._thread = self._thread.is_alive(), None
        if not stuck:       # the driver thread is gone: the books are ours
            try:
                self._collect()     # a step in flight still delivers its tokens
            except Exception:  # noqa: BLE001 — the streams are failed below
                self._inflight = None
        # Fail every open stream — a consumer blocked in queue.get() would
        # otherwise hang forever once the driver thread is gone.
        with self._lock:
            outs = list(self._outputs.values())
            self._outputs.clear()
            self._trace_info.clear()
            side, self._side_work = list(self._side_work), deque()
        for out in outs:
            out._q.put(RuntimeError("engine shut down"))
        if self._gc_hooked:
            self._gc_hooked = False
            _GC.release()
        for _, _, fut in side:
            # Exporters blocked in export_prompt_kv must not wait out their
            # full transfer deadline on a dead driver thread.
            try:
                fut.set_result(None)
            except Exception:  # noqa: BLE001
                pass

    def _nothing_to_run(self) -> bool:
        return (
            not self.scheduler.has_work()
            and self._inflight is None      # a step in flight is work
            and not self._side_work
            and not self._stop.is_set()
        )

    def _loop(self):
        t_ns = time.monotonic_ns()
        while not self._stop.is_set():
            with self._work:
                if self._nothing_to_run():
                    # No demand: not the program's time to shorten, so it
                    # is kept apart from every wait inside a step.
                    with flight.phase("engine.wait_work", self._idle,
                                      "waited_ns"):
                        while self._nothing_to_run():
                            self._work.wait(timeout=0.1)
            if self._stop.is_set():
                return
            try:
                self.step()
            except Exception as e:  # noqa: BLE001 — fail every open stream
                with self._lock:
                    outs = list(self._outputs.values())
                    self._outputs.clear()
                    self._trace_info.clear()
                    # Drop all scheduler state: without it the loop would
                    # respin on the same poisoned batch forever.
                    for seq in self.scheduler.running + self.scheduler.closing:
                        self.scheduler.finish(seq, "error")
                    self.scheduler.waiting.clear()
                    self.scheduler._seqs.clear()
                    self._inflight = None
                for out in outs:
                    out._q.put(e)
            # the whole iteration, wait and step and export: what the
            # books' shares of the thread's time are taken over
            now_ns = time.monotonic_ns()
            self._books["loop_ns"] += now_ns - t_ns
            t_ns = now_ns


def _expire_stamps(window: "deque[float]", now: float, span: float) -> None:
    """Drop the stamps more than `span` seconds before `now` from the old
    end of a window that one thread appends to in time order: a call pays
    for the stamps that expired since the last one, never for the window's
    length. (At the END of the file: the compile cache's key carries the
    line of every `def` and dispatch site above, ROADMAP S7.)"""
    while window and now - window[0] > span:
        window.popleft()


def _book_shared(engine: "InferenceEngine", decode: bool, tokens: int, lanes: int, run: int, every: int) -> None:
    """One dispatched program of a model some of whose layers READ rows that
    another layer wrote (`KVLayout.reads`: a decoder-hybrid-decoder's cross
    layers) into the books (`shared_kv_read_bytes`, `kv_read_bytes`,
    `cross_decoder_tokens`), from what `_count_attn` counted:
    `run` keys a layer without window covered, `every` query heads x keys over
    all attention layers. A decode program: the bytes of SHARED rows its
    attention read (the writer and each of its readers read every covered key's
    K and V row) beside the row bytes all its attention layers read. A chunk
    program of `tokens` tokens a lane: the tokens its cross-decoder ran on, by the
    rule the program itself cuts its stream by (`models/gpt.py`
    `sambay_cross_tokens`). Nothing for any other model. (At the END of the file:
    ROADMAP S7.)"""
    lay = engine._layout
    if not lay.reads:
        return
    books = engine._books
    if not decode:
        from ...models.gpt import sambay_cross_tokens

        books["cross_decoder_tokens"] += lanes * sambay_cross_tokens(tokens, True)
        return
    sharing = _SHARING.get(lay)
    if sharing is None:     # layers that read shared rows, their writers among them
        written = {(lay.group_of[l], lay.slot_of[l]) for l, reads in enumerate(lay.reads) if reads}
        sharing = _SHARING[lay] = sum(lay.reads) + len(written)
    row = (lay.key_row + lay.value_row) * engine._jnp.dtype(engine.cfg.dtype).itemsize
    books["shared_kv_read_bytes"] += run * row * sharing
    books["kv_read_bytes"] += every // engine.cfg.n_heads * row


_SHARING: Dict[Any, int] = {}   # by `KVLayout`: worked out once, not a dispatch


def _decode_tables(engine: "InferenceEngine", form: str, lanes: int, bucket: int):
    """The zeroed block tables [lanes, (groups,) width] of a decode step of `form`
    (what the programs' own rule gave `_count_attn`) whose widest sequence holds
    `bucket` blocks (the scheduler's power of two). The decode kernel reads each lane's
    blocks through its table and pads whatever table it gets to ONE width a lane count
    (`ops.paged_attention.decode_table_width`), so its tables ARE that width, no wider
    than a sequence this engine admits can hold (`submit`) and never under the bucket:
    the decode program is then keyed by its lanes alone, and a server warms one a lane
    bucket instead of one a (lanes, width) pair (booked: `decode_width_fixed`). Any
    other form gathers by the table's width, which stays the bucket. (At the END of the
    file: ROADMAP S7.)"""
    pa, opts, width = engine._paged_attention, engine.opts, bucket
    if form == pa.DECODE_KERNEL:
        most = _next_pow2(min(-(-engine.cfg.max_seq // opts.block_size), opts.num_blocks))
        width = max(bucket, min(pa.decode_table_width(lanes, opts.num_blocks), most))
        engine._books["decode_width_fixed"] += 1
    return engine._np.zeros(engine._table_shape(lanes, width), engine._np.int32)
