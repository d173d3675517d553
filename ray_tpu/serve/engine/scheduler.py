"""Iteration-level scheduler (reference-era analog: Orca's iteration-level
scheduling as productized by vLLM's `core/scheduler.py`, including its
chunked-prefill step budget).

The unit of scheduling is ONE decode iteration, not one request: every call
to `schedule()` re-forms the working set — finished sequences were retired
by the engine a step earlier (their blocks already back on the free list),
queued prefills are admitted the moment the KV budget covers their prompt,
and the decode batch is whatever is RUNNING right now. A long generation
therefore never gates a short one behind it: the short request joins the
batch at the next iteration boundary and exits as soon as it hits its stop
condition.

Chunked prefill: a prompt no longer runs as one monolithic prefill. Every
step has a TOKEN budget (`max_step_tokens`); decode lanes spend one token
each and the remainder funds prefill CHUNKS (`PrefillChunk`) of at most
`prefill_chunk` tokens, so a 4k-token prompt advances a slice per step
while every decode stream keeps emitting. A sequence mid-prefill is RUNNING
but not yet decoding (`Sequence.num_computed` tracks its prefill cursor —
prefix-cache hits start it past zero); in-flight prefills continue before
new admissions so held blocks convert to tokens ASAP. Decode lanes are
funded first: chunking bounds prefill's intrusion on inter-token latency,
never the reverse.

Batch-shape discipline for XLA: decode batches are padded up to a bucket
size (powers of two up to `max_num_seqs`) and block-table widths to a
bucket width, so the jitted paged-decode program compiles once per
(batch_bucket, width_bucket) pair instead of once per working-set shape.
Prefill chunk lengths are capped at `prefill_chunk` and padded to powers of
two by the engine for the same reason. Bucketing lives here (scheduler
policy); padding lives in the engine (tensor mechanics).

Preemption: when decode growth exhausts the pool, the YOUNGEST running
sequence (last admitted — minimizes wasted work) is preempted by recompute:
its blocks are freed and it re-enters the wait queue with prompt+generated
as the new prompt, vLLM's recompute-style preemption. With prefix caching
on, its freed full blocks stay cached, so the recompute usually costs one
cache-hit re-admission rather than a real re-prefill.

One step ahead of the values: the engine dispatches step n+1 before it has
read step n's sampled ids, so a step is planned from COUNTS. A sequence's
`unread` ids (sampled on the device, not yet on the host) count as tokens
everywhere a length is asked for; a sequence whose last token was just
dispatched is retired at once (`retire`: lane, slot and blocks go back
before the next `schedule()`, the stream closes when the id is read).
A model with state a sequence (`KVBlockManager.state_slots`) changes nothing
here: the manager claims the sequence's state slot with its first allocation
and frees it with its blocks, so admission waits for a slot as it waits for
blocks, and `_preempt` gives the slot back (the readmitted sequence
recomputes from zero: its first chunk starts at position 0).

Only two things here need token VALUES, and both raise `NeedsValues` so
that the engine reads the step in flight and asks again: folding a
preempted sequence's output into its prompt, and the n-gram proposer's
look-up. Every running sequence holds a lane SLOT, its entry in the
engine's device buffer of last ids.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Deque, Dict, List, Optional

from .kv_manager import KVBlockManager, KVCacheExhausted

WAITING = "WAITING"
RUNNING = "RUNNING"
FINISHED = "FINISHED"


class NeedsValues(Exception):
    """`schedule()` reached a decision that reads a sequence's tokens while
    its newest id is still unread on the device. Nothing was decided yet
    that a second call would not decide again the same way: the engine
    collects the step in flight and calls `schedule()` again."""


@dataclasses.dataclass
class Sequence:
    """One request's generation state, host-side."""

    request_id: str
    prompt: List[int]
    max_new_tokens: int
    eos_token: Optional[int] = None
    arrival_t: float = dataclasses.field(default_factory=time.monotonic)
    output: List[int] = dataclasses.field(default_factory=list)
    state: str = WAITING
    # Prefill cursor: prompt positions with KV already landed (cache hits +
    # completed chunks). Decoding begins once it reaches len(prompt).
    num_computed: int = 0
    # Prompt tokens served straight from the prefix cache at last admission.
    num_cached: int = 0
    # Lifetime token count: unlike len(output) it survives preemption's
    # output→prompt fold, so per-token latency (TPOT) stays honest.
    num_generated: int = 0
    first_token_t: Optional[float] = None
    finish_t: Optional[float] = None
    finish_reason: Optional[str] = None
    preemptions: int = 0
    # Lane slot while RUNNING: this sequence's entry in the engine's device
    # buffer of last sampled ids (-1: none).
    slot: int = -1
    # Ids sampled on the device that the engine has not read yet (0 or 1
    # when a step is planned): tokens by count, not yet by value.
    unread: int = 0

    @property
    def num_tokens(self) -> int:
        return len(self.prompt) + len(self.output) + self.unread

    @property
    def num_remaining(self) -> int:
        """Tokens still to sample, the unread ones already taken off."""
        return self.max_new_tokens - len(self.output) - self.unread

    @property
    def is_decoding(self) -> bool:
        """Prefill complete — this sequence rides the decode batch."""
        return self.num_computed >= len(self.prompt)

    def append_token(self, tok: int) -> None:
        if self.first_token_t is None:
            self.first_token_t = time.monotonic()
        self.output.append(tok)
        self.num_generated += 1

    def should_stop(self) -> Optional[str]:
        if len(self.output) >= self.max_new_tokens:
            return "length"
        if self.eos_token is not None and self.output and \
                self.output[-1] == self.eos_token:
            return "eos"
        return None


@dataclasses.dataclass
class PrefillChunk:
    """One step's slice of one prompt's prefill."""

    seq: Sequence
    start: int        # first prompt position this chunk computes
    num_tokens: int   # chunk length (<= scheduler.prefill_chunk)
    last: bool        # final chunk: the engine samples token 0 after it


@dataclasses.dataclass
class SchedulerOutput:
    """One iteration's work order for the engine."""

    prefills: List[PrefillChunk]   # chunk work: compute prompt[start:start+n]
    decodes: List[Sequence]        # running: one decode_step token each
    preempted: List[Sequence]      # freed + requeued this step (for logging)
    batch_bucket: int              # padded decode batch size (0 = no decode)
    width_bucket: int              # padded block-table width (blocks)
    # Speculative drafts funded this step: request_id -> draft tokens. A
    # lane with a draft runs the k+1-token verify step instead of a plain
    # decode; its draft tokens count against the step budget.
    drafts: Dict[str, List[int]] = dataclasses.field(default_factory=dict)

    @property
    def step_tokens(self) -> int:
        """Token budget actually spent this step (1/decode lane + funded
        draft tokens + prefill chunks)."""
        return (
            len(self.decodes)
            + sum(len(d) for d in self.drafts.values())
            + sum(c.num_tokens for c in self.prefills)
        )


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


class Scheduler:
    def __init__(
        self,
        kv: KVBlockManager,
        max_num_seqs: int = 8,
        max_prefills_per_step: int = 1,
        max_step_tokens: int = 256,
        prefill_chunk: int = 64,
        draft_proposer=None,
        prefill_budget_cap: Optional[int] = None,
    ):
        if max_step_tokens <= max_num_seqs:
            raise ValueError(
                "max_step_tokens must exceed max_num_seqs or a full decode "
                "batch starves prefill forever"
            )
        if prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        self.kv = kv
        self.max_num_seqs = max_num_seqs
        self.max_prefills_per_step = max_prefills_per_step
        self.max_step_tokens = max_step_tokens
        self.prefill_chunk = prefill_chunk
        # Role biasing (disaggregated pools, `EngineOptions.role`): a
        # DECODE-pool replica caps prefill's share of every step so the few
        # prompt tails it must recompute (import misses, degraded handoffs)
        # cannot crowd its decode lanes; None = chunking alone bounds
        # prefill intrusion (the mixed/colocated default).
        self.prefill_budget_cap = prefill_budget_cap
        # Speculative decoding (None = off): proposes draft tokens per
        # decoding lane; funded drafts ride the same step-token budget as
        # everything else (decode lanes first, drafts next, prefill last).
        self.proposer = draft_proposer
        self.waiting: Deque[Sequence] = deque()
        self.running: List[Sequence] = []
        # Retired at dispatch (`retire`): the last id is on its way, lane
        # and blocks are already given back.
        self.closing: List[Sequence] = []
        self._seqs: Dict[str, Sequence] = {}
        self._free_slots = list(range(max_num_seqs - 1, -1, -1))
        # Victims of a `schedule()` that `NeedsValues` cut short: the call
        # that completes reports them.
        self._preempted: List[Sequence] = []

    # ------------------------------------------------------------ intake
    def add(self, seq: Sequence) -> None:
        if seq.request_id in self._seqs:
            raise ValueError(f"duplicate request_id {seq.request_id!r}")
        # +1: the prompt's first generated token also needs a KV slot.
        if not self.kv.fits_ever(len(seq.prompt) + seq.max_new_tokens):
            raise KVCacheExhausted(
                f"request {seq.request_id!r} needs "
                f"{len(seq.prompt) + seq.max_new_tokens} KV slots but the "
                f"whole pool holds {(self.kv.num_blocks - 1) * self.kv.block_size}"
            )
        self._seqs[seq.request_id] = seq
        self.waiting.append(seq)

    def get(self, request_id: str) -> Sequence:
        return self._seqs[request_id]

    @property
    def queue_depth(self) -> int:
        return len(self.waiting)

    @property
    def num_running(self) -> int:
        return len(self.running)

    def has_work(self) -> bool:
        """Anything queued, running, or sampled and not yet delivered."""
        return bool(self.waiting or self.running or self.closing)

    # --------------------------------------------------------- scheduling
    def finish(self, seq: Sequence, reason: str) -> None:
        """Retire a sequence NOW — its blocks hit the free list before the
        next schedule() so a queued prefill can take them this iteration."""
        seq.state = FINISHED
        seq.finish_reason = reason
        seq.finish_t = time.monotonic()
        if seq in self.running:
            self._release(seq)
        elif seq in self.closing:
            self.closing.remove(seq)
        del self._seqs[seq.request_id]
        if self.proposer is not None:
            self.proposer.forget(seq.request_id)

    def retire(self, seq: Sequence) -> None:
        """The program that samples `seq`'s last token was just dispatched:
        give back its lane, slot and blocks NOW, so that the next
        `schedule()` decides on what a finished sequence leaves (programs
        run in dispatch order: whoever takes the blocks writes them after
        this one read them). `finish` closes it when the id is read."""
        self._release(seq)
        self.closing.append(seq)

    def _release(self, seq: Sequence) -> None:
        self.running.remove(seq)
        self.kv.free(seq.request_id)
        self._free_slots.append(seq.slot)
        seq.slot = -1

    def _chunk_for(self, seq: Sequence, budget: int) -> PrefillChunk:
        n = min(len(seq.prompt) - seq.num_computed, budget, self.prefill_chunk)
        return PrefillChunk(
            seq=seq,
            start=seq.num_computed,
            num_tokens=n,
            last=seq.num_computed + n >= len(seq.prompt),
        )

    def schedule(self) -> SchedulerOutput:
        prefills: List[PrefillChunk] = []
        preempted = self._preempted
        drafts: Dict[str, List[int]] = {}

        # Draft funding rides what's left after every decode lane gets its
        # guaranteed 1 token (conservative: preemption below only shrinks
        # the lane count).
        draft_budget = self.max_step_tokens - sum(
            1 for s in self.running if s.state == RUNNING and s.is_decoding
        )

        # 1. Grow every DECODING sequence's table for the token(s) this
        # iteration will append — one slot for the plain decode token plus
        # one per funded speculative draft; preempt the youngest on
        # exhaustion (dropping the lane's draft first: a shorter step beats
        # sacrificing someone's cache). token_ids + the computed watermark
        # let the KV manager register newly-full blocks in the prefix index
        # (KV for the latest token is not landed until the step consumes
        # it, hence num_tokens - 1). Registration progresses whenever the
        # landed watermark covers MORE full blocks than are registered —
        # speculative multi-token appends can jump past a boundary, so the
        # O(context) token-list concat is built only on that check, and the
        # register loop catches up on every missing block at once.
        for seq in list(self.running):
            if seq.state != RUNNING or not seq.is_decoding:
                continue  # mid-prefill, or preempted as a victim this loop
            landed = seq.num_tokens - 1
            reg = {}
            if landed > 0 and (
                landed // self.kv.block_size
                > self.kv.num_registered(seq.request_id)
            ):
                reg = dict(
                    token_ids=seq.prompt + seq.output, num_computed=landed
                )
            d: List[int] = []
            if self.proposer is not None and seq.unread:
                # drafts are looked up in tokens, and a verify step takes
                # every lane's current token from the host
                raise NeedsValues
            if self.proposer is not None and draft_budget > 0:
                # Cap: emitting accepted+1 tokens must never overshoot the
                # request's remaining generation budget. The proposer keeps
                # its own history copy — this call is O(new tokens).
                remaining = seq.num_remaining
                if remaining > 1:
                    d = self.proposer.propose(
                        seq.request_id, seq.prompt, seq.output,
                        min(draft_budget, remaining - 1),
                    )
            while True:
                try:
                    self.kv.grow(
                        seq.request_id, seq.num_tokens + 1 + len(d),
                        first_query=seq.num_tokens - 1, **reg
                    )
                    break
                except KVCacheExhausted:
                    if d:
                        d = []  # drop the draft before preempting anyone
                        continue
                    victim = self._pick_victim(exclude=seq)
                    if victim is None:
                        # seq itself is the youngest — preempt it.
                        self._preempt(seq)
                        preempted.append(seq)
                        break
                    self._preempt(victim)
                    preempted.append(victim)
            if d and seq.state == RUNNING:
                drafts[seq.request_id] = d
                draft_budget -= len(d)

        decodes = [
            s for s in self.running if s.state == RUNNING and s.is_decoding
        ]
        # Decode lanes (and their funded drafts) first; prefill chunks
        # spend the remainder (capped for decode-pool replicas).
        budget = (
            self.max_step_tokens
            - len(decodes)
            - sum(len(d) for d in drafts.values())
        )
        if self.prefill_budget_cap is not None:
            budget = min(budget, self.prefill_budget_cap)

        # 2. Continue in-flight partial prefills (admission order) before
        # admitting anyone new — their blocks are already committed.
        # A window group's blocks for the chunk are acquired here (and the
        # ones behind its window released): exhaustion preempts the youngest.
        for seq in list(self.running):
            if len(prefills) >= self.max_prefills_per_step or budget <= 0:
                break
            if seq.state != RUNNING or seq.is_decoding:
                continue
            chunk = self._fit_chunk(seq, budget, preempted)
            if chunk is None:           # not even one token's blocks
                self._preempt(seq)
                preempted.append(seq)
                continue
            prefills.append(chunk)
            budget -= chunk.num_tokens

        # 3. Admit queued prompts while lanes, KV, and budget allow.
        # FCFS: head-of-line blocking on the QUEUE is fine (arrival order is
        # fair); what iteration-level scheduling removes is blocking on the
        # multi-second decode of earlier admissions. Admission allocates the
        # WHOLE prompt (+1 for the first generated token) by prefix-cache
        # lookup first — a cached prefix starts the cursor past zero.
        while (
            self.waiting
            and len(prefills) < self.max_prefills_per_step
            and budget > 0
            and len(self.running) < self.max_num_seqs
        ):
            seq = self.waiting[0]
            try:
                _, cached = self.kv.allocate_cached(
                    seq.request_id, seq.prompt, len(seq.prompt) + 1
                )
            except KVCacheExhausted:
                break  # stays queued — refusal, not failure
            seq.num_computed = cached
            chunk = self._fit_chunk(seq, budget, None)
            if chunk is None:
                self.kv.free(seq.request_id)
                seq.num_computed = 0
                break  # stays queued
            self.waiting.popleft()
            seq.state = RUNNING
            seq.num_cached = cached
            seq.slot = self._free_slots.pop()
            self.running.append(seq)
            prefills.append(chunk)
            budget -= chunk.num_tokens

        # A chunk's window blocks may have preempted a lane or a chunk that
        # was already on this step's work order.
        if preempted:
            decodes = [s for s in decodes if s.state == RUNNING]
            prefills = [c for c in prefills if c.seq.state == RUNNING]
        # A lane preempted AFTER its draft was funded must not leak a stale
        # drafts entry into the work order.
        if drafts:
            live = {s.request_id for s in decodes}
            drafts = {rid: d for rid, d in drafts.items() if rid in live}

        self._preempted = []
        bb = _next_pow2(len(decodes)) if decodes else 0
        max_w = max(
            (len(self.kv.block_table(s.request_id)) for s in decodes),
            default=0,
        )
        return SchedulerOutput(
            prefills=prefills,
            decodes=decodes,
            preempted=preempted,
            batch_bucket=min(bb, _next_pow2(self.max_num_seqs)),
            width_bucket=_next_pow2(max_w) if max_w else 0,
            drafts=drafts,
        )

    def _fit_chunk(self, seq: Sequence, budget: int,
                   preempted: Optional[List[Sequence]]) -> Optional[PrefillChunk]:
        """The sequence's next chunk with its window groups moved to it
        (`KVBlockManager.slide`: the blocks behind the window released, the
        chunk's acquired). When the pool cannot give the chunk's blocks:
        preempt the youngest OTHER sequence (never for a new admission:
        `preempted` None), then halve the chunk; None when not even one
        token's blocks can be had. A model without window groups always
        gets its chunk: its blocks were all acquired at admission."""
        chunk = self._chunk_for(seq, budget)
        while True:
            try:
                self.kv.slide(seq.request_id, chunk.start,
                              chunk.start + chunk.num_tokens)
                return chunk
            except KVCacheExhausted:
                victim = None if preempted is None else self._pick_victim(exclude=seq)
                if victim is not None:
                    self._preempt(victim)
                    preempted.append(victim)
                elif chunk.num_tokens > 1:
                    chunk = self._chunk_for(seq, chunk.num_tokens // 2)
                else:
                    return None

    def _pick_victim(self, exclude: Sequence) -> Optional[Sequence]:
        for seq in reversed(self.running):  # youngest first
            if seq is not exclude and seq.state == RUNNING:
                return seq
        return None

    def _preempt(self, seq: Sequence) -> None:
        """Recompute-style preemption: fold generated tokens into the prompt
        and requeue at the FRONT (it has seniority over never-run arrivals).
        With prefix caching, the freed full blocks stay cached — the
        "recompute" usually re-admits as cache hits."""
        if seq.unread:
            raise NeedsValues       # the fold below wants every token's value
        self._release(seq)
        # Already-generated tokens were already streamed out; fold them into
        # the prompt and shrink the remaining generation budget to match.
        seq.max_new_tokens -= len(seq.output)
        seq.prompt = seq.prompt + seq.output
        seq.output = []
        seq.state = WAITING
        seq.num_computed = 0
        seq.preemptions += 1
        self.waiting.appendleft(seq)
