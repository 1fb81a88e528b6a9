"""Continuous-batching scheduler: the policy half of the serving subsystem.

Reference frame: vLLM's scheduler / PaddleNLP's block-attention batch
builder. Every engine step serves ONE fixed token budget shared by chunked
prefill and decode (the MPK argument from PAPERS.md: collapse the ragged
request mix into one fixed-shape compiled program):

- **admission control / load shedding**: ``add_request`` raises
  :class:`RejectedError` the moment the wait queue exceeds
  ``FLAGS_serving_max_queue`` — backpressure surfaces at the edge instead
  of as unbounded latency;
- **chunked prefill**: long prompts are fed ``prefill_chunk`` tokens at a
  time, interleaved with running decodes in the same step, so admission
  never stalls in-flight tokens for a whole prompt's worth of compute;
- **preemption under block exhaustion**: when the KV pool cannot grow a
  running sequence, the lowest-priority / youngest sequence is evicted —
  its pages freed, its state reset to recompute-on-resume (prompt +
  generated tokens re-prefill when capacity returns, numerically exact).
  With a window pool beside the pool (a model with sliding-window layers)
  admission, growth and preemption look at both: a sequence runs only
  with its pages in each. With recurrent state (a model with state-space
  layers: `BlockManager(state_slots=...)`) a sequence also runs only with
  a state slot: `allocate_sequence` takes it or admits nobody, every way
  out of the running set (`_finish`, `_preempt`) gives it back through
  `free_sequence`, and a preempted sequence re-prefills from `num_computed`
  0, which is also what makes the device start it from a zero state;
- **deadlines & cancellation**: per-request absolute deadlines checked at
  every schedule point; expired or cancelled requests free their pages
  immediately and finish with reason ``"deadline"`` / ``"cancelled"``;
- **generation by diffusion over blocks** (``block_length`` Bd > 0, SDAR):
  a step no longer yields one token a sequence. A prompt is prefilled in
  chunks that end on multiples of Bd, up to its last whole block; its tail
  opens the first generated block. From then on a running sequence is
  scheduled Bd rows a tick, its open block, and charged Bd in the token
  budget, whether the engine runs a denoise or the commit forward on them.

The scheduler owns sequence state and the
:class:`~.block_manager.BlockManager`; the engine owns device state and
asks ``schedule()`` for the next mixed batch.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Tuple

from ...core import flags
from ...observability import emit as _emit
from ...observability import tracing as _tracing
from .block_manager import BlockManager, NoFreeBlocksError

__all__ = ["RejectedError", "DeadlineExceededError", "Sequence",
           "Completion", "ScheduledBatch", "Scheduler", "UNKNOWN"]

flags.define_flag("serving_max_queue", 128,
                  "Serving admission control: submissions beyond this many "
                  "waiting requests raise RejectedError (load shedding)")


class RejectedError(RuntimeError):
    """Load-shed signal: the serving queue is full. Clients should back
    off and retry; the request was NOT enqueued."""


class DeadlineExceededError(RuntimeError):
    """A request's deadline expired mid-flight: the scheduler freed its
    pages and finished it with reason ``"deadline"``. Raised through
    ``stream(rid)`` so streaming clients see a typed failure instead of a
    silently truncated token stream (``run()`` still returns the
    completion with ``finish_reason == "deadline"``)."""


WAITING, RUNNING, FINISHED = "waiting", "running", "finished"
# in `Sequence.tokens`: a position a dispatched tick will yield, whose id
# the host has not read yet (`Scheduler.on_dispatched`)
UNKNOWN = -1


@dataclass(eq=False)   # identity semantics: sequences live in sets/lists
class Sequence:
    rid: int
    prompt: List[int]
    max_new_tokens: int
    eos: int = -1                       # -1 = no eos
    priority: int = 0                   # higher = evicted later
    deadline: Optional[float] = None    # absolute time.monotonic()
    temperature: float = 0.0            # 0 = greedy
    top_p: float = 1.0
    seed: int = 0
    # LoRA adapter this request decodes through (None = base model);
    # pinned in the AdapterManager while the sequence is live
    adapter: Optional[str] = None
    # mutable state
    tokens: List[int] = field(default_factory=list)  # prompt + generated
    generated: List[int] = field(default_factory=list)
    num_computed: int = 0
    status: str = WAITING
    preemptions: int = 0
    arrival: float = 0.0
    first_token_at: Optional[float] = None
    last_token_at: Optional[float] = None
    finish_reason: Optional[str] = None
    # span context (host-side ints riding the object; never jitted args —
    # the zero-retrace contract of observability.tracing)
    trace_id: int = 0
    parent_span: int = 0
    _qw_span: Optional[object] = None   # open queue.wait span, if any
    # the request's time to its first token on the span clock
    # (time.perf_counter_ns, the ring's and, through a step's `perf_ns`,
    # the profile's): entry of `engine.submit`; the admission attempt in
    # `schedule()` that first took it (a re-admission after a preemption
    # keeps the first); and the engine's odometer of device time at its
    # first dispatch. Never read by the scheduler: deadlines and `arrival`
    # are monotonic()
    submit_ns: int = 0
    admit_ns: int = 0
    busy0_ns: Optional[int] = None
    # generation by diffusion over blocks (the engine's, for a config with
    # block_length > 0): the request's denoise forwards a block at most,
    # and the open block — its ids (a masked row carries mask_token_id),
    # whether each row is still masked (the sequence's own state, never
    # `id == mask_token_id`: a prompt may hold that id), and the denoise
    # forwards it has had. They outlive a preemption: the block's rows
    # depend only on what is committed before it. A block-diffusion tick
    # writes none of this, nor `num_computed`, before its harvest; the rows
    # of a prefill chunk or a commit forward that `num_computed` will take
    # then stand in `in_flight` from the dispatch on, for the next plan
    denoising_steps: int = 0
    block_ids: Optional[List[int]] = None
    block_masked: Optional[List[bool]] = None
    block_forwards: int = 0
    in_flight: int = 0

    def __post_init__(self):
        self.tokens = list(self.prompt)

    def remaining(self) -> int:
        return len(self.tokens) - self.num_computed

    def planned(self) -> int:
        """Positions computed once every dispatched tick is in: what the
        next tick of the sequence starts behind."""
        return self.num_computed + self.in_flight


@dataclass
class Completion:
    """What a finished request leaves behind (`engine.run()`, the router)."""
    rid: int
    prompt_tokens: List[int]
    output_tokens: List[int]
    finish_reason: str  # stop | length | deadline | cancelled | shed | ...


@dataclass
class ScheduledBatch:
    """One engine step's worth of work: per sequence, how many of its
    pending tokens to run (decode rows have n=1 and num_computed ==
    len(tokens)-1; prefill rows chew through larger chunks)."""
    items: List[Tuple[Sequence, int]]

    def __bool__(self):
        return bool(self.items)

    @property
    def total_tokens(self) -> int:
        return sum(n for _, n in self.items)


class Scheduler:
    def __init__(self, block_manager: BlockManager, token_budget: int,
                 max_batch: int, prefill_chunk: Optional[int] = None,
                 max_queue: Optional[int] = None, block_length: int = 0):
        if token_budget < 1 or max_batch < 1:
            raise ValueError("token_budget and max_batch must be >= 1")
        self.blocks = block_manager
        self.token_budget = int(token_budget)
        self.max_batch = int(max_batch)
        self.prefill_chunk = int(prefill_chunk or token_budget)
        self.block_length = int(block_length)
        if self.block_length:
            # chunks are whole blocks: the keys and values inside a block
            # depend on the whole block
            self.prefill_chunk -= self.prefill_chunk % self.block_length
            if min(self.prefill_chunk, self.token_budget) < self.block_length:
                raise ValueError(
                    f"block_length={block_length} needs a token_budget and "
                    f"a prefill_chunk of at least one block")
        self._max_queue = max_queue
        self.waiting: Deque[Sequence] = deque()
        self.running: List[Sequence] = []
        self._by_rid: Dict[int, Sequence] = {}
        self.stats = {"admitted": 0, "scheduled_steps": 0, "preemptions": 0,
                      "shed": 0, "deadline_expired": 0, "cancelled": 0}

    # -- admission --------------------------------------------------------
    @property
    def max_queue(self) -> int:
        if self._max_queue is not None:
            return self._max_queue
        return int(flags.flag_value("serving_max_queue"))

    def queue_depth(self) -> int:
        return len(self.waiting)

    def num_running(self) -> int:
        return len(self.running)

    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    def add_request(self, seq: Sequence):
        if len(self.waiting) >= self.max_queue:
            self.stats["shed"] += 1
            _emit("serving.shed", rid=seq.rid, queue_depth=len(self.waiting))
            raise RejectedError(
                f"serving queue full ({len(self.waiting)} waiting >= "
                f"FLAGS_serving_max_queue={self.max_queue}); request "
                f"{seq.rid} shed — back off and resubmit")
        seq.arrival = time.monotonic()
        seq._qw_span = _tracing.start_span("queue.wait", seq.trace_id,
                                           seq.parent_span, rid=seq.rid)
        self.waiting.append(seq)
        self._by_rid[seq.rid] = seq
        self.stats["admitted"] += 1
        _emit("serving.admit", rid=seq.rid, prompt_len=len(seq.prompt),
              queue_depth=len(self.waiting))

    def get(self, rid: int) -> Optional[Sequence]:
        return self._by_rid.get(rid)

    def cancel(self, rid: int) -> bool:
        seq = self._by_rid.get(rid)
        if seq is None or seq.status == FINISHED:
            return False
        self._finish(seq, "cancelled")
        self.stats["cancelled"] += 1
        _emit("serving.cancel", rid=rid)
        return True

    # -- lifecycle helpers ------------------------------------------------
    def _finish(self, seq: Sequence, reason: str):
        seq.status = FINISHED
        seq.finish_reason = reason
        if seq._qw_span is not None:   # finished without ever being scheduled
            _tracing.end_span(seq._qw_span, outcome=reason)
            seq._qw_span = None
        if seq in self.running:
            self.running.remove(seq)
        if seq in self.waiting:
            self.waiting.remove(seq)
        if self.blocks.has_sequence(seq.rid):
            self.blocks.free_sequence(seq.rid)

    def finish(self, seq: Sequence, reason: str):
        self._finish(seq, reason)

    def _preempt(self, seq: Sequence):
        """Evict a running sequence: free its pages, reset to
        recompute-on-resume (the whole prompt+generated re-prefills when
        capacity returns — exactness over cache-migration complexity)."""
        if seq.tokens[-1] == UNKNOWN or seq.in_flight:
            raise RuntimeError(
                f"sequence {seq.rid} has a row in flight: it re-prefills "
                "from its ids, so the engine plans a tick that preempts "
                "only with every tick harvested (next_fits)")
        self.blocks.free_sequence(seq.rid)
        seq.num_computed = 0
        seq.status = WAITING
        seq.preemptions += 1
        self.running.remove(seq)
        self.waiting.appendleft(seq)   # resumes ahead of new arrivals
        # back in the queue: a fresh queue.wait span covers the re-wait
        seq._qw_span = _tracing.start_span("queue.wait", seq.trace_id,
                                           seq.parent_span, rid=seq.rid,
                                           resumed=True)
        self.stats["preemptions"] += 1
        _emit("serving.preempt", rid=seq.rid,
              tokens=len(seq.tokens), priority=seq.priority)

    def _preempt_one(self, exclude) -> bool:
        """Evict the lowest-priority (then youngest) running sequence not
        in `exclude`; False when there is nothing left to evict."""
        victims = [s for s in self.running if s not in exclude]
        if not victims:
            return False
        victim = min(victims, key=lambda s: (s.priority, -s.arrival))
        self._preempt(victim)
        return True

    def _expire_deadlines(self) -> List[Sequence]:
        now = time.monotonic()
        expired = [s for s in list(self.running) + list(self.waiting)
                   if s.deadline is not None and now > s.deadline]
        for seq in expired:
            self._finish(seq, "deadline")
            self.stats["deadline_expired"] += 1
            _emit("serving.shed", rid=seq.rid, reason="deadline",
                  queue_depth=len(self.waiting))
        return expired

    # -- the step builder -------------------------------------------------
    def prefill_left(self, seq: Sequence) -> int:
        """Positions of `seq` still to prefill. Autoregressive: all that
        is not computed (a decode row is a prefill of one). Block
        diffusion: up to the last whole block of its tokens; at 0 the
        sequence is in its open block."""
        if not self.block_length:
            return seq.remaining()
        bd = self.block_length
        return len(seq.tokens) // bd * bd - seq.planned()

    def _chunk(self, seq: Sequence, budget: int) -> int:
        """Rows to run for `seq` in this step inside `budget`: a prefill
        chunk (whole blocks under block diffusion), else the open block."""
        left = self.prefill_left(seq)
        n = min(left, self.prefill_chunk, budget)
        if not self.block_length:
            return n
        if left > 0:
            return n - n % self.block_length
        return self.block_length if budget >= self.block_length else 0

    def schedule(self) -> Tuple[ScheduledBatch, List[Sequence]]:
        """Build the next mixed prefill+decode batch. Returns (batch,
        expired) where expired sequences hit their deadline and finished
        without compute."""
        expired = self._expire_deadlines()
        budget = self.token_budget
        items: List[Tuple[Sequence, int]] = []
        scheduled = set()

        # 1) running sequences first (decode steps and in-flight prefills):
        #    starving them for new admissions would throw away paid-for KV
        for seq in list(self.running):
            if budget <= 0 or len(items) >= self.max_batch:
                break
            if seq.status != RUNNING:   # preempted by an earlier iteration
                continue
            n = self._chunk(seq, budget)
            if n <= 0:
                continue
            while True:
                try:
                    self.blocks.ensure_capacity(seq.rid,
                                                seq.planned() + n)
                    break
                except NoFreeBlocksError:
                    # block exhaustion: evict the lowest-priority running
                    # sequence that is not already in this step's batch
                    if not self._preempt_one(exclude=scheduled | {seq}):
                        # nothing evictable but `seq` itself: park it and
                        # let capacity recover as the batch drains
                        self._preempt(seq)
                        break
            if seq.status != RUNNING:
                continue
            items.append((seq, n))
            scheduled.add(seq)
            budget -= n

        # 2) admit waiting sequences into leftover budget (chunked prefill)
        while (self.waiting and budget > 0 and len(items) < self.max_batch
               and budget >= self.block_length):
            seq = self.waiting[0]
            # queue wait ends where the attempt that admits begins: hashing
            # the prompt's pages is the host at work on it, not waiting
            now_ns = time.perf_counter_ns()
            try:
                cached = self.blocks.allocate_sequence(seq.rid, seq.tokens)
            except NoFreeBlocksError:
                break  # never evict running work for new admissions
            if cached:
                seq.num_computed = cached
                _emit("serving.prefix_hit", rid=seq.rid, tokens=cached)
            n = self._chunk(seq, budget)
            if self.blocks.window_blocks:
                # the window pool's pages come a chunk at a time: the first
                # chunk's now, or the sequence is not admitted
                try:
                    self.blocks.ensure_capacity(seq.rid,
                                                seq.planned() + n)
                except NoFreeBlocksError:
                    self.blocks.free_sequence(seq.rid)
                    break
            self.waiting.popleft()
            seq.status = RUNNING
            if not seq.admit_ns:
                seq.admit_ns = now_ns
            if seq._qw_span is not None:
                _tracing.end_span(seq._qw_span, end_ns=now_ns)
                seq._qw_span = None
            self.running.append(seq)
            items.append((seq, n))
            budget -= n

        self.stats["scheduled_steps"] += 1 if items else 0
        return ScheduledBatch(items), expired

    def next_fits(self) -> bool:
        """Whether the next `schedule()` can grow every running sequence
        by its next chunk out of free pages, i.e. will preempt nobody
        (admissions never preempt). An upper bound: each sequence is
        charged its chunk at the whole token budget."""
        need = wneed = 0
        for seq in self.running:
            n = self._chunk(seq, self.token_budget)
            grow = self.blocks.growth(seq.rid, seq.planned() + n)
            need, wneed = need + grow[0], wneed + grow[1]
        return self.blocks.can_allocate(need, wneed)

    def on_dispatched(self, seq: Sequence, n: int) -> bool:
        """The dispatch half of a tick's progress, by count: `n` rows of
        `seq` are on their way, so `num_computed` advances now, and if
        they reach the end of its tokens the tick yields it one more, a
        position whose id stays `UNKNOWN` until the tick is harvested
        (returns True). The scheduler can plan the next tick from this."""
        seq.num_computed += n
        if seq.num_computed < len(seq.tokens):
            return False
        seq.tokens.append(UNKNOWN)
        return True

    def on_harvested(self, seq: Sequence, upto: int):
        """The harvest half: the tick that took `seq` to `upto` computed
        positions has been read, so the pages they fill are registered in
        the prefix cache. Every id below `upto` is known by now; the
        block manager must never hash an `UNKNOWN` one."""
        self.blocks.register_computed(seq.rid, seq.tokens, upto)
        # with a window pool: the pages behind every window to come go back
        self.blocks.release_behind(seq.rid, upto)

    def on_computed(self, seq: Sequence, n: int):
        """Both halves at once, at harvest: for a tick whose progress is
        no count known at dispatch (a speculative chunk advances by the
        accepted length, a block-diffusion block when it is committed)."""
        seq.num_computed += n
        self.on_harvested(seq, seq.num_computed)

    def append_token(self, seq: Sequence, token: int,
                     at: Optional[int] = None):
        """At harvest: a token read from the device extends the sequence.
        `at` is the position `on_dispatched` left `UNKNOWN` for it when the
        tick was dispatched (its KV may already be on its way, computed by
        the next tick from the id on the device); None appends it."""
        seq.generated.append(int(token))
        if at is None:
            seq.tokens.append(int(token))
        else:
            seq.tokens[at] = int(token)
        now = time.monotonic()
        if seq.first_token_at is None:
            seq.first_token_at = now
        seq.last_token_at = now
