"""Continuous-batching serve scheduler (TensorRT-LLM-style in-flight batching).

The static :meth:`Engine.generate` pads every request in a batch to the
slowest sequence: one long prompt stalls the whole batch, and finished
sequences keep burning decode FLOPs until the last one ends.  The
:class:`Scheduler` instead admits variable-length requests into a fixed pool
of KV-cache slots (:mod:`repro.serve.kv_slots`) and runs one *pool-shaped*
decode step per iteration:

  admit   : while a slot is free and requests wait, bind the next request to
            a slot and run its prompt through fixed-shape chunked prefill
            (``Engine.prefill_chunk_step``) — ceil(S/C) calls of one compiled
            [1, C] executable, never a per-prompt-length recompile;
  decode  : ONE batched decode step over all n_slots rows with per-slot
            positions (``decode_step`` accepts a [B] position vector);
  retire  : sequences hitting EOS / their token budget complete immediately
            and free their slot for the next admission — completions stream
            out as they happen (:meth:`Scheduler.run_iter`).

Prefill and decode steps are traced under different dispatch phases, so the
sparse operators inside run the per-phase implementations the engine pinned
at build time.  Attention-cache families only (recurrent state caches have no
random-access rows to slot into); everything else should keep using the
static engine — same Engine object, same weights, same step primitives.

Request lifecycle (see ``docs/robustness.md`` for the state machine): every
request ends at exactly one terminal :data:`STATUSES` value.  ``deadline_s``
expires a request (queued or in flight) relative to submission;
:meth:`Scheduler.cancel` withdraws one by uid; injected faults
(:mod:`repro.fault`) fail or preempt requests without ever leaking a slot or
page; and under the paged tier's ``alloc="grow"`` policy, page exhaustion
preempts the latest-admitted request — its pages are freed and it is
re-enqueued with its generated prefix appended to the prompt, so the greedy
re-prefill reproduces the identical continuation (preempt -> restore is
token-transparent).
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable, Dict, Iterable, Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import fault as _fault
from repro.models import registry as reg
from repro.obs import metrics as _om
from repro.obs import trace as _ot
from repro.serve.engine import Engine
from repro.serve.kv_pages import PageError, PagePool, pack_prompts
from repro.serve.kv_slots import SlotPool

#: Terminal request statuses (every Completion carries exactly one).
STATUSES = ("ok", "timeout", "cancelled", "failed", "preempted")


@dataclasses.dataclass
class Request:
    """One generation request: a prompt, a token budget, and an optional
    deadline (seconds after submission; expiry retires the request with
    status ``"timeout"`` whether it is queued or in flight)."""

    uid: int
    prompt: np.ndarray  # [S] int32
    max_new_tokens: int = 32
    deadline_s: Optional[float] = None

    def __post_init__(self):
        self.prompt = np.asarray(self.prompt, np.int32).reshape(-1)
        if self.prompt.size == 0:
            raise ValueError(f"request {self.uid}: empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError(f"request {self.uid}: max_new_tokens < 1")
        if self.deadline_s is not None and self.deadline_s <= 0:
            raise ValueError(f"request {self.uid}: deadline_s <= 0")


@dataclasses.dataclass
class Completion:
    """A finished request: generated tokens + latency breakdown + terminal
    status.  Non-``ok`` completions carry whatever was generated before the
    terminal event (empty for never-admitted requests)."""

    uid: int
    prompt_len: int
    tokens: np.ndarray  # [n_generated] int32, EOS included when emitted
    t_submit: float
    t_first: float  # first token sampled (end of this request's prefill)
    t_done: float
    status: str = "ok"

    @property
    def n_generated(self) -> int:
        return int(self.tokens.shape[0])

    @property
    def ttft_s(self) -> float:
        return self.t_first - self.t_submit

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_submit


class RequestQueue:
    """FIFO admission queue."""

    def __init__(self, requests: Iterable[Request] = ()):
        self._q = collections.deque(requests)

    def push(self, req: Request) -> None:
        self._q.append(req)

    def push_front(self, req: Request) -> None:
        """Re-enqueue at the head (preempted requests resume first, keeping
        the restore close to FIFO order)."""
        self._q.appendleft(req)

    def pop(self) -> Request:
        return self._q.popleft()

    def peek(self) -> Request:
        """Head of the queue without removing it (paged admission checks the
        head's page cost before committing)."""
        return self._q[0]

    def take(self, pred) -> List[Request]:
        """Remove and return every queued request matching ``pred``,
        preserving the order of the rest (deadline/cancel sweeps)."""
        taken = [r for r in self._q if pred(r)]
        if taken:
            self._q = collections.deque(r for r in self._q if not pred(r))
        return taken

    def __len__(self) -> int:
        return len(self._q)

    def __bool__(self) -> bool:
        return bool(self._q)


@dataclasses.dataclass
class _InFlight:
    """Scheduler-side state of an admitted request.  ``admit_seq`` orders
    admissions globally (the preemption policy's victim = highest)."""

    req: Request
    t_first: float
    tokens: List[int]
    admit_seq: int = 0


class Scheduler:
    """Slot-based continuous batching on top of an :class:`Engine`.

    n_slots        : decode batch width == KV slot count (one compiled decode
                     executable for the whole run)
    max_len        : per-slot KV rows; defaults to the trace's
                     max(prompt_len + max_new_tokens)
    prefill_chunk  : chunked-prefill width C (admission latency knob: smaller
                     chunks interleave admissions and decode more finely;
                     contiguous mode only)
    paged          : page the KV seq dimension (serve.kv_pages): admission is
                     accounted in free *pages* — a short request costs
                     ceil((prompt+budget)/page_size) pages, not max_len rows
                     — and prompts prefill as ONE packed padding-free stream
    page_size      : KV rows per page; None lets dispatch.choose_page_size
                     race the PAGED_ATTN_GEOMETRY layouts for this shape
    kv_budget_rows : total physical KV rows for the paged pool (the memory
                     budget admission is charged against); defaults to
                     n_slots * max_len rounded up to whole pages per slot,
                     i.e. the contiguous pool's footprint: every slot can
                     hold a max-length request at any page size
    alloc          : paged allocation policy. ``"reserve"`` (default) maps a
                     request's full prompt+budget up front — admitted never
                     OOMs, but EOS-early requests strand their unused tail
                     until retire (measured by the ``pages_stranded``
                     counter).  ``"grow"`` maps prompt pages at admission and
                     grows one row ahead of decode; exhaustion triggers the
                     preemption policy (victim = latest-admitted, restored
                     token-identically via prefix re-prefill)
    max_restores   : per-request preemption budget before it retires with
                     status ``"failed"`` (livelock guard under injected
                     allocator faults)
    """

    def __init__(self, engine: Engine, *, n_slots: int = 4,
                 max_len: Optional[int] = None, prefill_chunk: int = 16,
                 paged: bool = False, page_size: Optional[int] = None,
                 kv_budget_rows: Optional[int] = None,
                 alloc: str = "reserve", max_restores: int = 8):
        cfg = engine.cfg
        if cfg.is_encoder_decoder or cfg.block_pattern != "attn":
            raise ValueError(
                f"continuous batching requires a decoder-only attention "
                f"family (slot-addressable KV rows); {cfg.name} has "
                f"block_pattern={cfg.block_pattern!r}. Use Engine.generate.")
        if prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")
        if page_size is not None and page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if alloc not in ("reserve", "grow"):
            raise ValueError(f"alloc must be 'reserve' or 'grow', got {alloc!r}")
        if alloc == "grow" and not paged:
            raise ValueError("alloc='grow' requires paged=True (the "
                             "contiguous pool has nothing to grow)")
        self.engine = engine
        self.n_slots = n_slots
        self.max_len = max_len
        self.prefill_chunk = prefill_chunk
        self.paged = bool(paged)
        self.page_size = page_size
        self.kv_budget_rows = kv_budget_rows
        self.alloc = alloc
        self.max_restores = int(max_restores)
        self._cancelled: set = set()
        # Always-on private metrics registry backing the ``stats`` view —
        # live counters, so a partially-consumed run_iter generator reports
        # consistent numbers at any point (and zeros before the first run,
        # full key set included, instead of the old empty/stale dict).
        self.metrics = _om.Registry()
        for name in ("decode_steps", "decode_s", "generated_tokens",
                     "completed_requests", "preemptions", "iter_faults",
                     "pages_stranded"):
            self.metrics.counter(name)
        for name in STATUSES:
            self.metrics.counter(f"retired_{name}")
        for name in ("requests", "total_s", "queue_depth", "slots_active",
                     "pages_active", "pages_free", "page_fragmentation",
                     "pages_peak"):
            self.metrics.gauge(name)
        for name in ("ttft_s", "tpot_s", "latency_s"):
            self.metrics.histogram(name)
        # Re-plan dispatch for the geometry this scheduler actually traces:
        # chunked prefill runs [1, C]-row operands (C capped by max_len, the
        # same cap run_iter applies) and pool decode [n_slots] rows — the
        # engine's build-time hints describe the *static* path's shapes, so
        # without this the scheduler's phase-tagged lookups would miss the
        # plan and fall back to the heuristic.
        from repro import dispatch as _dispatch

        c_w = min(prefill_chunk, max_len) if max_len is not None else prefill_chunk
        self.dispatch_plan = _dispatch.plan_params(
            engine.params,
            phase_hints={"prefill": c_w, "decode": n_slots},
            profile=engine.scfg.profile_dispatch)
        engine.dispatch_plan.update(self.dispatch_plan)
        # pool-cache write-back for admissions: donate the pool so XLA can
        # update the slot's rows in place instead of copying the whole
        # [L, n_slots, max_len, KV, D] cache per admitted request
        def _writeback(full, part, slot):
            def one(f, p):
                idx = (jnp.zeros((), jnp.int32), slot) + \
                    (jnp.zeros((), jnp.int32),) * (f.ndim - 2)
                return jax.lax.dynamic_update_slice(f, p.astype(f.dtype), idx)

            return jax.tree_util.tree_map(one, full, part)

        self._writeback = jax.jit(_writeback, donate_argnums=(0,))

    # ------------------------------------------------------------------

    def cancel(self, uid: int) -> None:
        """Withdraw request ``uid``: queued, it never admits; in flight, it
        retires at the next iteration boundary — either way its Completion
        carries status ``"cancelled"``.  Unknown uids are ignored (the
        request may already have finished)."""
        self._cancelled.add(uid)

    @property
    def stats(self) -> Dict[str, float]:
        """Latency/throughput counters as a derived view over
        :attr:`metrics` — the pre-obs ad-hoc dict's key set (plus latency
        percentiles and per-status retire counters), consistent at ANY
        point: before the first run it is all-zeros, and while a
        :meth:`run_iter` generator is partially consumed it reflects the
        work done so far."""
        c = self.metrics
        gen = c.counter("generated_tokens").value
        dec_s = c.counter("decode_s").value
        out = {
            "decode_steps": c.counter("decode_steps").value,
            "decode_s": dec_s,
            "total_s": c.gauge("total_s").value,
            "generated_tokens": gen,
            "requests": c.gauge("requests").value,
            "completed_requests": c.counter("completed_requests").value,
            "decode_tok_s": gen / dec_s if dec_s > 0 else 0.0,
            "preemptions": c.counter("preemptions").value,
            "iter_faults": c.counter("iter_faults").value,
        }
        for name in STATUSES:
            out[f"retired_{name}"] = c.counter(f"retired_{name}").value
        for h in ("ttft_s", "tpot_s", "latency_s"):
            hist = c.histogram(h)
            out[f"{h[:-2]}_p50_s"] = hist.percentile(50)
            out[f"{h[:-2]}_p99_s"] = hist.percentile(99)
        return out

    @property
    def page_stats(self) -> Dict[str, float]:
        """Paged-pool occupancy view (all zeros in contiguous mode)."""
        m = self.metrics
        ps = self.page_size or 0
        peak = m.gauge("pages_peak").value
        return {
            "page_size": float(ps),
            "pages_active": m.gauge("pages_active").value,
            "pages_free": m.gauge("pages_free").value,
            "page_fragmentation": m.gauge("page_fragmentation").value,
            "pages_peak": peak,
            "kv_rows_hwm": peak * ps,
            "pages_stranded": m.counter("pages_stranded").value,
        }

    def run(self, requests: Iterable[Request],
            log_fn: Optional[Callable[[str], None]] = None,
            should_drain: Optional[Callable[[], bool]] = None,
            heartbeat: Optional[Callable[[], None]] = None) -> List[Completion]:
        """Serve every request; returns completions in finish order (see
        :meth:`run_iter` for the streaming form). Latency/throughput counters
        land in ``self.stats``."""
        return list(self.run_iter(requests, log_fn=log_fn,
                                  should_drain=should_drain,
                                  heartbeat=heartbeat))

    def run_iter(self, requests: Iterable[Request],
                 log_fn: Optional[Callable[[str], None]] = None,
                 should_drain: Optional[Callable[[], bool]] = None,
                 heartbeat: Optional[Callable[[], None]] = None
                 ) -> Iterator[Completion]:
        """Generator form of :meth:`run`: yields each Completion the moment
        its admit/decode iteration ends, while later requests are still
        decoding.

        ``should_drain`` is polled once per iteration; once it returns True
        admissions stop, in-flight requests decode to completion, and
        still-queued requests flush with status ``"cancelled"``
        (``"preempted"`` if they hold a restore prefix) — the SIGTERM
        graceful-drain hook ``launch.serve`` wires to
        ``train.fault.PreemptionGuard``.  ``heartbeat`` is called once per
        iteration (wire it to ``StepWatchdog.beat`` for a scheduler-iteration
        watchdog)."""
        reqs = list(requests)
        log = log_fn or (lambda _msg: None)
        m = self.metrics
        m.reset()
        m.gauge("requests").set(len(reqs))
        if not reqs:
            return
        engine, cfg = self.engine, self.engine.cfg
        needed = max(len(r.prompt) + r.max_new_tokens for r in reqs)
        if self.max_len is None:
            # the padded final prefill chunk writes rows up to
            # round_up(prompt, C); size the cache so that write always fits
            # (dynamic_update_slice clamps a too-high start *backwards*,
            # which would silently corrupt earlier rows)
            c_w = self.prefill_chunk
            pad_end = max(-(-len(r.prompt) // c_w) * c_w for r in reqs)
            max_len = max(needed, pad_end)
        else:
            max_len = self.max_len
            c_w = min(self.prefill_chunk, max_len)
            if needed > max_len:
                raise ValueError(
                    f"max_len={max_len} cannot hold the longest request "
                    f"(prompt+budget={needed})")
            pad_end = max(-(-len(r.prompt) // c_w) * c_w for r in reqs)
            if pad_end > max_len:
                raise ValueError(
                    f"prefill_chunk={c_w} pads the longest prompt to "
                    f"{pad_end} rows > max_len={max_len}; lower "
                    f"prefill_chunk or raise max_len")
        n = self.n_slots
        queue = RequestQueue(reqs)
        pool = SlotPool(n, max_len)
        pages: Optional[PagePool] = None
        tables_np = None
        ps = max_pages = 0
        if self.paged:
            if self.page_size is None:
                # cache-layout plan: race the PAGED_ATTN_GEOMETRY page sizes
                # for this serving shape (heuristic rung when unprofiled)
                from repro import dispatch as _dispatch

                self.page_size = _dispatch.choose_page_size(
                    cfg.padded_heads, cfg.n_kv_heads, cfg.resolved_head_dim,
                    max_len, q_rows=n, dtype=cfg.dtype,
                    profile=bool(engine.scfg.profile_dispatch))
            ps = self.page_size
            max_pages = -(-max_len // ps)
            budget_rows = self.kv_budget_rows or n * max_pages * ps
            n_pages = budget_rows // ps
            if n_pages < max_pages:
                raise ValueError(
                    f"kv_budget_rows={budget_rows} ({n_pages} pages of {ps}) "
                    f"cannot hold one max-length request ({max_pages} pages)")
            pages = PagePool(n_pages, ps)
            cache = reg.paged_cache_init_fn(cfg, n_pages, ps)()
        else:
            cache = reg.cache_init_fn(cfg, n, max_len)()
        tok_buf = np.zeros((n,), np.int32)
        inflight: Dict[int, _InFlight] = {}
        key = jax.random.PRNGKey(engine.scfg.seed)
        eos = engine.scfg.eos_id
        t0 = time.perf_counter()
        c_steps = m.counter("decode_steps")
        c_decode_s = m.counter("decode_s")
        c_gen = m.counter("generated_tokens")
        c_done = m.counter("completed_requests")
        c_preempt = m.counter("preemptions")
        c_stranded = m.counter("pages_stranded")
        g_total = m.gauge("total_s")
        h_ttft, h_tpot, h_lat = (m.histogram("ttft_s"), m.histogram("tpot_s"),
                                 m.histogram("latency_s"))
        admit_seq = 0  # monotonic admission counter (preemption victim order)
        grow = pages is not None and self.alloc == "grow"

        def finish(comp: Completion) -> Completion:
            """Shared retire bookkeeping: counters, histograms (ok only, so
            cancellations don't skew latency percentiles), obs events."""
            c_done.inc()
            m.counter(f"retired_{comp.status}").inc()
            self._cancelled.discard(comp.uid)  # consume the cancel request
            if comp.status == "ok":
                tpot = (comp.t_done - comp.t_first) / max(comp.n_generated - 1, 1)
                h_ttft.observe(comp.ttft_s)
                h_tpot.observe(tpot)
                h_lat.observe(comp.latency_s)
            _ot.instant("serve.retire", uid=comp.uid, status=comp.status,
                        generated=comp.n_generated,
                        ttft_s=round(comp.ttft_s, 6),
                        latency_s=round(comp.latency_s, 6))
            log(f"[retire] uid={comp.uid} status={comp.status} "
                f"generated={comp.n_generated} latency={comp.latency_s:.3f}s")
            return comp

        def retire(idx: int, status: str = "ok") -> Completion:
            st = inflight.pop(idx)
            if pages is not None:
                if not grow:
                    # reserve policy: measure (and explicitly release) the
                    # unused tail of the upfront reservation the moment the
                    # request ends, so pages_stranded records how much of the
                    # budget EOS-early requests never touched
                    c_stranded.inc(pages.release_unused(idx))
                pages.free(idx)
            pool.free(idx)
            return finish(Completion(
                uid=st.req.uid,
                prompt_len=getattr(st.req, "_orig_prompt_len",
                                   len(st.req.prompt)),
                tokens=np.asarray(st.tokens, np.int32), t_submit=t0,
                t_first=st.t_first, t_done=time.perf_counter(),
                status=status))

        def finish_queued(req: Request, status: str) -> Completion:
            """Terminal completion for a request that is not in flight
            (never admitted, or preempted and not restored).  Carries the
            restore prefix — tokens generated before preemption are not
            lost."""
            now = time.perf_counter()
            prefix = getattr(req, "_prefix", None)
            return finish(Completion(
                uid=req.uid,
                prompt_len=getattr(req, "_orig_prompt_len", len(req.prompt)),
                tokens=np.asarray([] if prefix is None else prefix, np.int32),
                t_submit=t0, t_first=getattr(req, "_t_first", now),
                t_done=now, status=status))

        def preempt(idx: int, reason: str) -> None:
            """Preemption policy: free the victim's slot+pages and re-enqueue
            it at the queue head with its generated prefix appended to the
            prompt.  Greedy re-prefill over prompt+prefix reproduces the
            identical continuation, so a restored request's final tokens are
            token-identical to an uninterrupted run."""
            st = inflight.pop(idx)
            pool.free(idx)
            if pages is not None:
                pages.free(idx)
            base = st.req
            orig_len = getattr(base, "_orig_prompt_len", len(base.prompt))
            gen = np.asarray(st.tokens, np.int32)
            restored = Request(
                uid=base.uid,
                prompt=np.concatenate([base.prompt[:orig_len], gen]),
                max_new_tokens=base.max_new_tokens,
                deadline_s=base.deadline_s)
            restored._orig_prompt_len = orig_len
            restored._prefix = gen
            restored._t_first = st.t_first
            restored._restores = getattr(base, "_restores", 0) + 1
            queue.push_front(restored)
            c_preempt.inc()
            _ot.instant("serve.preempt", uid=base.uid, slot=idx,
                        generated=int(gen.shape[0]),
                        restores=restored._restores, reason=reason[:120])
            log(f"[preempt] uid={base.uid} slot={idx} "
                f"generated={gen.shape[0]} ({reason})")

        def set_page_gauges() -> None:
            m.gauge("pages_active").set(pages.n_mapped)
            m.gauge("pages_free").set(pages.n_free)
            m.gauge("page_fragmentation").set(pages.fragmentation())
            m.gauge("pages_peak").set(pages.peak_pages)

        it = 0
        draining = False
        while queue or pool.n_active:
            if heartbeat is not None:
                heartbeat()
            try:
                _fault.maybe_fail("scheduler.iter", it=it)
            except _fault.InjectedFault:
                # transient iteration hiccup: nothing was mutated yet, so the
                # iteration simply re-runs (the site's probe counter advanced,
                # so deterministic schedules do not re-fire)
                m.counter("iter_faults").inc()
                _ot.instant("serve.iter_fault", it=it)
                it += 1
                continue
            # Completions are collected per iteration and yielded after the
            # iteration span closes — an open span across a yield would
            # interleave with whatever the consumer traces between steps and
            # break B/E nesting.
            done_now: List[Completion] = []
            with _ot.span("serve.iter", it=it) as isp:
                if not draining and should_drain is not None and should_drain():
                    draining = True
                    _ot.instant("serve.drain", it=it, queued=len(queue),
                                active=pool.n_active)
                    log(f"[drain] admissions stopped; {pool.n_active} in "
                        f"flight, {len(queue)} queued")

                # -- lifecycle sweep: cancellations + deadline expiries -----
                now = time.perf_counter()

                def _expired(r: Request) -> bool:
                    return r.deadline_s is not None and now - t0 > r.deadline_s

                with _ot.span("serve.sweep"):
                    for r in queue.take(
                            lambda r: r.uid in self._cancelled or _expired(r)):
                        status = ("cancelled" if r.uid in self._cancelled
                                  else "timeout")
                        done_now.append(finish_queued(r, status))
                    for idx in sorted(inflight):
                        st = inflight[idx]
                        if st.req.uid in self._cancelled:
                            done_now.append(retire(idx, "cancelled"))
                        elif _expired(st.req):
                            done_now.append(retire(idx, "timeout"))

                def admit_token(req, slot, tok):
                    """Post-prefill bookkeeping shared by both admission
                    paths: the prompt's first sampled token either retires
                    the request on the spot or seeds its decode feed.
                    Restored requests resume their pre-preemption token list
                    and first-token time."""
                    nonlocal admit_seq
                    c_gen.inc()
                    prefix = getattr(req, "_prefix", None)
                    toks = ([] if prefix is None else
                            [int(t) for t in prefix]) + [tok]
                    admit_seq += 1
                    inflight[slot.index] = _InFlight(
                        req=req,
                        t_first=getattr(req, "_t_first", None)
                        or time.perf_counter(),
                        tokens=toks, admit_seq=admit_seq)
                    log(f"[admit] uid={req.uid} slot={slot.index} "
                        f"prompt={len(req.prompt)} budget={req.max_new_tokens}")
                    if ((eos is not None and tok == eos)
                            or len(toks) >= req.max_new_tokens):
                        done_now.append(retire(slot.index))
                    else:
                        tok_buf[slot.index] = tok

                if pages is not None and not draining:
                    # -- paged admission: free-PAGE accounting, then ONE
                    # packed padding-free prefill over every admitted
                    # prompt (exact-shape stream, zero pad-token FLOPs) ----
                    admitted = []
                    while queue and pool.n_free:
                        head = queue.peek()
                        # grow policy maps the prompt only; the budget is
                        # claimed page-by-page as decode advances
                        need = (len(head.prompt) if grow
                                else len(head.prompt) + head.max_new_tokens)
                        if not pages.can_admit(need):
                            break  # FIFO: the head blocks on memory
                        req = queue.pop()
                        slot = pool.alloc(req.uid)
                        try:
                            pages.alloc(slot.index, need, request_id=req.uid)
                        except (PageError, _fault.InjectedFault) as e:
                            # allocator fault (injected or real): this
                            # admission fails terminally; the pool stays
                            # consistent because alloc raises pre-mutation
                            pool.free(slot.index)
                            done_now.append(finish_queued(req, "failed"))
                            log(f"[fail] uid={req.uid} admission alloc: {e}")
                            continue
                        admitted.append((req, slot))
                    if admitted:
                        packed = pack_prompts(
                            [r.prompt for r, _ in admitted],
                            [s.index for _, s in admitted])
                        tables_np = pages.table_array(n, max_pages)
                        try:
                            with _ot.span("serve.admit", n=len(admitted),
                                          tokens=packed.total_tokens,
                                          packed=True):
                                logits, cache = engine.packed_prefill_step(
                                    cache, packed, tables_np, page_size=ps)
                                for i, (req, slot) in enumerate(admitted):
                                    slot.pos = len(req.prompt)
                                    pages.advance(slot.index, len(req.prompt))
                                    key, k = jax.random.split(key)
                                    tok = int(np.asarray(
                                        engine.sample(logits[i:i + 1], k))[0])
                                    admit_token(req, slot, tok)
                        except _fault.InjectedFault as e:
                            # unrecoverable injected prefill failure (the
                            # dispatch ladder is exhausted): every admission
                            # in this packed batch fails terminally
                            for req, slot in admitted:
                                if slot.index in inflight:
                                    done_now.append(
                                        retire(slot.index, "failed"))
                                else:
                                    pages.free(slot.index)
                                    pool.free(slot.index)
                                    done_now.append(
                                        finish_queued(req, "failed"))
                            log(f"[fail] packed prefill: {e}")
                elif not draining:
                    # -- contiguous admission: chunked prefill per slot ---
                    while queue and pool.n_free:
                        req = queue.pop()
                        slot = pool.alloc(req.uid)
                        try:
                            with _ot.span("serve.admit", uid=req.uid,
                                          prompt=len(req.prompt),
                                          budget=req.max_new_tokens) as asp:
                                logits, cache = self._prefill_into(
                                    cache, slot.index, req.prompt, c_w)
                                slot.pos = len(req.prompt)
                                key, k = jax.random.split(key)
                                tok = int(np.asarray(
                                    engine.sample(logits, k))[0])
                                asp.set(slot=slot.index)
                        except _fault.InjectedFault as e:
                            pool.free(slot.index)
                            done_now.append(finish_queued(req, "failed"))
                            log(f"[fail] uid={req.uid} prefill: {e}")
                            continue
                        admit_token(req, slot, tok)
                m.gauge("queue_depth").set(len(queue))
                m.gauge("slots_active").set(pool.n_active)
                if pages is not None:
                    set_page_gauges()

                if grow and pool.n_active:
                    # -- grow-on-demand: map the next decode row for every
                    # live sequence; exhaustion (real or injected) invokes
                    # the preemption policy until the grow fits ------------
                    pos_now = pool.positions()
                    for idx in sorted(inflight):
                        while idx in inflight:
                            try:
                                pages.grow(idx, int(pos_now[idx]) + 1)
                                break
                            except (PageError, _fault.InjectedFault) as e:
                                victim = max(
                                    inflight,
                                    key=lambda i: inflight[i].admit_seq)
                                vst = inflight[victim]
                                if (getattr(vst.req, "_restores", 0)
                                        >= self.max_restores):
                                    done_now.append(retire(victim, "failed"))
                                else:
                                    preempt(victim, reason=str(e))
                    set_page_gauges()

                if pool.n_active:
                    # -- one pool-shaped decode step ----------------------
                    pos_vec = pool.positions()
                    t1 = time.perf_counter()
                    try:
                        with _ot.span("serve.decode", active=pool.n_active,
                                      paged=bool(pages is not None)):
                            if pages is not None:
                                # tables rebuilt every iteration: a retire
                                # frees pages a NEW admission may re-map, and
                                # a stale table would route an inactive
                                # slot's decode write into the new owner's
                                # live page
                                with _ot.span("serve.tables"):
                                    tables_np = pages.table_array(n, max_pages)
                            # enqueue the step and the sampling ops; the
                            # host blocks only on the sampled tokens
                            with _ot.span("serve.dispatch"):
                                if pages is not None:
                                    logits, cache = engine.paged_decode_step(
                                        cache, tok_buf[:, None], pos_vec,
                                        tables_np, page_size=ps)
                                else:
                                    logits, cache = engine.decode_step(
                                        cache, jnp.asarray(tok_buf[:, None]),
                                        jnp.asarray(pos_vec))
                                key, k = jax.random.split(key)
                                sampled = engine.sample(logits, k)
                            with _ot.span("serve.wait"):
                                toks = np.asarray(sampled)
                            dt = time.perf_counter() - t1
                    except _fault.InjectedFault as e:
                        # the decode step itself is unservable (ladder
                        # exhausted at trace time — donated buffers are
                        # never consumed by a failed trace): every in-flight
                        # request ends terminally rather than wedging
                        for idx in sorted(inflight):
                            done_now.append(retire(idx, "failed"))
                        log(f"[fail] decode step: {e}")
                    else:
                        c_decode_s.inc(dt)
                        c_steps.inc()

                        # -- retire finished sequences, advance the rest --
                        with _ot.span("serve.advance"):
                            for idx in sorted(inflight):
                                st = inflight[idx]
                                pool.advance(idx)  # the step wrote its token
                                if pages is not None:
                                    pages.advance(idx)  # bounds-checked
                                tok = int(toks[idx])
                                st.tokens.append(tok)
                                c_gen.inc()
                                if ((eos is not None and tok == eos)
                                        or len(st.tokens)
                                        >= st.req.max_new_tokens):
                                    done_now.append(retire(idx))
                                else:
                                    tok_buf[idx] = tok

                if draining and not pool.n_active and queue:
                    # graceful drain: flush never-to-be-admitted requests
                    # with a terminal status (restored prefixes survive in
                    # the completion tokens)
                    for r in queue.take(lambda _r: True):
                        status = ("preempted"
                                  if getattr(r, "_prefix", None) is not None
                                  else "cancelled")
                        done_now.append(finish_queued(r, status))
                isp.set(retired=len(done_now))
            g_total.set(time.perf_counter() - t0)
            for comp in done_now:
                yield comp
            it += 1

        g_total.set(time.perf_counter() - t0)
        if pages is not None:
            pages.check_invariants()  # end-of-run: no leak survives retire
            set_page_gauges()

    # ------------------------------------------------------------------

    def _prefill_into(self, cache, slot: int, prompt: np.ndarray, c_w: int):
        """Chunked prefill of one prompt into one slot's cache rows.

        Slices the slot's [L, 1, S_max, KV, D] view out of the pool cache,
        streams fixed-shape [1, C] chunks through ``prefill_chunk_step``
        (the final chunk is right-padded; pad rows land beyond the prompt
        and are overwritten by decode before they are ever attended), then
        writes the view back.  Returns (last-real-token logits, cache).
        """
        s_len = int(len(prompt))
        sub = jax.tree_util.tree_map(lambda a: a[:, slot:slot + 1], cache)
        logits = None
        with _ot.span("serve.prefill", slot=slot, prompt=s_len,
                      chunks=-(-s_len // c_w), chunk_w=c_w):
            for start in range(0, s_len, c_w):
                chunk = np.asarray(prompt[start:start + c_w], np.int32)[None, :]
                if chunk.shape[1] < c_w:
                    chunk = np.pad(chunk, ((0, 0), (0, c_w - chunk.shape[1])))
                logits, sub = self.engine.prefill_chunk_step(
                    sub, chunk, start, with_logits=start + c_w >= s_len)
        last = (s_len - 1) % c_w
        # sub is the last chunk call's jit output (fresh buffers), so
        # donating the pool here can never delete a buffer sub still uses
        cache = self._writeback(cache, sub, jnp.asarray(slot, jnp.int32))
        return logits[:, last:last + 1], cache


def latency_percentiles(completions) -> tuple:
    """(p50_s, p99_s) of request latency over a completion list
    (nearest-rank; (0.0, 0.0) when empty)."""
    lat = sorted(c.latency_s for c in completions)
    if not lat:
        return 0.0, 0.0
    p50 = lat[len(lat) // 2]
    p99 = lat[min(len(lat) - 1, int(len(lat) * 0.99))]
    return p50, p99


def synthetic_trace(n_requests: int, *, seed: int = 0, vocab: int = 128,
                    prompt_lens=(4, 48), new_tokens=(4, 32)) -> List[Request]:
    """Mixed-length request trace for benchmarks/smoke tests: prompt lengths
    and token budgets drawn uniformly from the given inclusive ranges."""
    rng = np.random.default_rng(seed)
    out = []
    for uid in range(n_requests):
        s = int(rng.integers(prompt_lens[0], prompt_lens[1] + 1))
        g = int(rng.integers(new_tokens[0], new_tokens[1] + 1))
        out.append(Request(uid=uid,
                           prompt=rng.integers(0, vocab, (s,)).astype(np.int32),
                           max_new_tokens=g))
    return out
