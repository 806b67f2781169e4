"""Continuous-batching autoregressive decode over a fixed slot grid.

The encode path (scheduler.py) batches whole requests; generation can't
— a sequence occupies the batch for many steps and sequences finish at
different times. This loop implements iteration-level join/leave (the
Orca scheduling insight): the decode batch is a FIXED grid of KV-cache
slots, requests are admitted into free slots BETWEEN steps, run however
many steps they need, and release their slot the moment they finish —
no waiting for stragglers, no reshaping, one compiled step shape.

The step contract is model-agnostic:

    step_fn(tokens, cache, active) -> logits

with ``tokens (slots,) int32`` (pad token in inactive rows), ``cache``
the KVCache (the step reads/writes its entries for ALL slots at once —
inactive rows compute garbage that is never observed), and ``active
(slots,) bool``. By default prompts are prefilled one token per step
through the same path, so a joining request warms its KV slot without a
separate prefill program; families that provide a ``prefill_fn`` (the
gpt_decoder paged family) instead get the prompt prefix committed in
chunked forwards at admission, and the grid only ever feeds the last
prompt token. Greedy argmax sampling — deterministic, which the
acceptance tests rely on.

Deadline shed: at join the loop estimates ``(prompt+max_new) * EWMA
(step seconds)``; mid-generation an expired deadline retires the slot
immediately (stage "decode") instead of finishing a reply nobody will
read — unless the sequence finished on that very step, in which case
the already-paid-for result is delivered.
"""

import collections
import os
import threading
import time

import numpy as np

from ..telemetry import catalog as _cat
from ..telemetry import flight as _fl
from ..telemetry import tracing as _tr
from .scheduler import Request

__all__ = ["DecodeRequest", "DecodeLoop"]


def _is_capacity_error(e):
    """KV pool exhaustion is pressure, not a bug: shed-on-pressure
    (stage "capacity") keeps the client retrying against a less loaded
    replica and feeds the kv_pool_pressure rule, while real step bugs
    stay errors.  Imported lazily — generate -> serving.loader -> here
    would otherwise cycle at import time."""
    from ..generate.paged_kv import KVPoolExhausted
    return isinstance(e, KVPoolExhausted)


class DecodeRequest(Request):
    """Generate up to `max_new_tokens` after `prompt` (1-D int tokens);
    stops early at `eos_id`. Result: {"tokens": generated int32 array}.
    """

    def __init__(self, model, prompt, max_new_tokens, eos_id=None,
                 deadline=None):
        prompt = np.asarray(prompt, np.int32).ravel()
        if prompt.size < 1:
            raise ValueError("prompt must hold at least one token")
        super().__init__(model, {"tokens": prompt.reshape(1, -1)},
                         deadline=deadline)
        self.prompt = prompt
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = eos_id


class _Seq:
    """Per-slot progress: prompt prefill (one token per step), then
    greedy generation off the model's logits."""

    def __init__(self, req):
        self.req = req
        self.fed = 0
        self.generated = []
        self.last_tok = None    # monotonic time of last committed token
        #                         (TTFT on the first, TPOT gaps after)

    def next_input(self):
        if self.fed < self.req.prompt.size:
            return int(self.req.prompt[self.fed])
        return self.generated[-1]

    def consume(self, logits):
        """Account one executed step; once the whole prompt is in, the
        step's logits predict the next token."""
        self.fed += 1
        if self.fed >= self.req.prompt.size:
            self.generated.append(int(np.argmax(logits)))

    @property
    def finished(self):
        if len(self.generated) >= self.req.max_new_tokens:
            return True
        return (self.req.eos_id is not None and self.generated
                and self.generated[-1] == self.req.eos_id)

    def steps_remaining(self):
        return (self.req.prompt.size - self.fed) \
            + (self.req.max_new_tokens - len(self.generated))


class DecodeLoop:
    """One per served generative model; owns the KVCache exclusively."""

    def __init__(self, name, step_fn, cache, pad_token=0,
                 max_new_tokens_cap=None, prefill_fn=None,
                 prefill_chunk=None):
        self.name = name
        self._step_fn = step_fn
        self._cache = cache
        self._prefill_fn = prefill_fn
        # the width of `prefill_fn`'s forwards, as its family says it
        # (``ServedModel.prefill_chunk``): the join estimate counts them
        self._prefill_chunk = max(1, int(prefill_chunk or 32))
        self._pad = int(pad_token)
        self._cap = int(max_new_tokens_cap if max_new_tokens_cap is not None
                        else os.environ.get("MXTPU_SERVE_MAX_NEW_TOKENS",
                                            "64"))
        self._cond = threading.Condition()
        self._pending = collections.deque()
        self._active = {}               # slot -> _Seq
        self._stopping = False
        self._draining = False
        self._in_step = False           # a step_fn call is running now
        self._steps = 0
        self._ewma_step = None
        self._thread = threading.Thread(
            target=self._run, name="serve-decode-%s" % name, daemon=True)

    # ---------------------------------------------------------- admission
    def submit(self, req):
        if req.max_new_tokens > self._cap:
            req.max_new_tokens = self._cap
        now = time.monotonic()
        if req.deadline is not None and now >= req.deadline:
            self._shed(req, "queue", "deadline expired before admission")
            return req
        if req.prompt.size + req.max_new_tokens > self._cache.max_len:
            req.fail(ValueError(
                "prompt %d + max_new_tokens %d exceeds the KV cache "
                "max_len %d" % (req.prompt.size, req.max_new_tokens,
                                self._cache.max_len)))
            return req
        with self._cond:
            if self._stopping:
                req.fail(RuntimeError("decode loop %r is stopped"
                                      % self.name))
                return req
            if self._draining:
                self._shed(req, "draining",
                           "model is draining for a weight swap; retry")
                return req
            self._pending.append(req)
            self._cond.notify_all()
        return req

    def _shed(self, req, stage, detail=""):
        if req.shed(stage, detail):     # no double-count if already done
            _cat.serving_shed.inc(model=self.name, stage=stage)
            _cat.serving_requests.inc(model=self.name, status="shed")
            attrs = {"model": self.name, "stage": stage,
                     "request_id": req.id}
            if req.trace:
                attrs["trace_id"] = req.trace[0]
                t1 = time.time()
                _tr.record_span(
                    "serve.shed", req.trace[0], parent_id=req.trace[1],
                    t0=t1 - (time.monotonic() - req.arrival), t1=t1,
                    sampled=True, model=self.name, stage=stage,
                    request_id=req.id, detail=detail)
            _fl.record("serving.shed", **attrs)

    # ---------------------------------------------------------- lifecycle
    def start(self):
        self._thread.start()
        return self

    def stop(self, timeout=5.0):
        with self._cond:
            self._stopping = True
            self._cond.notify_all()
        if self._thread.ident is not None:      # started
            self._thread.join(timeout)
        with self._cond:
            while self._pending:
                self._pending.popleft().fail(
                    RuntimeError("decode loop %r stopped" % self.name))
            for slot, seq in list(self._active.items()):
                seq.req.fail(RuntimeError("decode loop %r stopped"
                                          % self.name))
                self._cache.free(slot)
            self._active.clear()

    # ------------------------------------------------------ drain/re-admit
    @property
    def draining(self):
        return self._draining

    def drain(self, timeout=30.0):
        """Fence the decode plane for a weight swap. New submits shed
        with the RETRIABLE "draining" stage; queued-but-unslotted
        requests are shed immediately (their retry re-prefills against
        the new weights); ACTIVE sequences get `timeout` seconds to
        finish naturally. Stragglers past the deadline are fenced —
        shed "draining", slots freed on the loop's next retire pass —
        so the session is re-prefillable on retry and the swap never
        lands mid-step. Returns True when the grid is empty and no step
        is in flight; False means a step is STILL running — do not
        swap."""
        deadline = time.monotonic() + float(timeout)
        with self._cond:
            self._draining = True
            while self._pending:
                self._shed(self._pending.popleft(), "draining",
                           "drained before admission; retry")
            self._cond.notify_all()
            while self._active or self._in_step:
                left = deadline - time.monotonic()
                if left <= 0:
                    break
                self._cond.wait(min(left, 0.05))
            for seq in list(self._active.values()):
                self._shed(seq.req, "draining",
                           "fenced at the drain deadline; the session "
                           "re-prefills on retry")
            # fenced sequences retire (slots freed) on the loop's next
            # pass; give the in-flight step one more window to land
            while self._active or self._in_step:
                left = deadline + float(timeout) - time.monotonic()
                if left <= 0:
                    return False
                self._cond.wait(min(left, 0.05))
        return True

    def admit(self):
        """Re-open admission after a drain()."""
        with self._cond:
            self._draining = False
            self._cond.notify_all()

    def reset_service_estimates(self):
        """Forget the EWMA step time (see ContinuousBatcher's twin —
        compile-skewed early samples would join-shed deadlined work)."""
        with self._cond:
            self._ewma_step = None

    def stats(self):
        with self._cond:
            return {"pending": len(self._pending),
                    "active": len(self._active),
                    "draining": self._draining,
                    "steps": self._steps,
                    "step_ewma_s": self._ewma_step}

    # -------------------------------------------------------- decode loop
    def _est_steps(self, req):
        """Grid steps a request still needs: with a family prefill_fn
        the prompt prefix lands in ceil((P-1)/chunk) chunked forwards
        plus one step for the last prompt token; without, one step per
        prompt token — plus max_new decode steps either way."""
        if self._prefill_fn is not None and req.prompt.size > 1:
            chunks = -(-(req.prompt.size - 1) // self._prefill_chunk)
            return chunks + 1 + req.max_new_tokens
        return req.prompt.size + req.max_new_tokens

    def _admit_locked(self):
        """Join point: fill free slots from the FIFO between steps.
        Families with a ``prefill_fn`` get their prompt prefix committed
        here, chunked, so the step grid only ever feeds the LAST prompt
        token (chunked prefill replaces one-token-per-step prefill)."""
        if self._draining:      # no new sessions join mid-drain
            return
        now = time.monotonic()
        est = self._ewma_step or 0.0
        while self._pending and self._cache.in_use < self._cache.slots:
            req = self._pending[0]
            if req.done:                # cancelled while queued
                self._pending.popleft()
                continue
            if req.deadline is not None and \
                    now + est * self._est_steps(req) > req.deadline:
                self._pending.popleft()
                self._shed(req, "join", "full generation can't meet "
                           "the deadline")
                continue
            slot = self._cache.alloc()
            if slot is None:
                return
            self._pending.popleft()
            seq = _Seq(req)
            _cat.serving_queue_seconds.observe(
                time.monotonic() - req.arrival, model=self.name,
                exemplar=req.trace[0] if req.trace else None)
            t_adm = None
            if req.trace:
                # retroactive queue span: arrival -> slot grant
                t_adm = time.time()
                _tr.record_span(
                    "serve.queue", req.trace[0], parent_id=req.trace[1],
                    t0=t_adm - (time.monotonic() - req.arrival),
                    t1=t_adm, sampled=True, model=self.name,
                    request_id=req.id)
            if self._prefill_fn is not None and req.prompt.size > 1:
                t0 = time.perf_counter()
                try:
                    self._prefill_fn(slot, req.prompt[:-1], self._cache)
                except Exception as e:  # noqa: BLE001 — a broken
                    # prefill fails this request, not the serving loop
                    if _is_capacity_error(e):
                        self._shed(req, "capacity", str(e))
                    elif req.fail(e):
                        _cat.serving_requests.inc(model=self.name,
                                                  status="error")
                    self._cache.free(slot)
                    continue
                dt = time.perf_counter() - t0
                seq.fed = req.prompt.size - 1
                _cat.gen_prefill_seconds.observe(
                    dt, model=self.name,
                    exemplar=req.trace[0] if req.trace else None)
                _cat.serving_forward_seconds.observe(
                    dt, model=self.name, bucket="prefill")
                _cat.gen_tokens_committed.inc(
                    req.prompt.size - 1, model=self.name,
                    phase="prefill")
                if req.trace:
                    t1 = time.time()
                    _tr.record_span(
                        "decode.prefill", req.trace[0],
                        parent_id=req.trace[1], t0=t1 - dt, t1=t1,
                        sampled=True, model=self.name, request_id=req.id,
                        prefill_tokens=int(req.prompt.size - 1),
                        chunk=self._prefill_chunk, slot=slot)
            if req.trace:
                # join span: slot grant -> active in the step grid
                # (chunked prefill, when it ran, sits inside this window)
                _tr.record_span(
                    "serve.join", req.trace[0], parent_id=req.trace[1],
                    t0=t_adm, t1=time.time(), sampled=True,
                    model=self.name, request_id=req.id, slot=slot)
            self._active[slot] = seq
        _cat.serving_decode_slots.set(len(self._active), model=self.name)

    def _run(self):
        slots = self._cache.slots
        while True:
            with self._cond:
                while (not self._stopping and not self._pending
                        and not self._active):
                    self._cond.wait(0.1)
                if self._stopping:
                    return
                self._admit_locked()
                active = dict(self._active)
                if active:
                    self._in_step = True
            if not active:
                continue
            tokens = np.full(slots, self._pad, np.int32)
            mask = np.zeros(slots, bool)
            for slot, seq in active.items():
                tokens[slot] = seq.next_input()
                mask[slot] = True
            t0 = time.perf_counter()
            try:
                logits = np.asarray(self._step_fn(tokens, self._cache,
                                                  mask))
            except Exception as e:  # noqa: BLE001 — a broken step fails
                # the in-flight sequences, not the serving loop; pool
                # exhaustion mid-grid sheds the whole step's sessions as
                # a capacity event (freeing their blocks IS the relief)
                capacity = _is_capacity_error(e)
                with self._cond:
                    for slot, seq in list(self._active.items()):
                        if capacity:
                            self._shed(seq.req, "capacity", str(e))
                        elif seq.req.fail(e):
                            _cat.serving_requests.inc(model=self.name,
                                                      status="error")
                        self._cache.free(slot)
                    self._active.clear()
                    self._in_step = False
                    self._cond.notify_all()
                continue
            dt = time.perf_counter() - t0
            with self._cond:
                # the EWMA read-modify-write must sit under the cond:
                # reset_service_estimates()/stats() touch it from other
                # threads, and a bare update here could resurrect a
                # just-reset estimate
                self._ewma_step = dt if self._ewma_step is None else \
                    0.7 * self._ewma_step + 0.3 * dt
                self._steps += 1
            _cat.serving_decode_steps.inc(model=self.name)
            _cat.serving_batch_occupancy.observe(len(active),
                                                 model=self.name)
            _cat.serving_forward_seconds.observe(dt, model=self.name,
                                                 bucket="decode")
            now = time.monotonic()
            # token accounting happens in the retire pass BELOW the
            # consume, so the final step of a retiring sequence counts
            # too (the historical undercount: per-step counters bumped
            # before retirement skipped the buzzer token)
            step_decode_tokens = 0
            step_prefill_tokens = 0
            t_wall = None               # epoch stamp, taken lazily once
            with self._cond:
                for slot, seq in list(self._active.items()):
                    before = len(seq.generated)
                    seq.consume(logits[slot])
                    new_tok = len(seq.generated) > before
                    if new_tok:
                        step_decode_tokens += 1
                        ex = seq.req.trace[0] if seq.req.trace else None
                        if before == 0:
                            _cat.serving_ttft_seconds.observe(
                                now - seq.req.arrival, model=self.name,
                                exemplar=ex)
                        elif seq.last_tok is not None:
                            _cat.serving_tpot_seconds.observe(
                                now - seq.last_tok, model=self.name,
                                exemplar=ex)
                        seq.last_tok = now
                    else:
                        step_prefill_tokens += 1
                    if seq.req.trace:
                        if t_wall is None:
                            t_wall = time.time()
                        _tr.record_span(
                            "decode.step", seq.req.trace[0],
                            parent_id=seq.req.trace[1], t0=t_wall - dt,
                            t1=t_wall, sampled=True, model=self.name,
                            request_id=seq.req.id, slot=slot,
                            tokens_committed=int(new_tok),
                            generated=len(seq.generated))
                    if seq.req.done:    # cancelled mid-flight: release
                        reason = "cancelled"
                    elif seq.finished:
                        # finished beats the deadline check: this step's
                        # compute already paid for the final token, so a
                        # sequence that completed at the buzzer is
                        # delivered, not shed
                        reason = "ok"
                        if seq.req.complete({"tokens": np.asarray(
                                seq.generated, np.int32)}):
                            _cat.serving_requests.inc(model=self.name,
                                                      status="ok")
                            _cat.serving_request_seconds.observe(
                                now - seq.req.arrival, model=self.name,
                                exemplar=seq.req.trace[0]
                                if seq.req.trace else None)
                    elif seq.req.deadline is not None \
                            and now > seq.req.deadline:
                        reason = "deadline"
                        self._shed(seq.req, "decode",
                                   "deadline passed mid-generation")
                    else:
                        continue
                    attrs = {"model": self.name, "reason": reason,
                             "request_id": seq.req.id, "slot": slot,
                             "generated": len(seq.generated)}
                    if seq.req.trace:
                        attrs["trace_id"] = seq.req.trace[0]
                    _fl.record("serving.retire", **attrs)
                    self._cache.free(slot)
                    del self._active[slot]
                _cat.serving_decode_slots.set(len(self._active),
                                              model=self.name)
                self._in_step = False
                self._cond.notify_all()     # wake a waiting drain()
            if step_decode_tokens:
                _cat.gen_tokens_committed.inc(
                    step_decode_tokens, model=self.name, phase="decode")
            if step_prefill_tokens:
                _cat.gen_tokens_committed.inc(
                    step_prefill_tokens, model=self.name, phase="prefill")
