"""Continuous-batching serving engine over registry-resolved serve steps.

The engine is hosting-agnostic: it drives a
:class:`~repro.serve.steps.ServeStep` (``replicated`` or ``lane_zero3``
1/p weight hosting — the cell is resolved from the ``("serve_step", ...)``
registry exactly like the training driver resolves ``("train_step", ...)``)
through the prefill → splice → decode loop and owns only host-side
bookkeeping: slot assignment, admission (bucketed prompt padding),
per-request sampling, termination, and latency accounting.

Correctness contracts pinned by tests/test_serve.py:

  * batched == sequential: greedy continuous batching is token-identical
    to decoding each request alone at batch 1, across slot counts,
    admission orders and mid-stream refills — decode rows are
    independent and prefill is per-request batch-1, so batching is pure
    throughput, never a semantic.  Across slot counts this needs every
    batch shape to round alike, as the CPU tests do; on a TPU in bf16 a
    slots=1 step is another program and may flip a near-tied argmax, so
    chip_smoke.py serves each request alone through the same step.
  * seeded replay: with a :class:`~repro.serve.sampling.SamplerConfig`,
    every token is a pure function of (seed, rid, position) — the same
    request replays bit-identically regardless of slot assignment or
    batch composition.
  * admission: a prompt longer than its bucket selects a larger bucket
    (never truncated — the seed engine silently sliced ``prompt[:b]``);
    a request that cannot fit ``prefix + len(prompt) + max_new_tokens``
    inside ``max_seq`` raises ValueError at admit.
  * termination: eos / max_new_tokens / max_seq fire exactly once per
    request and are recorded in ``finish_reason``.

Every family prefills bucketed prompts (a bounded compile count) and
relies on ``prefill(..., true_len=...)``: logits are read at the last
true position; the attention cache's padded tail stays dead behind the
length mask, and the recurrent families (ssm/hybrid) keep the padding out
of their state (``dt = 0`` and a zero input past the true length, the
conv state read at the true end).  ``pad_positions`` counts the padded
prompt positions the engine has prefilled.
"""
from __future__ import annotations

import dataclasses
import gc
import time
import zlib
from typing import Any, Optional

import numpy as np

import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from .sampling import SamplerConfig, sample_token
from .steps import ServeStep, build_serve_step

__all__ = ["Request", "ContinuousBatcher", "termination_reason",
           "DEFAULT_BUCKETS"]

DEFAULT_BUCKETS = (32, 64, 128, 256, 512)

# Host spans (``jax.profiler.TraceAnnotation``), written into a running
# profiler's trace beside the device's ops; with no profiler running each
# costs one TraceMe check:
#   serve.admit    all of ``admit``; serve.decode  all of ``step_decode``
#   inside both    serve.dispatch (the call into the step), serve.fetch (the
#                  logits to the host, waiting on the device), serve.sample
#                  (sampling and termination); in admit also serve.splice
#   host.gc        each collection of Python's garbage collector
_gc_open: list = []


def _gc_span(phase: str, info: dict) -> None:
    if phase == "start":
        span = TraceAnnotation("host.gc")
        span.__enter__()
        _gc_open.append(span)
    elif _gc_open:
        _gc_open.pop().__exit__(None, None, None)


def _install_gc_span() -> None:
    """Put a ``host.gc`` span round every collection (once a process)."""
    if _gc_span not in gc.callbacks:
        gc.callbacks.append(_gc_span)


@dataclasses.dataclass
class Request:
    """One serving request (mutated in place by the engine)."""
    rid: Any
    prompt: Any                       # sequence of int token ids
    max_new_tokens: int = 32
    arrival_step: int = 0             # decode step at which it arrives
    extra: Any = None                 # vlm patches / audio frames
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    finish_reason: Optional[str] = None
    t_arrival: Optional[float] = dataclasses.field(default=None,
                                                   repr=False)
    t_first: Optional[float] = dataclasses.field(default=None, repr=False)
    t_done: Optional[float] = dataclasses.field(default=None, repr=False)


def termination_reason(token: int, n_out: int, length: int, *,
                       eos_id: int, max_new_tokens: int,
                       max_seq: int) -> Optional[str]:
    """The single termination decision, applied after appending the
    ``n_out``-th generated token (``length`` = cache positions consumed
    so far; the NEXT decode would write at position ``length``).
    Priority: eos, then the request's token budget, then cache capacity.
    Returns None while the request should keep decoding — callers set
    ``finish_reason`` from the first non-None answer and never overwrite
    it, so each reason fires exactly once per request.  (The property
    tests drive this function directly for the capacity branch, which a
    validated admit makes unreachable end-to-end.)"""
    if eos_id >= 0 and token == eos_id:
        return "eos"
    if n_out >= max_new_tokens:
        return "length"
    if length >= max_seq:
        return "max_seq"
    return None


def _int_rid(rid) -> int:
    """Stable uint32 for the sampling key (non-int rids hash via crc32)."""
    if isinstance(rid, (int, np.integer)):
        return int(rid) & 0xFFFFFFFF
    return zlib.crc32(str(rid).encode()) & 0xFFFFFFFF


class ContinuousBatcher:
    """Slot-based continuous batching over one ServeStep.

    params    replicated init_model tree; ``step.prepare`` lays it out
              for the chosen hosting (1/p masters under lane_zero3).
    sampler   None = greedy argmax; a SamplerConfig = seeded temperature/
              top-p sampling keyed by (seed, rid, position).
    hosting   a registered serve_step strategy ("replicated" |
              "lane_zero3"); lane_zero3 needs ``mesh`` and
              slots % chip-count == 0.
    step      inject a prebuilt ServeStep to share jit caches across
              engines (the equivalence tests run batched and sequential
              engines over ONE step).

    ``pad_positions`` counts the prompt positions past the true length
    that admission has prefilled (the bucket's padding).
    """

    def __init__(self, params, cfg, *, slots: int, max_seq: int,
                 eos_id: int = -1, sampler: Optional[SamplerConfig] = None,
                 hosting: str = "replicated", mesh=None,
                 step: Optional[ServeStep] = None,
                 buckets: tuple = DEFAULT_BUCKETS,
                 prefetch_blocks: int = 0, model_parallel: int = 1):
        self.cfg = cfg
        self.slots = int(slots)
        self.max_seq = int(max_seq)
        self.eos_id = int(eos_id)
        self.sampler = sampler
        self.buckets = tuple(sorted(buckets))
        if step is not None:
            if (step.ctx.max_seq, step.ctx.slots) != (self.max_seq,
                                                      self.slots):
                raise ValueError(
                    f"injected step was built for max_seq="
                    f"{step.ctx.max_seq}, slots={step.ctx.slots}; engine "
                    f"wants max_seq={self.max_seq}, slots={self.slots}")
            self.step = step
        else:
            self.step = build_serve_step(
                cfg, max_seq=self.max_seq, slots=self.slots,
                hosting=hosting, mesh=mesh,
                prefetch_blocks=prefetch_blocks,
                model_parallel=model_parallel)
        self.hosted = self.step.prepare(params)
        self.state = self.step.init_state()
        self._active: dict[int, Request] = {}
        self._free = list(range(self.slots))
        self._last_tok = np.zeros((self.slots,), np.int32)
        self._prefix = cfg.vision_tokens if cfg.family == "vlm" else 0
        self._sample_fn = None
        self.pad_positions = 0
        if sampler is not None and not sampler.greedy:
            import jax
            self._sample_fn = jax.jit(
                lambda row, rid, pos: sample_token(row, sampler, rid, pos))
        _install_gc_span()

    # -- sampling / termination ------------------------------------------

    def _next_token(self, row: np.ndarray, req: Request) -> int:
        pos = len(req.out)            # 0 = the prefill-produced token
        if self._sample_fn is None:
            return int(np.argmax(row))
        return int(self._sample_fn(jnp.asarray(row, jnp.float32),
                                   jnp.asarray(_int_rid(req.rid),
                                               jnp.uint32),
                                   jnp.asarray(pos, jnp.uint32)))

    def _finish_if_done(self, req: Request, token: int,
                        length: int) -> bool:
        reason = termination_reason(
            token, len(req.out), length, eos_id=self.eos_id,
            max_new_tokens=req.max_new_tokens, max_seq=self.max_seq)
        if reason is None:
            return False
        if req.finish_reason is not None:
            raise RuntimeError(
                f"request {req.rid} finished twice "
                f"({req.finish_reason!r} then {reason!r})")
        req.finish_reason = reason
        req.done = True
        req.t_done = time.perf_counter()
        return True

    # -- admission --------------------------------------------------------

    def _bucket_for(self, L: int) -> int:
        """Prompt pad width: smallest registered bucket >= L (falling
        back to the prompt length itself past the largest bucket).  Never
        below L — long prompts select a LARGER bucket instead of
        truncating.  Admission has already proven ``prefix + L +
        max_new_tokens <= max_seq``, so the capacity clamp can never push
        the bucket under L."""
        cap = self.max_seq - self._prefix
        for b in self.buckets:
            if b >= L:
                return min(b, cap)
        return min(max(L, self.buckets[-1]), cap)

    def _extra_embeds(self, req: Request):
        if self.cfg.family not in ("vlm", "audio"):
            return None
        kind = "patch" if self.cfg.family == "vlm" else "frame"
        if req.extra is None:
            raise ValueError(
                f"request {req.rid!r}: family {self.cfg.family!r} needs "
                f"{kind} embeddings in Request.extra")
        x = np.asarray(req.extra, np.float32)
        if x.ndim == 2:
            x = x[None]
        return jnp.asarray(x)

    def admit(self, req: Request, slot: int):
        """Prefill ``req`` at batch 1 and splice its state into ``slot``.
        Produces the first generated token (from the last TRUE prompt
        position).  Raises ValueError when the request cannot fit
        ``max_seq``.  A request with no arrival stamp is stamped on entry,
        so its ``ttft_ms`` holds its own prefill."""
        if req.t_arrival is None:
            req.t_arrival = time.perf_counter()
        with TraceAnnotation("serve.admit"):
            prompt = np.asarray(req.prompt, np.int32).reshape(-1)
            L = int(prompt.shape[0])
            if L == 0:
                raise ValueError(f"request {req.rid!r}: empty prompt")
            need = self._prefix + L + int(req.max_new_tokens)
            if need > self.max_seq:
                raise ValueError(
                    f"request {req.rid!r}: prompt length {L}"
                    + (f" + {self._prefix} vision tokens"
                       if self._prefix else "")
                    + f" + max_new_tokens {req.max_new_tokens} = {need} "
                    f"exceeds max_seq={self.max_seq}; shorten the prompt or "
                    f"lower max_new_tokens")
            b = self._bucket_for(L)
            if b < L:
                raise RuntimeError(
                    f"prefill bucket {b} shorter than prompt length {L}")
            toks = np.zeros((1, b), np.int32)
            toks[0, :L] = prompt          # whole prompt, never sliced
            self.pad_positions += b - L
            with TraceAnnotation("serve.dispatch"):
                logits, st1 = self.step.prefill(
                    self.hosted, jnp.asarray(toks), L,
                    self._extra_embeds(req))
            with TraceAnnotation("serve.fetch"):
                row = np.asarray(logits)[0, -1]
            with TraceAnnotation("serve.sample"):
                t = self._next_token(row, req)
                req.out.append(t)
                req.t_first = time.perf_counter()
                if self._finish_if_done(req, t, self._prefix + L):
                    return
            with TraceAnnotation("serve.splice"):
                self.state = self.step.splice(self.state, st1, slot)
            self._active[slot] = req
            self._last_tok[slot] = t

    # -- decode -----------------------------------------------------------

    def step_decode(self) -> int:
        """One batched decode over every slot (idle slots carry garbage
        rows; decode rows are independent so they cannot influence the
        active ones).  Returns the number of tokens appended."""
        with TraceAnnotation("serve.decode"):
            with TraceAnnotation("serve.dispatch"):
                tok = jnp.asarray(self._last_tok.reshape(self.slots, 1))
                logits, self.state = self.step.decode(self.hosted, tok,
                                                      self.state)
            with TraceAnnotation("serve.fetch"):
                rows = np.asarray(logits)
                lengths = np.asarray(self.state.length)
            with TraceAnnotation("serve.sample"):
                produced = 0
                for slot, req in list(self._active.items()):
                    t = self._next_token(rows[slot, -1], req)
                    req.out.append(t)
                    self._last_tok[slot] = t
                    produced += 1
                    if self._finish_if_done(req, t, int(lengths[slot])):
                        del self._active[slot]
                        self._free.append(slot)
            return produced

    # -- the serving loop -------------------------------------------------

    def run(self, requests, *, max_steps: int = 10_000):
        """Serve ``requests`` to completion (or ``max_steps`` decode
        steps).  Admission honors ``arrival_step`` (bursty scenarios: a
        request is invisible until the decode-step clock reaches it) and
        otherwise follows submission order.  Returns
        ``(requests, stats)``."""
        pending = list(requests)
        t0 = time.perf_counter()
        steps = 0
        decode_tokens = 0
        while (pending or self._active) and steps < max_steps:
            now = time.perf_counter()
            for r in pending:
                if r.arrival_step <= steps and r.t_arrival is None:
                    r.t_arrival = now
            while self._free and pending:
                nxt = next((r for r in pending
                            if r.arrival_step <= steps), None)
                if nxt is None:
                    break
                pending.remove(nxt)
                slot = self._free.pop(0)
                self.admit(nxt, slot)
                if nxt.done:          # finished on its very first token
                    self._free.insert(0, slot)
            if not self._active:
                steps += 1            # idle tick toward the next arrival
                continue
            decode_tokens += self.step_decode()
            steps += 1
        wall = time.perf_counter() - t0
        stats = {
            "steps": steps,
            "decode_tokens": decode_tokens,
            "wall_s": wall,
            "tok_per_s": decode_tokens / wall if wall > 0 else 0.0,
            "hosting": self.step.hosting,
            "requests": [
                {"rid": r.rid,
                 "tokens": len(r.out),
                 "finish_reason": r.finish_reason,
                 "ttft_ms": None if r.t_first is None or r.t_arrival is None
                 else (r.t_first - r.t_arrival) * 1e3,
                 "latency_ms": None if r.t_done is None or r.t_arrival is None
                 else (r.t_done - r.t_arrival) * 1e3}
                for r in requests],
        }
        return requests, stats
