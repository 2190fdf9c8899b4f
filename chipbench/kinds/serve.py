"""Serving cells: open-loop arrivals into the program's continuous batcher.

The mix (``traffic/<mix>.json``) gives the engine's slots, cache length
and prefill buckets, and the request sizes; the cell gives the arrival
rate.  Every seed gets the same schedule of gaps between arrivals, prompt
lengths and output lengths (quantiles of the mix's distributions, in one
fixed order), turned round to start at a request drawn from the seed, and
its own prompt tokens: the work, and the bursts in it, are the same.

Set-up makes the weights on the device from the seed, builds the engine
(``ContinuousBatcher`` over a ``build_serve_step`` step) and warms every
prefill bucket, the splice and the decode on a throw-away engine that
shares the step.  The window is one loop: requests become due at their
arrival times (open loop: the time a request waits counts from when it
was due), due requests are admitted into free slots through
``ContinuousBatcher.admit`` (prefill and first token), and every slot
decodes through ``step_decode``.  At ``--seconds`` no more requests
arrive; the loop serves what is left (at most ``drain_s`` more, counted
from when the profiler has written its trace) so each request due in the
window has its first token and its outputs.  Greedy
decoding.
"""
from __future__ import annotations

import collections
import gc
import math
import time
from statistics import NormalDist

import numpy as np

from chipbench import checks as C
from chipbench import weights


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


# the order of the schedule every seed turns round
SCHEDULE_SEED = 0


def lengths(spec: dict, n: int, rng) -> np.ndarray:
    """``n`` lognormal lengths (median, sigma) at evenly spaced quantiles,
    clipped to [min, max], in an order drawn from ``rng``."""
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    x = np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)
    return rng.permutation(x)


def gaps(rate: float, n: int, rng) -> np.ndarray:
    """``n`` gaps (s) between Poisson arrivals at ``rate``: exponential
    quantiles, evenly spaced, in an order drawn from ``rng``."""
    return rng.permutation(-np.log1p(-_quantiles(n)) / rate)


def requests(traffic: dict, rate: float, seconds: float, seed: int,
             vocab: int):
    """[(due_s, prompt ids, output length)] of one run."""
    rng = np.random.default_rng(seed)
    n = max(int(rate * seconds), 1)
    fixed = np.random.default_rng(SCHEDULE_SEED)
    sched = (gaps(rate, n, fixed), lengths(traffic["prompt"], n, fixed),
             lengths(traffic["output"], n, fixed))
    start = int(rng.integers(n))
    gap, plen, olen = (np.roll(x, -start) for x in sched)
    due = np.concatenate([[0.0], np.cumsum(gap)[:-1]])
    return [(float(d), rng.integers(0, vocab, size=int(p), dtype=np.int32),
             int(o)) for d, p, o in zip(due, plen, olen)]


class Window:
    """One serving loop over an engine (see the module doc).  Records, on
    the host clock: each request's first-token wait from its due time,
    each gap between consecutive output tokens, how late the loop saw
    each arrival, and per decode call the positions each active slot
    attends to."""

    def __init__(self, engine, slots: int, spans):
        self.engine, self.spans = engine, spans
        self.free = list(range(slots))
        self.active = {}
        self.last = {}
        self.ttft, self.gaps, self.late = {}, [], []
        self.prefill_lens, self.decode_lens = [], []
        self.failed, self.done = [], []
        self.queue_len = []           # (t, waiting) once per iteration
        self.iterations = 0
        self.stop_s = 0.0             # profiler's stop after the window

    def _admit(self, queue, in_window: bool):
        while self.free and queue:
            r = queue.popleft()
            slot = self.free.pop(0)
            try:
                self.engine.admit(r, slot)
            except ValueError:
                self.failed.append(r)
                self.free.insert(0, slot)
                continue
            self.ttft[r.rid] = r.t_first - r.t_arrival
            self.last[r.rid] = r.t_first
            if in_window:
                self.prefill_lens.append(len(r.prompt))
            if r.done:
                self.free.insert(0, slot)
                self.done.append(r)
            else:
                self.active[slot] = r

    def _decode(self, in_window: bool):
        if in_window:
            self.decode_lens.append([len(r.prompt) + len(r.out)
                                     for r in self.active.values()])
        self.engine.step_decode()
        now = time.perf_counter()
        for slot, r in list(self.active.items()):
            if in_window:
                self.gaps.append(now - self.last[r.rid])
            self.last[r.rid] = now
            if r.done:
                del self.active[slot]
                self.free.append(slot)
                self.done.append(r)

    def run(self, reqs, t0: float, seconds: float, drain_s: float):
        """Serve ``reqs`` (Request objects with ``t_arrival`` offsets in
        seconds from ``t0``); returns the window's end on the host clock."""
        due = collections.deque(sorted(reqs, key=lambda r: r.t_arrival))
        queue = collections.deque()
        deadline = t0 + seconds
        for r in due:
            r.t_arrival += t0
        with self.spans("chipbench.window"):
            while True:
                now = time.perf_counter()
                if now >= deadline:
                    break
                while due and due[0].t_arrival <= now:
                    r = due.popleft()
                    self.late.append(now - r.t_arrival)
                    queue.append(r)
                self.queue_len.append((now - t0, len(queue)))
                if not (queue and self.free) and not self.active:
                    with self.spans("chipbench.idle"):
                        nxt = due[0].t_arrival if due else deadline
                        time.sleep(max(min(nxt, deadline) - now, 0.0))
                    continue
                self.iterations += 1
                with self.spans("chipbench.iteration"):
                    with self.spans("chipbench.admit"):
                        self._admit(queue, True)
                    if self.active:
                        with self.spans("chipbench.decode"):
                            self._decode(True)
            t1 = time.perf_counter()
        self.spans.stop()
        self.stop_s = time.perf_counter() - t1
        stop = time.perf_counter() + drain_s
        while (queue or self.active) and time.perf_counter() < stop:
            self._admit(queue, False)
            if self.active:
                self._decode(False)
        self.failed += list(queue) + list(self.active.values())
        return t1


def _percentile(values, q: float) -> float:
    """Nearest-rank percentile; missing values (inf) sort last."""
    v = sorted(values)
    if not v:
        return float("nan")
    return float(v[max(math.ceil(q / 100 * len(v)) - 1, 0)])


def build(cell, seed: int):
    """A factory of fresh engines over the cell's warmed-up step and the
    seed's weights (which it holds)."""
    import jax
    from repro.configs import resolve
    from repro.serve import ContinuousBatcher, Request, build_serve_step
    cfg = resolve(cell.config["registry"],
                  smoke=bool(cell.config.get("registry_smoke")))
    C.config_matches(cfg, cell.config)
    t = cell.traffic
    params = jax.jit(weights.make_fn(cell.config))(weights.seed_key(seed))
    step = build_serve_step(cfg, max_seq=t["max_seq"], slots=t["slots"],
                            hosting=t["hosting"])

    def engine():
        return ContinuousBatcher(params, cfg, slots=t["slots"],
                                 max_seq=t["max_seq"], step=step,
                                 buckets=tuple(t["buckets"]))

    warm = engine()
    for i, b in enumerate(t["buckets"]):
        warm.admit(Request(rid=f"warm{i}", prompt=[1] * b,
                           max_new_tokens=2), i % t["slots"])
    warm.step_decode()
    jax.block_until_ready(warm.state)
    del warm
    gc.collect()
    return engine


def compiled_peak(cell, eng) -> int:
    """Bytes the fullest moment of the window needs by the compiled
    programs' own accounts: the decode's footprint (the weights and the
    batched state are its arguments), or the largest prefill's beside the
    batched state it leaves alone.  (The splice's arguments are the state
    and one slot's, donated, so it needs less than the decode.)"""
    import jax
    import jax.numpy as jnp
    from chipbench.harness import compiled_footprint
    t = cell.traffic
    tok = jnp.zeros((t["slots"], 1), jnp.int32)
    decode = eng.step.decode.lower(eng.hosted, tok, eng.state).compile()
    toks = jnp.zeros((1, max(t["buckets"])), jnp.int32)
    prefill = eng.step.prefill.lower(eng.hosted, toks, 1).compile()
    state = sum(x.nbytes for x in jax.tree.leaves(eng.state))
    return max(compiled_footprint(decode),
               compiled_footprint(prefill) + state)


def make_requests(cell, rate: float, seconds: float, seed: int):
    from repro.serve import Request
    return [Request(rid=i, prompt=p, max_new_tokens=o, t_arrival=d)
            for i, (d, p, o) in enumerate(requests(
                cell.traffic, rate, seconds, seed,
                cell.config["vocab_size"]))]


def run(cell, seed: int, seconds: float, spans, compiles, devices):
    from chipbench.harness import runtime_peak
    t = cell.traffic
    rate = float(cell.params["rate_per_s"])
    engine = build(cell, seed)
    eng = engine()
    compiled = compiled_peak(cell, eng)
    reqs = make_requests(cell, rate, seconds, seed)
    win = Window(eng, t["slots"], spans)
    mark = compiles.mark()
    spans.start()
    t0 = time.perf_counter()
    t1 = win.run(reqs, t0, seconds, t["drain_s"])
    in_window = compiles.mark()
    mem = runtime_peak(devices)
    served = [r for r in reqs if r.rid in win.ttft]
    missing = len(reqs) - len(served)
    ttft = list(win.ttft.values()) + [math.inf] * missing
    late = sorted(win.late)
    print(f"serve window: {len(reqs)} requests due at {rate}/s, "
          f"{len(served)} first tokens, {len(win.done)} finished, "
          f"{len(win.failed)} failed; {win.iterations} iterations, "
          f"{len(win.decode_lens)} decodes, {len(win.prefill_lens)} "
          f"prefills in the window; profiler stopped in "
          f"{win.stop_s:.1f} s; ttft median "
          f"{_percentile(ttft, 50) * 1e3:.2f} ms, itl median "
          f"{_percentile(win.gaps, 50) * 1e3:.2f} ms over {len(win.gaps)} "
          f"gaps; arrivals seen late by median "
          f"{_percentile(late, 50) * 1e3:.3f} ms, max "
          f"{(late[-1] if late else 0) * 1e3:.3f} ms; waiting at the "
          f"close {win.queue_len[-1][1] if win.queue_len else 0}; "
          f"compiles in window {in_window[1] - mark[1]}; memory: runtime "
          f"peak {mem} B, compiled programs' peak {compiled} B", flush=True)
    rec = {"kind": "serve", "chips": cell.chips, "window_t0": t0,
           "window_s": t1 - t0, "ttft_s": ttft, "itl_s": list(win.gaps),
           "prefill_lens": win.prefill_lens,
           "decode_lens": win.decode_lens,
           "iterations": win.iterations, "config": cell.config,
           "compiles_in_window": in_window[1] - mark[1],
           "memory_peak_bytes": mem, "memory_compiled_bytes": compiled,
           "attempted": len(reqs), "failed": missing + len(win.failed)}
    finished = win.done
    del eng, win, engine
    gc.collect()
    rec["checks"] = C.with_limits(
        {"logit_gap": _check(cell, seed, finished)},
        cell.params["limits"])
    return rec


def sample(finished, tokens: int, seed: int) -> list:
    """The finished request with the most outputs, then others drawn from
    the seed, until ``tokens`` served tokens are in the sample."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (-len(r.out), r.rid))
    rest = order[1:]
    rng = np.random.default_rng(seed + 1)
    picked, n = [order[0]], len(order[0].out)
    for i in rng.permutation(len(rest)):
        if n >= tokens:
            break
        picked.append(rest[i])
        n += len(rest[i].out)
    return picked


def _check(cell, seed: int, finished) -> float:
    """Widest gap of a served token below the reference's best logit over
    the sample (``nan`` when nothing finished)."""
    gaps = [g for g, _ in reference_gaps(cell, seed, sample(
        finished, cell.traffic["check_tokens"], seed))]
    return max(gaps) if gaps else float("nan")


def reference_gaps(cell, seed: int, reqs, modes=("f32",)):
    """Per request: (widest gap of its served tokens under the float32
    reference, {mode: widest gap of the token ``mode`` puts first}) for
    each other precision in ``modes``."""
    import jax
    import jax.numpy as jnp
    from chipbench.reference import dense
    c = cell.config
    width = cell.traffic["prompt"]["max"] + cell.traffic["output"]["max"]
    params = jax.jit(weights.make_fn(c))(weights.seed_key(seed))
    fwd = {m: jax.jit(lambda p, tok, m=m: dense.logits(p, tok, c, m))
           for m in set(modes) | {"f32"}}
    out = []
    for r in reqs:
        seq = np.zeros((width,), np.int32)
        ids = list(r.prompt) + list(r.out[:-1])
        seq[:len(ids)] = ids
        L = len(r.prompt)
        rows = slice(L - 1, L - 1 + len(r.out))
        ref = np.asarray(fwd["f32"](params, jnp.asarray(seq)))[rows]
        other = {}
        for m in modes:
            if m == "f32":
                continue
            lo = np.asarray(fwd[m](params, jnp.asarray(seq)))[rows]
            other[m] = C.served_gap(ref, lo.argmax(axis=-1))
        out.append((C.served_gap(ref, r.out), other))
    return out
