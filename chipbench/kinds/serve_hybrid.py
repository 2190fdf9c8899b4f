"""Serving cells of the hybrid family (Zamba2): the loop of
``kinds/serve.py`` over a hybrid configuration, its weights and its
reference (``reference/zamba2.py``).

Before it makes weights or compiles anything, the registry's
configuration is held to the configuration file (the shared blocks, their
applications, the adapter rank, the attention's width and every other
size) and cut as the file's ``reduced`` keys say: a program whose hybrid
block is not the file's exits here.  The mix and the window are
``kinds/serve.py``'s; the record also holds the padded prompt positions
the engine prefilled in the window, and in a traced run the decode's leaf
device time per call by the model's innermost scope (``attn``,
``kv_write``, ``mlp``, ``mamba``, ``ssd``, and ``""`` for none), each op's
scope read from the compiled decode's text.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import math
import re
import time
from functools import partial

import numpy as np

from chipbench import checks as C
from chipbench import weights
from chipbench.kinds.serve import (Window, _percentile, compiled_peak,
                                   make_requests, sample)

SCOPES = ("attn", "kv_write", "mlp", "mamba", "ssd")
# a published ``hidden_act`` under the program's name for the same function
# (transformers' ``gelu`` is the erf form)
PUBLISHED_ACT = {"gelu": "gelu_exact"}
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s+=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def program_config(config: dict):
    """The registry's ``config["registry"]`` held to the file and cut to
    its layers; raises ValueError where a size or the block differs."""
    from repro.configs import resolve
    cfg = resolve(config["registry"],
                  smoke=bool(config.get("registry_smoke")))
    L = config["num_hidden_layers"]
    ids = tuple(config["hybrid_layer_ids"])
    have = lambda k: getattr(cfg, k, None)
    pairs = {"family": (have("family"), config["family"]),
             "num_mem_blocks": (have("num_mem_blocks"),
                                config["num_mem_blocks"]),
             "adapter_rank": (have("adapter_rank"), config["adapter_rank"]),
             "hybrid_layer_ids below num_hidden_layers": (
                 tuple(i for i in have("hybrid_layer_ids") or () if i < L),
                 ids),
             "attention width": (2 * cfg.d_model,
                                 config["attention_hidden_size"]),
             "head_dim": (cfg.hd(), config["attention_head_dim"]),
             "d_model": (cfg.d_model, config["hidden_size"]),
             "num_heads": (cfg.num_heads, config["num_attention_heads"]),
             "num_kv_heads": (cfg.num_kv_heads,
                              config["num_key_value_heads"]),
             "d_ff": (cfg.d_ff, config["intermediate_size"]),
             "act": (cfg.act, PUBLISHED_ACT.get(config["hidden_act"],
                                                config["hidden_act"])),
             "vocab_size": (cfg.vocab_size, config["vocab_size"]),
             "ssm_state": (cfg.ssm_state, config["mamba_d_state"]),
             "ssm_head_dim": (cfg.ssm_head_dim, config["mamba_headdim"]),
             "ssm_expand": (cfg.ssm_expand, config["mamba_expand"]),
             "ssm_heads": (cfg.ssm_heads(), config["n_mamba_heads"]),
             "ssm_groups": (cfg.ssm_groups, config["mamba_ngroups"]),
             "ssm_conv_width": (cfg.ssm_conv_width, config["mamba_d_conv"]),
             "ssm_chunk": (cfg.ssm_chunk, config["chunk_size"]),
             "rope_theta": (cfg.rope_theta, config["rope_theta"]),
             "norm_eps": (cfg.norm_eps, config["rms_norm_eps"]),
             "tie_embeddings": (cfg.tie_embeddings,
                                config["tie_word_embeddings"]),
             "dtype": (cfg.dtype, config["dtype"]),
             "num_layers >= num_hidden_layers": (cfg.num_layers >= L, True)}
    bad = {k: v for k, v in pairs.items() if v[0] != v[1]}
    if bad:
        raise ValueError(f"registered {cfg.name} is not the hybrid block "
                         f"of its configuration file (program, file): "
                         f"{bad}")
    return dataclasses.replace(cfg, num_layers=L, hybrid_layer_ids=ids)


def weights_fn(config: dict):
    """``fn(key) -> params`` of the reference's seeded weights; jit it."""
    from chipbench.reference import zamba2
    return partial(zamba2.weights, config)


def build(cell, seed: int):
    """As ``kinds/serve.py:build``: a factory of fresh engines over the
    cell's warmed-up step and the seed's weights."""
    import jax
    from repro.serve import ContinuousBatcher, Request, build_serve_step
    cfg = program_config(cell.config)
    t = cell.traffic
    params = jax.jit(weights_fn(cell.config))(weights.seed_key(seed))
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


class PaddedWindow(Window):
    """``Window``, counting the padded prompt positions the engine
    prefilled for the requests admitted in the window."""

    def __init__(self, engine, slots: int, spans):
        super().__init__(engine, slots, spans)
        self.pad_positions = 0

    def _admit(self, queue, in_window: bool):
        before = self.engine.pad_positions
        super()._admit(queue, in_window)
        if in_window:
            self.pad_positions += self.engine.pad_positions - before

    def run(self, reqs, t0: float, seconds: float, drain_s: float):
        """``Window.run``, then the requests due in the window that the
        loop never saw: a host stall across the window's end leaves them
        among the arrivals, not in the queue the drain serves.  They are
        served in what is left of the drain, like the others."""
        t1 = super().run(reqs, t0, seconds, drain_s)
        if self.failed:               # the drain ran out already
            return t1
        unseen = collections.deque(sorted(
            (r for r in reqs if r.rid not in self.ttft),
            key=lambda r: r.t_arrival))
        now = time.perf_counter()
        self.late += [now - r.t_arrival for r in unseen]
        stop = t1 + self.stop_s + drain_s
        while (unseen or self.active) and time.perf_counter() < stop:
            self._admit(unseen, False)
            if self.active:
                self._decode(False)
        self.failed += list(unseen) + list(self.active.values())
        return t1


def scope_of(op_name: str) -> str:
    """The innermost of ``SCOPES`` in an HLO ``op_name`` path, or ``""``."""
    found = ""
    for part in op_name.split("/"):
        if part in SCOPES:
            found = part
    return found


def hlo_scopes(text: str) -> dict:
    """{instruction name: scope} over a compiled program's text."""
    out = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(line)
            out[m.group(1)] = scope_of(op.group(1)) if op else ""
    return out


def decode_scope_ms(path: str, decode_text: str, calls: int,
                    host_ops_as_chip: bool = False) -> dict:
    """{scope: ms per call} of the decode's leaf ops in the trace at
    ``path``, averaged over chips; the values sum to its leaf time."""
    from chipbench import program_trace as P
    red = P.reduce(path, host_ops_as_chip=host_ops_as_chip)
    names = hlo_scopes(decode_text)
    acc = dict.fromkeys(SCOPES + ("",), 0.0)
    for ops in red.chips:
        for o in ops:
            if o.leaf and "decode" in o.module:
                acc[names.get(P.instruction(o.name), "")] += o.end - o.start
    k = len(red.chips) * max(calls, 1)
    return {s: t / k * 1e-6 for s, t in acc.items()}


def _decode_text(cell, eng) -> str:
    import jax.numpy as jnp
    tok = jnp.zeros((cell.traffic["slots"], 1), jnp.int32)
    return eng.step.decode.lower(eng.hosted, tok, eng.state).compile() \
        .as_text()


def run(cell, seed: int, seconds: float, spans, compiles, devices):
    from chipbench.harness import runtime_peak
    t = cell.traffic
    rate = float(cell.params["rate_per_s"])
    engine = build(cell, seed)
    eng = engine()
    compiled = compiled_peak(cell, eng)
    reqs = make_requests(cell, rate, seconds, seed)
    win = PaddedWindow(eng, t["slots"], spans)
    mark = compiles.mark()
    spans.start()
    t0 = time.perf_counter()
    t1 = win.run(reqs, t0, seconds, t["drain_s"])
    in_window = compiles.mark()
    mem = runtime_peak(devices)
    served = [r for r in reqs if r.rid in win.ttft]
    missing = len(reqs) - len(served)
    ttft = list(win.ttft.values()) + [math.inf] * missing
    true_positions = sum(win.prefill_lens)
    late = sorted(win.late)
    print(f"serve window: {len(reqs)} requests due at {rate}/s, "
          f"{len(served)} first tokens, {len(win.done)} finished, "
          f"{len(win.failed)} failed; {win.iterations} iterations, "
          f"{len(win.decode_lens)} decodes, {len(win.prefill_lens)} "
          f"prefills in the window ({true_positions} prompt positions, "
          f"{win.pad_positions} padded); profiler stopped in "
          f"{win.stop_s:.1f} s; ttft median "
          f"{_percentile(ttft, 50) * 1e3:.2f} ms, itl median "
          f"{_percentile(win.gaps, 50) * 1e3:.2f} ms over {len(win.gaps)} "
          f"gaps; arrivals seen late by median "
          f"{_percentile(late, 50) * 1e3:.3f} ms, max "
          f"{(late[-1] if late else 0) * 1e3:.3f} ms; waiting at the "
          f"close {win.queue_len[-1][1] if win.queue_len else 0}; "
          f"compiles in window {in_window[1] - mark[1]}; memory: "
          f"runtime peak {mem} B, compiled programs' peak {compiled} B",
          flush=True)
    rec = {"kind": "serve", "chips": cell.chips, "window_t0": t0,
           "window_s": t1 - t0, "ttft_s": ttft, "itl_s": list(win.gaps),
           "prefill_lens": win.prefill_lens,
           "pad_positions": win.pad_positions,
           "decode_lens": win.decode_lens,
           "iterations": win.iterations, "config": cell.config,
           "compiles_in_window": in_window[1] - mark[1],
           "memory_peak_bytes": mem, "memory_compiled_bytes": compiled,
           "attempted": len(reqs), "failed": missing + len(win.failed)}
    if spans.path:
        rec["decode_scope_ms"] = split = decode_scope_ms(
            spans.path, _decode_text(cell, eng), len(win.decode_lens),
            host_ops_as_chip=devices[0].platform != "tpu")
        print("decode ms a call by scope: " + ", ".join(
            f"{s or 'unscoped'} {v:.4f}" for s, v in split.items()),
            flush=True)
    finished = win.done
    del eng, win, engine
    gc.collect()
    rec["checks"] = C.with_limits(
        {"logit_gap": check(cell, seed, finished)}, cell.params["limits"])
    return rec


def check(cell, seed: int, finished) -> float:
    """Widest gap of a served token below the reference's best logit over
    the sample (``nan`` when nothing finished)."""
    gaps = [g for g, _ in reference_gaps(cell, seed, sample(
        finished, cell.traffic["check_tokens"], seed))]
    return max(gaps) if gaps else float("nan")


def reference_gaps(cell, seed: int, reqs, modes=("f32",)):
    """As ``kinds/serve.py:reference_gaps``, under ``reference/zamba2.py``:
    per request, (widest gap of its served tokens under the float32
    reference, {mode: widest gap of the token ``mode`` puts first})."""
    import jax
    import jax.numpy as jnp
    from chipbench.reference import zamba2
    c = cell.config
    width = cell.traffic["prompt"]["max"] + cell.traffic["output"]["max"]
    params = jax.jit(weights_fn(c))(weights.seed_key(seed))
    fwd = {m: jax.jit(lambda p, tok, m=m: zamba2.logits(p, tok, c, m))
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
