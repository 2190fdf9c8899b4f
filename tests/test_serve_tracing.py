"""The serving path's own tracing: the model's named scopes reach the
compiled programs' op_names, and ``admit`` stamps a request's arrival
before its prefill.  (The engine's host spans under the profiler are read
back in chipbench/tests/test_program_trace.py, through the reduction the
benchmark uses.)"""
import re

import pytest

import jax
import jax.numpy as jnp

from repro.configs import resolve
from repro.models import init_model
from repro.serve import ContinuousBatcher, Request, build_serve_step

SLOTS, MAX_SEQ, BUCKET = 4, 64, 32
SCOPES = ("attn", "kv_write", "mlp")


@pytest.fixture(scope="module")
def engine():
    cfg = resolve("h2o-danube-3-4b", smoke=True)
    params = init_model(jax.random.PRNGKey(0), cfg)
    step = build_serve_step(cfg, max_seq=MAX_SEQ, slots=SLOTS)
    return ContinuousBatcher(params, cfg, slots=SLOTS, max_seq=MAX_SEQ,
                             step=step, buckets=(BUCKET,))


def _compiled_text(eng, program: str) -> str:
    if program == "decode":
        tok = jnp.zeros((SLOTS, 1), jnp.int32)
        lowered = eng.step.decode.lower(eng.hosted, tok, eng.state)
    else:
        toks = jnp.zeros((1, BUCKET), jnp.int32)
        lowered = eng.step.prefill.lower(eng.hosted, toks, 1)
    return lowered.compile().as_text()


def _op_names(text: str) -> list:
    """(instruction text, op_name) of every instruction that has one."""
    return [(line, m.group(1)) for line in text.splitlines()
            for m in [re.search(r'op_name="([^"]*)"', line)] if m]


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_compiled_programs_carry_the_model_scopes(engine, program):
    names = _op_names(_compiled_text(engine, program))
    parts = {p for _, n in names for p in n.split("/")}
    assert set(SCOPES) <= parts
    # kv_write sits inside attn, never beside it
    assert all("/attn/kv_write/" in n for _, n in names if "kv_write" in n)


def test_decode_cache_select_sits_inside_kv_write(engine):
    """The decode's cache write is scoped ``kv_write``: every op that writes
    the stacked K or V cache ([layers, slots, max_seq, kv heads, head
    dim]) in place, and every select of the rows it writes ([slots, kv
    heads, head dim]: the new row, or a slot past max_seq's row kept)."""
    cfg = engine.cfg
    K, hd = cfg.num_kv_heads, cfg.hd()
    stack = re.escape(f"[{cfg.num_layers},{SLOTS},{MAX_SEQ},{K},{hd}]")
    rows = re.escape(f"[{SLOTS},{K},{hd}]")
    names = _op_names(_compiled_text(engine, "decode"))
    writes = [n for line, n in names
              if (m := re.search(r"= \w+" + stack + r"\S* ([\w-]+)\(", line))
              and m.group(1) not in ("parameter", "get-tuple-element")]
    selects = [n for line, n in names
               if re.search(r"= \w+" + rows + r"\S* select\(", line)]
    assert writes and selects
    assert all("/attn/kv_write/" in n for n in writes + selects), \
        writes + selects


def test_admit_stamps_arrival_before_the_prefill(engine, monkeypatch):
    """A request with no arrival stamp is stamped on entry to ``admit``,
    so its time to first token holds its own prefill."""
    import time
    seen = {}
    orig = ContinuousBatcher._extra_embeds

    def at_prefill(self, req):
        seen["prefill"] = time.perf_counter()
        return orig(self, req)
    monkeypatch.setattr(ContinuousBatcher, "_extra_embeds", at_prefill)
    req = Request(rid="r", prompt=[1, 2, 3], max_new_tokens=4)
    engine.admit(req, 0)
    assert req.t_arrival is not None
    assert req.t_arrival <= seen["prefill"] < req.t_first
