"""The reduction of the program's own tracing: op scopes, the leaf rule,
the decode's split by scope, host time per engine span, gaps named by the
innermost span, and the engine's spans read back from a CPU trace."""
import gc
import math
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1]), str(HERE.parents[1] / "src")]

from chipbench import harness as H  # noqa: E402
from chipbench import program_trace as P  # noqa: E402
from chipbench import trace as T  # noqa: E402


@pytest.mark.parametrize("op_name,scope", [
    ("jit(_decode)/while/body/closed_call/attn/kv_write/jit(_where)/select_n",
     "kv_write"),
    ("jit(_decode)/while/body/closed_call/attn/bkgd,bskd->bkgs/dot_general",
     "attn"),
    ("jit(_decode)/while/body/closed_call/mlp/dot_general", "mlp"),
    ("jit(_decode)/while/body/dynamic_update_slice", ""),
    ("jit(_decode)/attention_mask/mul", ""),
])
def test_scope_of(op_name, scope):
    assert P.scope_of(op_name) == scope


HLO = """\
%fused_computation.3 (param_0: bf16[2,4]) -> bf16[2,4] {
  %select_n.20 = bf16[4]{0} select(%a, %b, %c), metadata={op_name="jit(_decode)/while/body/closed_call/attn/kv_write/select_n"}
  ROOT %dynamic_update_slice.6 = bf16[2,4]{1,0} dynamic-update-slice(%p, %select_n.20), metadata={op_name="jit(_decode)/while/body/dynamic_update_slice"}
}
ENTRY %main.1 () -> bf16[2,4] {
  %fusion.131 = bf16[16]{0} fusion(%x), kind=kOutput, calls=%f, metadata={op_name="jit(_decode)/while/body/closed_call/mlp/dot_general"}
  %fusion.7 = bf16[16]{0} fusion(%x), kind=kLoop, metadata={op_name="jit(_decode)/while/body/closed_call/attn/add"}
  %select_dynamic-update-slice_fusion.3 = bf16[2,4]{1,0} fusion(%y), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(_decode)/while/body/dynamic_update_slice"}
  %while.4 = (s32[]) while(%t), condition=%c, body=%b, metadata={op_name="jit(_decode)/while"}
  ROOT %copy.35 = bf16[2,4]{1,0} copy(%z)
}
"""


def test_hlo_scopes_and_instruction_names():
    scopes = P.hlo_scopes(HLO)
    assert scopes["select_n.20"] == "kv_write"
    assert scopes["fusion.131"] == "mlp"
    assert scopes["fusion.7"] == "attn"
    # the fusion takes its root's op_name: the scan's own stacking
    assert scopes["select_dynamic-update-slice_fusion.3"] == ""
    assert scopes["copy.35"] == ""
    # a device plane may name an op event by its whole instruction text
    assert P.instruction("%fusion.131 = bf16[16]{0} fusion(%x)") == \
        "fusion.131"
    assert P.instruction("fusion.131") == "fusion.131"


def _decode_trace():
    """One chip, window [0, 200] ns, two decode calls (0-80, 100-180) each
    a ``while`` holding an attn, a kv_write and an mlp op and the scan's
    unscoped stacking, then a copy outside the loop; one prefill op.  A
    v5e plane names each op event by its instruction's text."""
    ops, modules = [], []
    for base in (0, 100):
        ops += [("while.4", base + 5, base + 60),
                ("fusion.7", base + 10, base + 20),
                ("select_n.20", base + 20, base + 25),
                ("%fusion.131 = bf16[16]{0} fusion(%x), kind=kOutput",
                 base + 25, base + 45),
                ("select_dynamic-update-slice_fusion.3", base + 45,
                 base + 58),
                ("copy.35", base + 62, base + 78)]
        modules.append(("jit__decode(3)", base, base + 80))
    ops.append(("fusion.9", 185, 195))
    modules.append(("jit__prefill(5)", 184, 196))
    spans = [("chipbench.window", 0, 200), ("chipbench.iteration", 0, 99),
             ("serve.decode", 0, 90), ("serve.dispatch", 0, 4),
             ("serve.fetch", 4, 82), ("serve.sample", 82, 90),
             ("host.gc", 83, 89), ("serve.decode", 100, 182),
             ("serve.admit", 183, 200)]
    return P.from_events([(ops, modules)], spans,
                         hlo={"jit__decode": HLO})


def test_leaf_rule_counts_no_while_twice():
    red = _decode_trace()
    ops = red.chips[0]
    leaves = {o.name for o in ops if o.leaf}
    assert "while.4" not in leaves
    assert {"fusion.7", "select_n.20", "copy.35",
            "%fusion.131 = bf16[16]{0} fusion(%x), kind=kOutput",
            "select_dynamic-update-slice_fusion.3", "fusion.9"} == leaves
    # equal intervals hold each other neither way
    same = P.mark_leaves([P.ScopedOp("a", 0, 10), P.ScopedOp("b", 0, 10)])
    assert all(o.leaf for o in same)


def test_scope_split_partitions_the_decode_leaf_time():
    red = _decode_trace()
    split = P.scope_ms(red, "decode", calls=2)
    assert split == pytest.approx({"attn": 10e-6, "kv_write": 5e-6,
                                   "mlp": 20e-6, "": (13 + 16) * 1e-6})
    leaf = sum(o.end - o.start for o in red.chips[0]
               if o.leaf and o.module == "jit__decode") / 2 * 1e-6
    assert sum(split.values()) == pytest.approx(leaf)
    assert P.has_scopes(red)
    # a program without named scopes (or no compiled text) gives none
    assert not P.has_scopes(P.from_events(
        [([("fusion.7", 0, 10)], [("jit__decode(1)", 0, 10)])],
        [("chipbench.window", 0, 20)]))
    # busy time of the decode holds the while itself, so more than leaves
    assert T.module_s(red, "decode") * 1e3 / 2 > leaf


def test_host_time_per_engine_span():
    red = _decode_trace()
    # serve.decode 0-90 holds 71 ns of device time (5-60, 62-78), and
    # 100-182 as much: (19 + 11) / 2 host ns per call
    assert P.host_ms(red, "serve.decode") == pytest.approx(15e-6)
    # serve.admit 183-200 holds the prefill op 185-195
    assert P.host_ms(red, "serve.admit") == pytest.approx(7e-6)
    assert P.host_ms(red, "serve.splice") is None


def test_gaps_are_named_by_the_innermost_span_covering_them():
    red = _decode_trace()
    gaps = {round(s * 1e9): n for n, s in P.idle_gaps(red)}
    # the longest gap, 78-105: the iteration 0-99 alone covers half of it
    assert gaps[27] == "chipbench.iteration"
    # 83-89 inside host.gc, serve.sample, serve.decode, iteration
    assert P.covering_span(red.spans, (83, 89)) == "host.gc"
    # 60-62 between the loop and the copy: inside serve.fetch
    assert P.covering_span(red.spans, (60, 62)) == "serve.fetch"
    # 195-200: serve.admit 183-200 covers all of it
    assert gaps[5] == "serve.admit"


def _serve_reduced(trace_path, hlo=None):
    return P.reduce(trace_path, hlo=hlo, host_ops_as_chip=True)


def _same_readings(path, hlo=None):
    """Every serving reader of the benchmark reads the same value from
    this reduction as from ``trace.reduce``; returns this reduction."""
    old = T.reduce(path, host_ops_as_chip=True)
    new = _serve_reduced(path, hlo)
    assert [(o.name, o.start, o.end, o.module) for o in old.chips[0]] == \
        [(o.name, o.start, o.end, o.module) for o in new.chips[0]]
    assert old.window == new.window
    assert old.spans == [s for s in new.spans
                         if s[0].startswith(T.SPAN_PREFIX)]
    base = {"kind": "serve", "prefill_lens": [8, 8], "decode_lens": [[9]],
            "config": {"num_hidden_layers": 2, "hidden_size": 64,
                       "num_attention_heads": 4, "num_key_value_heads": 2,
                       "head_dim": 16, "intermediate_size": 128,
                       "vocab_size": 256, "tie_word_embeddings": False,
                       "dtype": "float32"},
            "peak": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
            "itl_s": [0.01], "ttft_s": [0.02]}
    names = [m["name"] for m in H.load_benchmark()["per_layer"]
             if m["name"].endswith(".serve")]
    assert names
    for name in names:
        read = H.metric_reader(name)
        a, b = read(dict(base, trace=old)), read(dict(base, trace=new))
        assert (a is None and b is None) or a == pytest.approx(b), name
    return new


def test_existing_readers_read_the_same_from_this_reduction():
    """On the recorded CPU trace the benchmark's serving readers read
    what they read from ``trace.reduce``."""
    _same_readings(str(HERE / "data" / "trace_cpu.xplane.pb"))


def test_engine_spans_under_the_profiler(tmp_path):
    """The engine served under the profiler on the CPU writes the six
    ``serve.*`` spans, nested as the engine's docstring says, and a
    ``host.gc`` span round a forced collection; the host time per admit
    and per decode reads finite."""
    import jax
    import jax.numpy as jnp
    from repro.configs import resolve
    from repro.models import init_model
    from repro.serve import ContinuousBatcher, Request, build_serve_step
    cfg = resolve("h2o-danube-3-4b", smoke=True)
    params = init_model(jax.random.PRNGKey(0), cfg)
    step = build_serve_step(cfg, max_seq=64, slots=2)
    eng = ContinuousBatcher(params, cfg, slots=2, max_seq=64, step=step,
                            buckets=(16,))
    eng.admit(Request(rid="warm", prompt=[1, 2], max_new_tokens=3), 0)
    eng.step_decode()
    hlo = eng.step.decode.lower(eng.hosted, jnp.zeros((2, 1), jnp.int32),
                                eng.state).compile().as_text()
    jax.block_until_ready(eng.state)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    span = jax.profiler.TraceAnnotation
    with span("chipbench.window"):
        with span("chipbench.iteration"):
            eng.admit(Request(rid=1, prompt=[3, 4, 5], max_new_tokens=6),
                      1)
        for _ in range(3):
            with span("chipbench.iteration"):
                eng.step_decode()
        gc.collect()
    jax.profiler.stop_trace()
    red = _same_readings(T.find_xplane(str(tmp_path)),
                         hlo={"jit__decode": hlo})
    spans = {}
    for name, s, e in red.spans:
        spans.setdefault(name, []).append((s, e))
    assert {"serve.admit", "serve.decode", "serve.dispatch", "serve.fetch",
            "serve.sample", "serve.splice", "host.gc"} <= set(spans)
    assert len(spans["serve.admit"]) == 1 and len(spans["serve.decode"]) == 3

    def inside(iv, names):
        return any(s <= iv[0] and iv[1] <= e
                   for n in names for s, e in spans[n])
    for name in ("serve.dispatch", "serve.fetch", "serve.sample"):
        assert len(spans[name]) == 4
        assert all(inside(iv, ("serve.admit", "serve.decode"))
                   for iv in spans[name]), name
    assert all(inside(iv, ("serve.admit",)) for iv in spans["serve.splice"])
    for name in ("serve.decode", "serve.admit"):
        v = P.host_ms(red, name)
        assert v is not None and math.isfinite(v) and v > 0, name
    # the decode's ops carry the model's scopes through the compiled text
    split = P.scope_ms(red, "decode", calls=3)
    assert all(split[s] > 0 for s in P.SCOPES)
