"""The hybrid serving cell on the CPU at smoke size, past the look for a
chip: a sound run is correct and reads its per-layer metrics, a token
altered where it is produced makes ``correct`` false, and a registry whose
hybrid block is not the configuration file's is refused before anything
is built."""
import json
from pathlib import Path

import numpy as np
import pytest

import smoke

from chipbench import harness as H  # noqa: E402  (smoke puts it on the path)
from chipbench import shapes_hybrid
from chipbench.kinds import serve_hybrid

CELL = "zamba2_serve_chat"
DATA = Path(__file__).resolve().parent / "data" / "zamba2-smoke.json"
# The cell's committed limit (1.4) is set from bf16 readings at full width,
# where the logits spread over several units.  The smoke model's float32
# logits span about 1.3: a sound run reads 0 and a token altered to the
# least likely about 1.0, so the smoke cell holds the same check to 0.3.
SMOKE_LIMIT = 0.3


def smoke_cell():
    """The cell with the registry's smoke-size zamba2-7b, ``smoke.cell``'s
    tiny serving mix and ``SMOKE_LIMIT``."""
    c = H.find_cell(H.load_benchmark(), CELL)
    c.config = json.loads(DATA.read_text())
    c.traffic = dict(
        c.traffic, slots=4, max_seq=64, buckets=[8, 16],
        prompt={"median": 8, "sigma": 0.5, "min": 4, "max": 12},
        output={"median": 6, "sigma": 0.5, "min": 3, "max": 8},
        drain_s=20, check_tokens=30)
    c.params = dict(c.params, rate_per_s=20.0,
                    limits={"logit_gap": SMOKE_LIMIT})
    return c


def test_sound_run_is_correct():
    res = smoke.run_cell(smoke_cell(), trace=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    got = res["metrics"]
    assert {"idle_share.serve", "engine_host_ms.serve", "ttft_p95_ms.serve",
            "prefill_ms.serve", "mamba_ms.serve_hybrid",
            "shared_block_ms.serve_hybrid", "decode_roofline.serve_hybrid",
            "prefill_pad_share.serve_hybrid", "mfu.serve_hybrid"} == set(got)
    assert got["mamba_ms.serve_hybrid"]["value"] > 0
    assert got["shared_block_ms.serve_hybrid"]["value"] > 0
    assert got["prefill_pad_share.serve_hybrid"]["value"] > 0
    assert 0 < got["mfu.serve_hybrid"]["value"] < 100
    assert list(res)[-1] == "checks"


def test_untraced_run_reports_the_end_to_end_metrics():
    res = smoke.run_cell(smoke_cell())
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"peak_hbm_gib", "itl_p95_ms", "setup_s"}


def test_altered_token_is_caught(monkeypatch):
    from repro.serve.engine import ContinuousBatcher
    pick = ContinuousBatcher._next_token

    def altered(self, row, req):
        t = pick(self, row, req)
        # every third token of a request: the least likely one instead
        return int(np.argmin(row)) if len(req.out) % 3 == 2 else t
    monkeypatch.setattr(ContinuousBatcher, "_next_token", altered)
    res = smoke.run_cell(smoke_cell())
    assert not res["correct"], res["checks"]
    assert res["checks"]["logit_gap"]["value"] > 2 * SMOKE_LIMIT


def test_requests_a_stall_hides_at_the_close_are_served(monkeypatch):
    """A host stall across the window's end keeps the loop from seeing the
    requests due in its last second; they are served in the drain, so the
    run stays correct with nothing failed (``Window.run`` alone counts
    them as failed)."""
    import time
    from repro.serve.engine import ContinuousBatcher
    step, run = ContinuousBatcher.step_decode, serve_hybrid.PaddedWindow.run
    t = {}

    def timed(self, reqs, t0, seconds, drain_s):
        t["t0"] = t0
        return run(self, reqs, t0, seconds, drain_s)

    def stalled(self):
        if "t0" in t and not t.get("done") \
                and time.perf_counter() - t["t0"] > 1.2:
            t["done"] = True
            time.sleep(1.5)           # past the 2 s window's end
        return step(self)
    monkeypatch.setattr(serve_hybrid.PaddedWindow, "run", timed)
    monkeypatch.setattr(ContinuousBatcher, "step_decode", stalled)
    res = smoke.run_cell(smoke_cell())
    assert t.get("done")
    assert res["correct"] and res["failed"] == 0, res


@pytest.mark.parametrize("key,value,named", [
    ("num_mem_blocks", 1, "num_mem_blocks"),
    ("adapter_rank", 4, "adapter_rank"),
    ("hybrid_layer_ids", [1, 3], "hybrid_layer_ids"),
    ("attention_hidden_size", 64, "attention width")])
def test_registry_without_the_files_block_is_refused(key, value, named):
    """What a program with another hybrid block meets: the configuration
    check fails before weights are made or anything compiles."""
    c = dict(json.loads(DATA.read_text()), **{key: value})
    with pytest.raises(ValueError, match=named):
        serve_hybrid.program_config(c)


def test_the_files_cut_is_applied():
    full = json.loads((H.HERE / "configs" / "zamba2-7b.json").read_text())
    cfg = serve_hybrid.program_config(full)
    assert cfg.num_layers == 24 and cfg.hybrid_layer_ids == (6, 11, 17, 23)
    assert full["layers_block_type"] == [
        "hybrid" if i in cfg.hybrid_layer_ids else "mamba"
        for i in range(cfg.num_layers)]


def test_decode_counts_at_full_width():
    """The decode's bytes by hand at the cell's size: 3.40e9 parameters
    read (24 Mamba layers, the shared block at each of 4 applications with
    its adapter and projection, the tied head), 1.92 MB of float32 state
    per layer and slot read and written, 28,672 B of K/V per position and
    application (2 x 32 heads x 224 x 2 B)."""
    c = json.loads((H.HERE / "configs" / "zamba2-7b.json").read_text())
    mamba = 3584 * (2 * 7168 + 2 * 128 + 112) + 4 * (7168 + 256) \
        + 7168 * 3584
    block = 7168 * 3 * 7168 + 7168 * 3584 + 3584 * 28672 + 14336 * 3584
    app = 3584 * 128 + 128 * 28672 + 3584 * 3584
    reads = 24 * mamba + 4 * (block + app) + 32000 * 3584
    assert shapes_hybrid.decode_weight_reads(c) == reads
    assert shapes_hybrid.kv_bytes_per_position(c) == 4 * 28672
    state = 24 * (112 * 64 * 64 + 3 * (7168 + 256)) * 4
    assert shapes_hybrid.state_bytes_per_slot(c) == state
    assert shapes_hybrid.decode_bytes(c, [10, 20]) == \
        2 * reads + 2 * (3584 * 2 + 2 * state) + 4 * 28672 * 30


def test_prefill_counts_at_full_width():
    """A prompt's operations by hand at the cell's size: 2 per parameter of
    every matrix but the head and per token, 4·P·S per Mamba head, layer
    and token for the SSD recurrence, 4·H·hd per attended pair and
    application (n(n+1)/2 pairs, causal), and the head once."""
    c = json.loads((H.HERE / "configs" / "zamba2-7b.json").read_text())
    mats = shapes_hybrid.decode_weight_reads(c) - 32000 * 3584
    ssd = 24 * 4 * 112 * 64 * 64
    attn = 4 * 4 * 32 * 224
    n = 100
    want = (2 * mats + ssd) * n + 2 * 32000 * 3584 + attn * n * (n + 1) / 2
    assert shapes_hybrid.prefill_flops(c, n) == pytest.approx(want, rel=1e-12)
    # one token's prefill is one decode step at length 1
    assert shapes_hybrid.prefill_flops(c, 1) == pytest.approx(
        shapes_hybrid.decode_flops(c, [1]), rel=1e-12)
