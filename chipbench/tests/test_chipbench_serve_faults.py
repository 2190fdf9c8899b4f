"""A serving run on the CPU at smoke size, past the look for a chip: sound
serving is correct, a token altered where it is produced makes
``correct`` false, and the control (the reference in fp8) reads far above
the sound run."""
import numpy as np

import smoke

CELL = "danube_serve_chat"


def test_sound_run_is_correct():
    res = smoke.run_cell(smoke.cell(CELL), trace=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert {"idle_share.serve", "engine_host_ms.serve", "prefill_ms.serve",
            "decode_roofline.serve", "mfu.serve",
            "ttft_p95_ms.serve"} <= set(res["metrics"])
    assert list(res)[-1] == "checks"


def test_altered_token_is_caught(monkeypatch):
    from repro.serve.engine import ContinuousBatcher
    pick = ContinuousBatcher._next_token

    def altered(self, row, req):
        t = pick(self, row, req)
        # every third token of a request: the least likely one instead
        return int(np.argmin(row)) if len(req.out) % 3 == 2 else t
    monkeypatch.setattr(ContinuousBatcher, "_next_token", altered)
    res = smoke.run_cell(smoke.cell(CELL))
    assert not res["correct"], res["checks"]


def test_control_reads_far_above_the_sound_run(capsys):
    """The control (the reference in fp8 choosing each token) against the
    float32 reference, beside the sound program's served tokens, on the
    same prompts.  At smoke size the logits are small (d_model 64), so the
    control is held against the sound run here; against the cell's limit
    it is read on the chip at the cell's own size (PERF.md)."""
    import json
    from chipbench import control
    cell = smoke.cell(CELL)
    control.serve_readings(cell, [3], {3}, 2.0)
    got = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert got["tokens"] >= 20
    assert got["control"]["logit_gap"] > 0.0
    assert got["control"]["logit_gap"] >= 10 * got["program"]["logit_gap"]
    assert got["correct"]["program"] is True
