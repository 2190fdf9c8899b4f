"""A training run on the CPU at smoke size, past the look for a chip: a
sound step is correct, and each fault planted in the timed step makes
``correct`` false; the control (the reference in fp8) fails the limits."""
import pytest

import smoke

CELL = "mamba2_train_1chip"


def test_sound_run_is_correct():
    res = smoke.run_cell(smoke.with_train_metrics(
        smoke.cell(CELL, candidate=True)), trace=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert {"idle_share.train", "mfu.train"} <= set(res["metrics"])
    assert res["device"]["busy_s"] > 0
    assert list(res)[-1] == "checks"


def _broken(monkeypatch, fault):
    import repro.launch.steps as steps
    build = steps.build_train_step_lane

    def patched(*a, **k):
        step, comm = build(*a, **k)

        def bad(params, opt, tokens, labels, extra=None):
            if fault == "unchanged":
                loss, _, _ = step(params, opt, tokens, labels, extra)
                return loss, params, opt
            n = tokens.shape[0] // 2
            return step(params, opt, tokens[:n], labels[:n], extra)
        return bad, comm
    monkeypatch.setattr(steps, "build_train_step_lane", patched)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_fault_in_the_step_is_caught(monkeypatch, fault):
    _broken(monkeypatch, fault)
    res = smoke.run_cell(smoke.cell(CELL, candidate=True))
    assert not res["correct"], res["checks"]


def test_control_fails_the_limits():
    from chipbench import checks as C
    from chipbench.kinds import train
    cell = smoke.cell(CELL, candidate=True)
    seed = 11
    ref = train.reference(cell, seed)
    ctl = train.reference(cell, seed, mode="fp8")
    got = C.with_limits(C.train_numbers(ctl["losses"], ctl["grad1"],
                                        ctl["change"], ref),
                        cell.params["limits"])
    from chipbench import harness as H
    assert not H.judge(got), got
