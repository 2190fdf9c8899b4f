"""The comparison numbers and the serving mix's generator."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1]), str(HERE.parents[1] / "src")]

from chipbench import checks as C  # noqa: E402
from chipbench import harness as H  # noqa: E402


def test_leaf_gap_uses_the_larger_of_leaf_and_median():
    ref = np.array([1.0, 2.0, 4.0, 0.001])
    prog = np.array([1.1, 2.0, 4.0, 0.002])
    gap = C.leaf_gap(prog, ref)
    # median of ref is 1.5: the tiny leaf is judged against it
    assert gap == pytest.approx([0.1 / 1.5, 0.0, 0.0, 0.001 / 1.5])


def test_train_numbers_leave_out_leaves_that_do_not_move():
    ref = {"losses": [2.0, 1.0], "grad1": np.array([1.0, 1.0, 1e-6]),
           "change": np.array([1.0, 1.0, 1.0])}
    got = C.train_numbers([2.02, 1.0], np.array([1.0, 1.1, 0.0]),
                          np.array([1.0, 1.0, 5.0]), ref)
    assert got["loss_gap"] == pytest.approx(0.01)
    assert got["grad_gap"] == pytest.approx(0.1)
    # the third leaf's reference gradient is under a thousandth of the
    # median: its change (round-off) is not compared
    assert got["change_gap"] == 0.0


def test_state_left_unchanged_reads_one():
    ref = {"losses": [2.0], "grad1": np.ones(3), "change": np.ones(3)}
    got = C.train_numbers([2.0], np.ones(3), np.zeros(3), ref)
    assert got["change_gap"] == pytest.approx(1.0)


def test_served_gap():
    logits = np.array([[0.0, 3.0, 1.0], [2.0, 0.0, 1.5]])
    assert C.served_gap(logits, [1, 0]) == 0.0
    assert C.served_gap(logits, [2, 2]) == pytest.approx(2.0)


def test_judge():
    ok = {"a": {"value": 0.1, "limit": 0.2}}
    assert H.judge(ok)
    assert not H.judge({"a": {"value": 0.3, "limit": 0.2}})
    assert not H.judge({"a": {"value": float("nan"), "limit": 0.2}})


def test_every_seed_gets_the_same_work_in_another_order():
    from chipbench.kinds import serve
    cell = H.find_cell(H.load_benchmark(), "danube_serve_chat")
    a = serve.requests(cell.traffic, 8.0, 30.0, 2 ** 33 + 1, 32000)
    b = serve.requests(cell.traffic, 8.0, 30.0, 12345, 32000)
    assert len(a) == len(b) == 240
    for key in (lambda r: len(r[1]), lambda r: r[2]):
        assert sorted(map(key, a)) == sorted(map(key, b))
        assert list(map(key, a)) != list(map(key, b))
    assert a[-1][0] < 30.0 and b[-1][0] < 30.0
    gap = lambda reqs: set(np.round(np.diff([r[0] for r in reqs]), 9))
    assert len(gap(a) - gap(b)) <= 1     # the gap after the last falls out
    assert not np.array_equal(a[0][1], b[0][1][:len(a[0][1])])
    lens = [len(r[1]) for r in a]
    assert min(lens) >= 16 and max(lens) <= 1024
    assert np.median(lens) == pytest.approx(128, rel=0.05)


def test_configuration_files_match_the_registry():
    from repro.configs import resolve
    for name, reg in (("mamba2-780m", "mamba2-780m"),
                      ("h2o-danube-3-4b", "h2o-danube-3-4b")):
        c = json.loads((H.HERE / "configs" / f"{name}.json").read_text())
        C.config_matches(resolve(reg), c)
    bad = json.loads((H.HERE / "configs" / "mamba2-780m.json").read_text())
    bad["d_model"] = 2048
    with pytest.raises(ValueError):
        C.config_matches(resolve("mamba2-780m"), bad)
