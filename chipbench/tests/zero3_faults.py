"""Run by test_chipbench_zero3_faults.py in a process of its own with four
CPU devices: the lane_zero3 training cell at smoke size, sound and with
each fault planted in the timed step.  Prints one JSON line of
{case: correct}."""
import json
import sys

import jax
import jax.numpy as jnp

import smoke

CELL = "mamba2_train_zero3_4chip"


def no_exchange():
    """The ZeRO-3 gathers' transpose left as each chip's own slot of the
    cotangent: no gradient crosses a chip."""
    from repro.comm.lanecomm import LaneComm
    gather = LaneComm.prefetch_allgather

    def local(self, shard, **kw):
        mine = self.topo.global_rank().astype(shard.dtype)
        own = (gather(self, jnp.full_like(shard, mine), **kw)
               == mine).astype(shard.dtype)

        @jax.custom_vjp
        def f(x, own):
            return gather(self, x, **kw)

        def fwd(x, own):
            return f(x, own), (x, own)

        def bwd(res, ct):
            x, own = res
            _, vjp = jax.vjp(lambda y: gather(self, y, **kw), x)
            return vjp(ct * own.astype(ct.dtype))[0], jnp.zeros_like(own)
        f.defvjp(fwd, bwd)
        return f(shard, own)
    LaneComm.prefetch_allgather = local


def broken(fault):
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
    steps.build_train_step_lane = patched


def main():
    case = sys.argv[1]
    if case == "no_exchange":
        no_exchange()
    elif case != "sound":
        broken(case)
    res = smoke.run_cell(smoke.with_train_metrics(
        smoke.cell(CELL, candidate=True)),
        trace=case == "sound")
    print(json.dumps({"case": case, "correct": res["correct"],
                      "checks": res["checks"],
                      "metrics": sorted(res["metrics"])}))


if __name__ == "__main__":
    main()
