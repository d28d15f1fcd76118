"""The exact host settle pass, which re-derives each refuted key's
verdict and detail (`wgl.plan.pass.settle-exact` spans), per check."""

from benchmark.harness import intervals


def read(w):
    return intervals.union_per_check(w, "wgl.plan.pass.settle-exact")
