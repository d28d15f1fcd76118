"""The refutation screens: the single-history screen (`wgl.screen`) and
the cohort screen pass (`wgl.plan.pass.refute-screen`), their union per
check."""

from benchmark.harness import intervals


def read(w):
    return intervals.union_per_check(w, "wgl.screen",
                                      "wgl.plan.pass.refute-screen")
