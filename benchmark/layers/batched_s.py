"""The batched frontier BFS pass over the screens' survivors
(`wgl.plan.pass.batched-bfs` spans), per check."""

from benchmark.harness import intervals


def read(w):
    return intervals.union_per_check(w, "wgl.plan.pass.batched-bfs")
