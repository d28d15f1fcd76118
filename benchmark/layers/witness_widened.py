"""Wide witness rungs run: the `wgl.witness.widened` counter's growth,
per check."""


def read(w):
    n = (w.counters or {}).get("wgl.witness.widened")
    return None if not n else n / len(w.checks)
