"""Chain rounds the witness ran: the `wgl.witness.chain-rounds`
counter's growth, per check."""


def read(w):
    n = (w.counters or {}).get("wgl.witness.chain-rounds")
    return None if not n else n / len(w.checks)
