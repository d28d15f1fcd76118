"""Synced device chunk calls of the witness and the stream witness: the
`wgl.witness.chunks` counter's growth, per check."""


def read(w):
    n = (w.counters or {}).get("wgl.witness.chunks")
    return None if not n else n / len(w.checks)
