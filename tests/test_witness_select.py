"""The witness's chain-round child selection (`ops/wgl_witness.py`
`_pick_children`) and the chain-round counter.

`_pick_children` keeps at most B candidates of distinct state by B
masked-min passes over their state hashes.  It is compared here with a
copy of the selection it replaced (a stable argsort of the hashes, an
adjacent compare and a static-size `jnp.nonzero`), which must give the
same indices, the same count and the same children wherever no two
distinct states share a hash.  End to end, the counter
`wgl.witness.chain-rounds` and the verdict must not depend on the sweep
(`pallas`) or on how the block tables reach the device (`transfer`)."""

import json
import os

import numpy as np
import pytest

from jepsen_tpu import telemetry
from jepsen_tpu.history.packed import pack_history
from jepsen_tpu.models import cas_register, register
from jepsen_tpu.ops.wgl_witness import (
    NO_CHILD,
    _pick_children,
    _state_hash_vec,
    check_wgl_witness,
)
from jepsen_tpu.utils.histgen import random_register_history

SW = 2


def sorted_selection(h, child_states, B):
    """The argsort selection, as the chain round ran it before
    `_pick_children`: sort by hash, drop a candidate equal in hash and
    state to the one before it, keep the first B."""
    import jax.numpy as jnp

    order = jnp.argsort(h)
    hs = h[order]
    ss = child_states[order]
    same = (hs == jnp.roll(hs, 1)) & (
        ss == jnp.roll(ss, 1, axis=0)
    ).all(axis=1)
    same = same.at[0].set(False)
    uniq = (hs < NO_CHILD) & ~same
    n_child = jnp.minimum(uniq.sum(), B)
    pos = order[jnp.nonzero(uniq, size=B, fill_value=0)[0]]
    return pos, n_child


def tile(rows, B, n_distinct, seed):
    """A (rows * B)-candidate tile whose good candidates hold
    `n_distinct` distinct states, most of them many times over."""
    rng = np.random.default_rng(seed)
    m = rows * B
    k = max(n_distinct, 1)
    pool = np.stack([rng.permutation(1000)[:k], rng.integers(0, 5, k)],
                    axis=1).astype(np.int32)
    states = pool[rng.integers(0, k, size=m)]
    good = (rng.random(m) < 0.3) & (n_distinct > 0)
    # Every distinct state is good somewhere.
    states[:n_distinct] = pool[:n_distinct]
    good[:n_distinct] = True
    return states, good


def hashes(states, good):
    import jax.numpy as jnp

    hv = jnp.asarray(_state_hash_vec(SW))
    return jnp.where(jnp.asarray(good),
                     jnp.asarray(states).astype(jnp.float32) @ hv,
                     NO_CHILD)


@pytest.mark.parametrize("B,rows", [(8, 64), (8, 8192), (16, 512),
                                    (32, 64), (32, 2048)])
@pytest.mark.parametrize("distinct", ["none", "one", "within", "over"])
def test_masked_min_matches_sorted_selection(B, rows, distinct):
    import jax
    import jax.numpy as jnp

    n = {"none": 0, "one": 1, "within": B // 2 + 1, "over": 4 * B}[distinct]
    states, good = tile(rows, B, n, seed=rows * 131 + B * 7 + n)
    h = hashes(states, good)
    cs = jnp.asarray(states)
    pos, found = jax.jit(_pick_children, static_argnums=2)(h, cs, B)
    ref_pos, ref_n = jax.jit(sorted_selection, static_argnums=2)(h, cs, B)
    assert int(found.sum()) == int(ref_n) == min(n, B)
    # `found` is a prefix: the passes that keep a child come first.
    assert not np.any(np.diff(np.asarray(found).astype(int)) > 0)
    np.testing.assert_array_equal(np.asarray(pos), np.asarray(ref_pos))
    np.testing.assert_array_equal(states[np.asarray(pos)],
                                  states[np.asarray(ref_pos)])


@pytest.mark.parametrize("n_states,B", [(3, 8), (20, 8)])
def test_colliding_hashes_keep_each_state_once(n_states, B):
    """Distinct states that share one hash: each is kept exactly once,
    up to B of them, in index order."""
    import jax.numpy as jnp

    rng = np.random.default_rng(n_states)
    m = 64 * B
    pool = np.stack([np.arange(n_states), np.arange(n_states) * 3],
                    axis=1).astype(np.int32)
    which = rng.integers(0, n_states, size=m)
    which[:n_states] = np.arange(n_states)
    states = pool[which]
    good = np.ones(m, dtype=bool)
    h = jnp.where(jnp.asarray(good), jnp.float32(1.5), NO_CHILD)
    pos, found = _pick_children(h, jnp.asarray(states), B)
    kept = [tuple(states[i]) for i, f in zip(np.asarray(pos),
                                             np.asarray(found)) if f]
    assert len(kept) == min(n_states, B) == len(set(kept))
    assert list(np.asarray(pos)[: len(kept)]) == list(range(len(kept)))


# ---------------------------------------------------------------------------
# End to end: the counter and the verdict, across sweeps and transfers
# ---------------------------------------------------------------------------

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODES = [("off", "full"), ("off", "indices"), ("off", "device"),
         ("interpret", "full")]


@pytest.fixture
def counters():
    prior = telemetry.enabled()
    telemetry.reset()
    telemetry.enable(True)
    yield
    telemetry.enable(prior)
    telemetry.reset()


def partition_packed(pm, ops, seed):
    from benchmark.families import register_partition as rp

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "cas-register-100k-partition.json")) as f:
        config = {**json.load(f), "ops_per_key": ops, "phase_ops": 500}
    entry = rp.generate(config, {"pool": 1, "bad_key_share": 0.0},
                        seed)[0]
    return pack_history(rp.to_program(config, entry), pm.encode)


def register_packed(pm, ops, seed, cas):
    h = random_register_history(ops, procs=16, info_rate=0.05,
                                seed=seed, cas=cas)
    return pack_history(h, pm.encode)


def run(p, pm, pallas, transfer, info_window):
    telemetry.reset()
    info: dict = {}
    res = check_wgl_witness(p, pm, pallas=pallas, transfer=transfer,
                            info_window=info_window, out_info=info)
    return (None if res is None else res.valid, info.get("died_at_rank"),
            telemetry.counter_value("wgl.witness.chain-rounds"))


@pytest.mark.parametrize("family,seed,info_window,valid", [
    ("register", 1, 64, True),
    ("register", 2, 64, True),
    ("partition", 1, None, True),
    ("partition", 2, None, True),
    ("partition", 1, 64, None),   # the narrow rung dies
    ("cas", 2, 64, None),         # dies after several rounds
])
def test_chain_rounds_agree_across_modes(counters, family, seed,
                                         info_window, valid):
    if family == "register":
        pm = register().packed()
        p = register_packed(pm, 4000, seed, cas=False)
    elif family == "cas":
        pm = cas_register().packed()
        p = register_packed(pm, 4000, seed, cas=True)
    else:
        pm = cas_register().packed()
        p = partition_packed(pm, 4000, seed)
    outs = [run(p, pm, pl, tr, info_window) for pl, tr in MODES]
    assert all(o == outs[0] for o in outs), outs
    verdict, died, rounds = outs[0]
    assert verdict is valid
    assert rounds > 0
    assert (died is None) == (valid is True)
