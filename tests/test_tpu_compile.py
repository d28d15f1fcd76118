"""Main-path kernels compiled by the TPU compiler for a described v5e.

Nothing runs: each test lowers a kernel for a chip that is described,
not attached, and compiles it, so what Mosaic or XLA:TPU would refuse
on the chip fails here (an unaligned slice, too much SMEM, a primitive
Pallas cannot lower, a constant a kernel captures).  Shapes are the
ones the chip runs: the witness chunk at the default block shape of the
100k-op north-star history, the Pallas sweep at the largest bucket.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every xdist worker
imports this file.
"""

import pytest

B_WITNESS = 8        # beam bucket of the default witness (beam=8)
K_DEFAULT = 2048     # plan/costmodel heuristic bars_per_block
NB_DEFAULT = 32      # ... and blocks_per_call
W_NORTH_STAR = 4096  # window bucket of the 100k-op north star
N_NORTH_STAR = 100_000


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies

    # Executables built for a described chip cannot be read back here.
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *specs):
    import jax

    return jax.jit(fn).lower(*specs).compile()


def test_witness_chunk_pallas_on_default_block(one_chip):
    """The device-planned witness chunk (transfer="device", the TPU
    default) with the Pallas sweep and packed lanes, at the default
    block shape of the north-star history."""
    import jax.numpy as jnp

    from jepsen_tpu.models import cas_register
    from jepsen_tpu.ops.wgl_witness import _bucket, _make_chunk_fn

    pm = cas_register().packed()
    W, K, NB, n = W_NORTH_STAR, K_DEFAULT, NB_DEFAULT, N_NORTH_STAR
    _, _, make_dev = _make_chunk_fn(
        B_WITNESS, W, pm.state_width, K, 5, NB, pm.jax_step,
        pallas_mode="on", jax_step_rows=pm.jax_step_rows, packed=True,
    )
    s = lambda shape, dt=jnp.int32: _spec(shape, dt, one_chip)  # noqa: E731
    args = (
        s((W, B_WITNESS), jnp.bool_), s((B_WITNESS, pm.state_width)),
        s((B_WITNESS,), jnp.bool_), s((), jnp.bool_), s((W,)),
        *(s((NB,)) for _ in range(5)), s(()),
        *(s((n,)) for _ in range(7)), s((_bucket(n + K, lo=K),)),
    )
    compiled = make_dev(32768).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _model(name):
    from jepsen_tpu import models
    from jepsen_tpu.ops.wgl_stream import stream_model

    base, _, stream = name.partition("+")
    pm = getattr(models, base)().packed()
    return stream_model(pm) if stream else pm


@pytest.mark.parametrize("model", [
    "cas_register", "register", "mutex", "fifo_queue", "unordered_queue",
    "cas_register+stream", "unordered_queue+stream",
])
def test_pallas_sweep_every_model_largest_bucket(one_chip, model):
    """The easy-path sweep kernel with each model's lane-major step,
    at the largest witness block bucket (plan/costmodel.py)."""
    import jax.numpy as jnp

    from jepsen_tpu.ops.wgl_witness import _make_pallas_sweep
    from jepsen_tpu.plan.costmodel import _candidate_witness_blocks

    pm = _model(model)
    K = max(k for k, _ in _candidate_witness_blocks())
    W = W_NORTH_STAR
    sweep = _make_pallas_sweep(B_WITNESS, W, pm.state_width, K,
                               pm.jax_step_rows, interpret=False)
    compiled = _compile(
        sweep, _spec((), jnp.int32, one_chip),
        _spec((6, K), jnp.int32, one_chip),
        _spec((W, B_WITNESS), jnp.bool_, one_chip),
        _spec((B_WITNESS, pm.state_width), jnp.int32, one_chip),
        _spec((B_WITNESS,), jnp.bool_, one_chip),
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("K,W", [(16384, 32768), (32768, 4096)])
def test_pallas_sweep_smem_bound(one_chip, K, W):
    """The SMEM bound check_wgl_witness enforces before it builds the
    kernel agrees with what the compiler accepts and refuses."""
    import jax.numpy as jnp

    from jepsen_tpu.models import cas_register
    from jepsen_tpu.ops.wgl_witness import (
        PALLAS_SMEM_BYTES, _make_pallas_sweep, pallas_smem_bytes,
    )

    pm = cas_register().packed()
    sweep = _make_pallas_sweep(B_WITNESS, W, 1, K, pm.jax_step_rows,
                               interpret=False)
    specs = (
        _spec((), jnp.int32, one_chip), _spec((6, K), jnp.int32, one_chip),
        _spec((W, B_WITNESS), jnp.bool_, one_chip),
        _spec((B_WITNESS, 1), jnp.int32, one_chip),
        _spec((B_WITNESS,), jnp.bool_, one_chip),
    )
    fits = pallas_smem_bytes(K, W) <= PALLAS_SMEM_BYTES
    if fits:
        _compile(sweep, *specs)
    else:
        with pytest.raises(Exception, match="smem"):
            _compile(sweep, *specs)


def test_batched_key_fn(one_chip):
    """The vmapped per-key frontier search (__graft_entry__.entry)."""
    import __graft_entry__

    fn, args = __graft_entry__.entry()
    specs = [_spec(a.shape, a.dtype, one_chip) for a in args]
    _compile(fn, *specs)


def test_scc_screen(one_chip):
    """The Elle cycle screen's closure kernel (ops/scc.py) at the
    largest graph the device path takes."""
    import jax.numpy as jnp

    from jepsen_tpu.ops.scc import _get_kernel

    K, V = 8, 1024
    fn = _get_kernel(K, V)
    fn.lower(_spec((K, V, V), jnp.bool_, one_chip)).compile()


def test_shard_map_batched_four_devices(topo):
    """The shard_map batched program over a 4-device mesh: each device
    gets its own slice of keys and no collective is inserted."""
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from jepsen_tpu.models import cas_register
    from jepsen_tpu.ops.wgl_batched import _get_kernel

    pm = cas_register().packed()
    mesh = Mesh(np.asarray(topo.devices[:4]), ("keys",))
    keys, N, B = 200, 256, 256
    fn = _get_kernel(B, N, pm.state_width, 4 * B, pm.jax_step, mesh,
                     packed=True)
    per_key = NamedSharding(mesh, P("keys"))
    rows = lambda: _spec((keys, N), jnp.int32, per_key)  # noqa: E731
    compiled = fn.lower(
        rows(), rows(), rows(), rows(), rows(),
        _spec((keys, N), jnp.bool_, per_key),
        _spec((pm.state_width,), jnp.int32,
              NamedSharding(mesh, P(None))),
        _spec((keys,), jnp.int32, per_key),
    ).compile()
    text = compiled.as_text()
    assert "all-gather" not in text and "all-reduce" not in text
