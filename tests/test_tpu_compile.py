"""The main path's kernels compiled for a described TPU v5e at real widths.

Nothing runs: the TPU compiler installed with JAX compiles for a ``v5e:2x2``
topology that is described, not attached, and refuses what the chip would
refuse — a block that breaks the (8, 128) tiling, a dynamic row slice it
cannot prove aligned, more VMEM than a kernel may use. Interpret-mode tests
see none of these.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and every test worker
imports every test file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import rps
from repro.kernels import rps_ring
from repro.kernels.masked_avg import masked_avg_grid_pallas


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.array(topo.devices), ("data",))


def _custom_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


# the blocks the simulator's exchange hands the kernel for deepseek-7b with
# n = 2 workers (s = 2 server blocks, per-leaf plan, (rows, 128) payload):
# both 102400x4096 embeddings in one group, the three 2-layer MLP leaves in
# another, the norms; plus a flat payload and every dtype
@pytest.mark.parametrize("shape,dtype", [
    ((4, 2, 1638400, 128), jnp.bfloat16),     # embed + head
    ((6, 2, 352256, 128), jnp.bfloat16),      # mlp wi/wg/wo, 2 layers
    ((4, 2, 32, 128), jnp.bfloat16),          # ln1/ln2, 2 layers
    ((64, 16, 1152), jnp.float32),            # flat, one tile per block
    ((13, 4, 1000), jnp.int8),                # ragged: padded rows
])
def test_masked_avg_compiles_at_train_widths(one_chip, shape, dtype):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    m = jax.ShapeDtypeStruct(shape[:2], jnp.bool_, sharding=one_chip)
    compiled = masked_avg_grid_pallas.lower(x, m).compile()
    assert _custom_calls(compiled) == 1


def test_global_exchange_compiles_at_deepseek_leaf(one_chip, monkeypatch):
    """The simulator's whole exchange of one 102400x4096 embedding over
    n = 2 workers, on the Pallas path the TPU backend resolves to. A flat
    (…, d) view of the blocks once cost the compiler minutes and tens of
    GB of host memory here."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n = 2
    tree = {"tok": jax.ShapeDtypeStruct((n, 102400, 4096), jnp.bfloat16,
                                        sharding=one_chip)}
    mask = jax.ShapeDtypeStruct((n, n), jnp.bool_, sharding=one_chip)

    def exchange(t, rs, ag):
        return rps.rps_exchange_global(t, jax.random.PRNGKey(0), 0.1, n,
                                       masks=(rs, ag))

    compiled = jax.jit(exchange).lower(tree, mask, mask).compile()
    assert _custom_calls(compiled) == 1


# the widest power-of-two bucket each payload / wire pair compiles for:
# the kernel keeps the whole (n·k, W) table, padded to the narrowest
# operand's sublane tile, in VMEM (a real-width leaf does not fit)
@pytest.mark.parametrize("payload,wire,W", [
    (jnp.float32, "f32", 131072),
    (jnp.bfloat16, "bf16", 32768),
    (jnp.float32, "int8", 16384),
])
def test_ring_bucket_fused_compiles_on_mesh(mesh, payload, wire, W):
    n, k = mesh.shape["data"], 1
    S = n * k
    levels = 127 if wire == "int8" else 0
    rs_dtype = jnp.float32 if wire in ("f32", "int8") else jnp.bfloat16

    def body(tbl):
        pos, left, right = (r.reshape(1) for r in
                            rps_ring.logical_ring_ids(("data",)))
        tbl = tbl[0]
        qt = qs = None
        if levels:
            qt, qs = tbl.astype(jnp.int8), jnp.ones((S, 1), jnp.float32)
        out = rps_ring.ring_bucket_fused(
            tbl, jnp.ones((S, 1), rs_dtype), jnp.ones((S, 1), jnp.float32),
            jnp.full((S, 1), n, rs_dtype), pos, left, right, n=n, k=k,
            mode="model", rs_dtype=rs_dtype, qtable=qt, qscale=qs,
            levels=levels)
        return out[None]

    ring = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                                 out_specs=P("data"), check_vma=False))
    x = jax.ShapeDtypeStruct((n, S, W), payload,
                             sharding=NamedSharding(mesh, P("data")))
    compiled = ring.lower(x).compile()
    assert _custom_calls(compiled) == 1
