"""The check path's kernels compiled for a described TPU v5e at the shapes of
the largest deployment the benchmark serves (``nested-groups`` at 1,000,000
tuples, seed 7: 24,954 interior rows, 21,751 of them active, buckets up to
4,096 wide), with no chip attached: what the chip's compiler would refuse -
a shape it cannot tile, a program that does not fit - it refuses here.
Nothing runs, so this says nothing of results or times.

All in one file and behind one fixture: only the worker that is given this
file loads the TPU's library (guide ``on-chip-measurement``, section 2)."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from keto_tpu.check import kernels
from keto_tpu.check.dispatch import CheckDispatch

BUCKETS = [(8192, 1), (4096, 2), (4096, 4), (4096, 8), (4096, 16), (2048, 32), (1024, 64),
           (512, 128), (256, 256), (64, 512), (16, 1024), (4, 2048), (1, 4096)]
VALID_ROWS = (6854, 3468, 3700, 2903, 2125, 1262, 907, 264, 197, 51, 16, 3, 1)
N_ACTIVE, N_INT = 21751, 24954
HUB_RELAYS = (2304, 128)  # 834 users in more than 128 groups, 217,757 memberships
WIDTHS = [32, 256, 2048, 8192, 32768, 65536, 131072]
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def widest(kernel):
    """The hub rung of ``kernel`` with the most entries."""
    return max((sizes for k, sizes in CheckDispatch._hub_rungs(WIDTHS) if k == kernel), key=sum)


@pytest.mark.parametrize("sweep", kernels.SWEEPS)
def test_check_step_compiles_at_the_widest_hub_rung_with_a_bucket_past_the_degree_chunk(one_chip, sweep):
    assert BUCKETS[-1][1] > kernels._DEGREE_CHUNK, "the chunk loop of pull would not iterate"
    sizes = widest("check")
    assert sizes == (131072, 131072, 131072, 2048)
    S1, S2, SA, B = sizes
    buckets = tuple(jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip) for s in BUCKETS)
    entries = jax.ShapeDtypeStruct((2 * (S1 + S2 + SA) + B,), jnp.int32, sharding=one_chip)
    hub = jax.ShapeDtypeStruct(HUB_RELAYS, jnp.int32, sharding=one_chip)
    compiled = kernels._check_kernel.lower(
        buckets, entries, ov_nbrs=None, ov_dst=None, hub_nbrs=hub, sizes=sizes, n_active=N_ACTIVE,
        n_int=N_INT, valid_rows=VALID_ROWS, it_cap=64, block_iters=16, bitmap_sharding=None,
        sweep=sweep,
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES // 16


def test_label_step_compiles_at_the_widest_hub_rung(one_chip):
    P, B = widest("label")
    assert (P, B) == (262144, 8192)
    labels = jax.ShapeDtypeStruct((N_INT + 1, 64), jnp.int32, sharding=one_chip)
    entries = jax.ShapeDtypeStruct((3 * P,), jnp.int32, sharding=one_chip)
    compiled = kernels._label_kernel.lower(labels, labels, entries, n_pairs=P, B=B).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < HBM_BYTES // 16
