"""Compile the chip path for a TPU v5e chip that is described, not attached.

Nothing here runs on a device: each test lowers and compiles with the
TPU's own compiler, which refuses what the chip would refuse (a kernel
Mosaic cannot lower, a program that does not fit), and reads back the
scratch memory XLA plans for it.  The local products are compiled at
HPCG's 104³ size, about two seconds each; the fused PCG programs at a
small grid, since their shapes come from a host setup.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,  # noqa: E402
                          SingleDeviceSharding)

from repro.kernels.spmv.bcsr import bcsr_apply  # noqa: E402
from repro.kernels.spmv.dia import LANES, dia_apply  # noqa: E402
from repro.kernels.spmv.spmv import ell_apply  # noqa: E402

ROWS = 104 ** 3        # HPCG's reference local grid: 1,124,864 rows
K = 27                 # the 27-point stencil's ELL width
# ell_apply plans 6.1 MB (k=1) and 5.8 MB (k=8) of scratch at this size:
# each slot's gather fuses into the accumulation.  The one-shot gather
# form it replaced planned 1.73 GB (k=1) and 5.83 GB (k=8).
ELL_TEMP_LIMIT = 8 << 20
BCSR_TEMP_LIMIT = {1: 73_000_000, 8: 2_215_000_000}
# dia_apply plans no scratch at k=1 (one fusion streams the 27 diagonals
# and the shifted source) and 40.9 MB at k=8, one padded copy of the
# [rows, 8] source
DIA_TEMP_LIMIT = {1: 1 << 20, 8: 48 << 20}
# the 27 stencil offsets of the 104³ grid in natural order
DIA_OFFSETS = tuple(sorted(dz * 104 * 104 + dy * 104 + dx
                           for dz in (-1, 0, 1) for dy in (-1, 0, 1)
                           for dx in (-1, 0, 1)))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # a described compile cannot be read back from the persistent cache
    # without a chip; keep it out of any cache a caller has turned on
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    return compiled.memory_analysis()


@pytest.mark.parametrize("k", [1, 8])
def test_ell_apply_compiles_at_hpcg_size(one_chip, k):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    x = s((ROWS,) if k == 1 else (ROWS, k), jnp.float32)
    mem = _compile(ell_apply, s((ROWS, K), jnp.int32),
                   s((ROWS, K), jnp.float32), x)
    assert mem.temp_size_in_bytes <= ELL_TEMP_LIMIT, mem


@pytest.mark.parametrize("k", [1, 8])
def test_bcsr_apply_compiles_at_hpcg_size(one_chip, k):
    """The 27-point stencil blocked at bs=8: 27 blocks per block row."""
    bs, mb = 8, ROWS // 8
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    x = s((ROWS,) if k == 1 else (ROWS, k), jnp.float32)
    mem = _compile(bcsr_apply, s((mb, K), jnp.int32),
                   s((mb, K, bs, bs), jnp.float32), x)
    # what XLA plans today: the gathered [mb, bs, k] slab is tile-padded
    # on its two minor dims (72 MB at k=1, 2.21 GB at k=8); it must not grow
    assert mem.temp_size_in_bytes <= BCSR_TEMP_LIMIT[k], mem


@pytest.mark.parametrize("k", [1, 8])
def test_dia_apply_compiles_at_hpcg_size(one_chip, k):
    """The 27 diagonals folded onto 128 lanes: at k=1 the product moves
    what it must, each diagonal, the source and the result once (an
    unfolded [27, rows] array would be relaid out every call, 373.6 MB)."""
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    x = s((ROWS,) if k == 1 else (ROWS, k), jnp.float32)
    fn = lambda vals, x: dia_apply(DIA_OFFSETS, vals, x)
    compiled = jax.jit(fn).lower(
        s((K, ROWS // LANES, LANES), jnp.float32), x).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= DIA_TEMP_LIMIT[k], mem
    if k == 1:
        moved = compiled.cost_analysis()["bytes accessed"]
        assert moved <= 1.01 * 4 * (K + 2) * ROWS, moved


# the off-process product of L0 A on a middle chip of the 2×2 HPCG cell:
# 26 planes of 104² rows a chip, two neighbour planes in the halo, the
# off-part's widest row 9, and one row a halo entry in the two edge planes
REMOTE_ROWS = 26 * 104 * 104
REMOTE_HALO = 2 * 104 * 104
REMOTE_K = 9
# XLA plans 1.7 MB of scratch for the full-row product and none for the
# compacted one; it counts 1.19 GB accessed for the first, 0.28 GB for the
# second (each slot's gather is counted at more than its bytes)
REMOTE_TEMP_LIMIT = 4 << 20


def test_remote_apply_over_a_row_list_compiles_at_hpcg_2x2_size(one_chip):
    """The row-compacted off-process product moves at most a quarter of
    the bytes of the full-row one it replaces, in plain XLA."""
    from repro.amg.dist_spmv import remote_apply

    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    y = s((REMOTE_ROWS,), jnp.float32)
    halo = s((REMOTE_HALO,), jnp.float32)
    moved = {}
    for rows in (REMOTE_ROWS, REMOTE_HALO):
        args = [y, s((rows, REMOTE_K), jnp.int32),
                s((rows, REMOTE_K), jnp.float32), halo]
        if rows < REMOTE_ROWS:
            args.append(s((rows,), jnp.int32))
        compiled = jax.jit(remote_apply).lower(*args).compile()
        assert "tpu_custom_call" not in compiled.as_text()
        assert compiled.memory_analysis().temp_size_in_bytes \
            <= REMOTE_TEMP_LIMIT
        moved[rows] = compiled.cost_analysis()["bytes accessed"]
    assert moved[REMOTE_HALO] <= moved[REMOTE_ROWS] / 4, moved


@pytest.mark.parametrize("name", ["pcg_step", "pcg_step_m"])
def test_fused_pcg_program_compiles(topo, name):
    """The whole fused PCG iteration of a 1-chip session, with its
    programs built on a mesh of the described chip."""
    from repro.amg import SolveOptions, setup
    from repro.amg.dist_solve import DEV_AXES, DistHierarchy
    from repro.amg.problems import laplace_3d

    # a private lowering: its mesh is swapped for the described chip's
    # before any program is built
    dh = DistHierarchy.build(setup(laplace_3d(16)), 1, 1)
    dh.mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), DEV_AXES)
    progs, arrs = dh.programs(SolveOptions())
    dev = NamedSharding(dh.mesh, PartitionSpec(DEV_AXES))
    rep = NamedSharding(dh.mesh, PartitionSpec())
    arrs = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev), arrs)
    n = dh.levels[0].A.plan.local_n
    k = (8,) if name.endswith("_m") else ()
    vec = jax.ShapeDtypeStruct((1, n) + k, jnp.float32, sharding=dev)
    rz = jax.ShapeDtypeStruct(k, jnp.float32, sharding=rep)
    compiled = progs[name].lower(vec, vec, vec, rz, arrs).compile()
    assert "tpu_custom_call" not in compiled.as_text()
