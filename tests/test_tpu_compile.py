"""Compile guards: the serving path's device programs, compiled for a
described TPU v5e (nothing runs, no chip needed).

The chip's compiler refuses what interpret mode and the CPU backend accept:
a Pallas block not aligned to the lane tiling, a kernel over its scoped
VMEM, a program over the chip's HBM, an int64 index map.  These tests
compile, at the sizes ``chip_smoke.py`` serves, the batched CSR fixpoint of
both carriers (one chip and sharded over a 2x2 mesh) and every CSR SpMV
kernel, and check the kernels' VMEM arithmetic against the compiler.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the test workers all import this
file.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import distributed, sparse
from repro.kernels import spmv

# --- the smoke's deployment: powerlaw_graph(n=2**20, m=2**24, seed=0) -------
N = 1 << 20
CAP = 1 << 24  # the packed-arc bucket of its |E| arcs
#: its sliced-ELL layout, (rows, cap) per degree class, as built on the chip
SMOKE_SLICES = ((62470, 1), (13377, 2), (9324, 4), (6332, 8), (4116, 16),
                (2589, 32), (1668, 64), (1046, 128), (645, 256), (425, 512),
                (254, 1024), (170, 2048), (106, 4096), (64, 8192),
                (41, 16384), (26, 32768), (16, 65536), (11, 131072),
                (6, 262144), (5, 524288), (5, 1048576))
TAIL = 2048  # COO tail bucket after the smoke's 1,024-arc append
SMOKE_MAX_BATCH = 128  # what its memory probe admitted on a v5e
HBM_BYTES = 16 << 30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a described chip's compiles are written to a persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        described = topologies.get_topology_desc(platform="tpu",
                                                 topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield described
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def smoke_csr(kind: str, sharding) -> sparse.CSRMatrix:
    """The smoke's CSR relation as abstract arrays on ``sharding``."""
    def a(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    i32 = jnp.int32
    val = jnp.bool_ if kind == "bool" else jnp.float32
    return sparse.CSRMatrix(
        row_ptr=a((N + 1,), i32), col_idx=a((CAP,), i32),
        edge_val=a((CAP,), val), src_idx=a((CAP,), i32),
        ell_slices=tuple(a(s, i32) for s in SMOKE_SLICES),
        ell_rank=a((N,), i32), nnz=a((), i32), tail_src=a((TAIL,), i32),
        tail_dst=a((TAIL,), i32), tail_val=a((TAIL,), val),
        tail_ell=a((N, 2), i32), tail_nnz=a((), i32), plan_tile=None,
        plan_chunk=None, plan_first=None, n=N, n_alloc=N, kind=kind,
        ell_cfg=sparse.DEFAULT_ELL_CFG, plan_cfg=None)


def wide_64bit(lowered, compiled, rows: int) -> list[str]:
    """64-bit arrays with more than ``rows`` elements, in the traced program
    and in what the compiler made of it: the chip emulates 64-bit
    arithmetic, so none may span the (B, n) tables (a count per batch row
    may be 64-bit)."""
    traced = [tuple(int(d) for d in m.split("x")) for m in re.findall(
        r"tensor<([\dx]+)x[iuf]64>", lowered.as_text())]
    compiled = [tuple(int(d) for d in m.split(",")) for m in re.findall(
        r"\b[suf]64\[([\d,]+)\]", compiled.as_text())]
    return sorted({str(s) for s in traced + compiled if np.prod(s) > rows})


@pytest.mark.parametrize("kind", ["bool", "minplus"])
def test_csr_fixpoint_fits_one_chip(kind, one_chip):
    """The batched CSR fixpoint at the smoke's shapes and max_batch
    compiles for one v5e, fits its HBM, and keeps its tables 32-bit."""
    csr = smoke_csr(kind, one_chip)
    init = jax.ShapeDtypeStruct((SMOKE_MAX_BATCH, N), csr.semiring.dtype,
                                sharding=one_chip)
    lowered = sparse.lower_fixpoint_csr(csr, init)
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    total = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
             + ma.output_size_in_bytes)
    assert total < HBM_BYTES, f"{total} bytes do not fit one chip"
    assert wide_64bit(lowered, compiled, SMOKE_MAX_BATCH) == []


@pytest.mark.parametrize("kind", ["bool", "minplus"])
def test_csr_fixpoint_shards_over_four_chips(kind, topo):
    """``DatalogService(mesh=)``'s plan on a 2x2 mesh: batch rows sharded,
    arcs replicated, and one all-reduce (the convergence psum) the only
    collective."""
    mesh = jax.sharding.Mesh(np.asarray(topo.devices), ("data",))
    csr = smoke_csr(kind, NamedSharding(mesh, P()))
    rows = jax.ShapeDtypeStruct((32, N), csr.semiring.dtype,
                                sharding=NamedSharding(mesh, P("data", None)))
    lowered = distributed.csr_frontier_decomposable.lower(mesh, csr, rows)
    compiled = lowered.compile()
    collectives = set(re.findall(
        r"\b(all-reduce|all-gather|all-to-all|collective-permute|"
        r"reduce-scatter)(?:-start)?\(", compiled.as_text()))
    assert collectives == {"all-reduce"}
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes + ma.argument_size_in_bytes < HBM_BYTES
    assert wide_64bit(lowered, compiled, 32) == []


def _kernel_args(kind, batch, n, cap, sharding):
    def a(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    val = jnp.bool_ if kind == "bool" else jnp.float32
    return (a((batch, n), val), a((cap,), jnp.int32), a((cap,), jnp.int32),
            a((cap,), val))


_KERNELS = {"bool": spmv.csr_bool_spmv, "plustimes": spmv.csr_plustimes_spmv,
            "minplus": spmv.csr_minplus_spmv,
            "maxplus": spmv.csr_maxplus_spmv}


@pytest.mark.parametrize("kind,batch,n", [
    ("bool", 8, 4096), ("plustimes", 8, 4096),
    # min/max-plus hold one column tile, so the smoke's width compiles too
    ("minplus", 32, N), ("maxplus", 32, N)])
def test_spmv_kernel_compiles(kind, batch, n, one_chip):
    """Each CSR kernel lowers through Mosaic at a width its VMEM
    arithmetic admits."""
    assert spmv.vmem_bytes(kind, batch, n) <= spmv.VMEM_LIMIT_BYTES
    fn = _KERNELS[kind]
    jax.jit(lambda f, s, d, v: fn(f, s, d, v, interpret=False)).lower(
        *_kernel_args(kind, batch, n, 1 << 16, one_chip)).compile()


def test_spmv_tiled_kernel_compiles(one_chip):
    """The tile-skip min-plus kernel (scalar-prefetch worklist) at the
    smoke's width."""
    f, s, d, v = _kernel_args("minplus", 32, N, 1 << 16, one_chip)
    plan = jax.ShapeDtypeStruct((8192,), jnp.int32, sharding=one_chip)
    jax.jit(lambda *xs: spmv.csr_minplus_spmv_tiled(
        *xs, chunk=128, bn=128, interpret=False)).lower(
        f, s, d, v, plan, plan, plan).compile()


def test_vmem_arithmetic_matches_compiler(one_chip, monkeypatch):
    """At the edge of the scoped-VMEM limit the kernel's own arithmetic and
    Mosaic agree: the width it admits compiles, the one it refuses is
    refused by the compiler too."""
    fn = spmv.csr_plustimes_spmv
    assert spmv.vmem_bytes("plustimes", 128, 8192) <= spmv.VMEM_LIMIT_BYTES
    jax.jit(lambda f, s, d, v: fn(f, s, d, v, interpret=False)).lower(
        *_kernel_args("plustimes", 128, 8192, 1 << 12, one_chip)).compile()
    with pytest.raises(spmv.VmemBudgetError):
        spmv.check_vmem("plustimes", 256, 8192)
    monkeypatch.setattr(spmv, "check_vmem", lambda *a, **k: 0)
    with pytest.raises(Exception, match="(?i)vmem"):
        jax.jit(lambda f, s, d, v: fn(f, s, d, v, interpret=False)).lower(
            *_kernel_args("plustimes", 256, 8192, 1 << 12,
                          one_chip)).compile()
