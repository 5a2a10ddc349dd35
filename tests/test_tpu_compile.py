"""Chip-compile tests: the main path's programs compiled for a TPU v5e.

The TPU compiler (libtpu) compiles for a described ``v5e:2x2`` topology
without a chip attached, so these tests catch what interpret mode cannot:
kernels Mosaic refuses (i64 index maps under x64, unaligned slices, too
much VMEM), kernels that cannot be batched over subdomains, and programs
that do not fit the chip's 16 GB of HBM. Shapes are feti-heat-2d's real
ones (8x8 subdomains of 64x64 elements: n = 4225 DOFs padded to 4352,
m_max = 258 multipliers padded to 384, 128-blocks, f32), with x64 on as the
launchers run.

The topology is described inside a module fixture, and only there: the TPU
library is loaded by the first test that uses it, in that test's own
process, and where it cannot be described the tests skip.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import SchurAssemblyConfig
from repro.fem import decompose_problem
from repro.feti import FetiConfig
from repro.feti.assembly import batched_assemble, make_cluster_preprocessor
from repro.kernels.common import VMEM_LIMIT_BYTES, vmem_bytes
from repro.kernels.stepped_syrk import stepped_syrk_pallas
from repro.kernels.stepped_trsm import (
    stepped_trsm_packed_pallas,
    stepped_trsm_pallas,
)
from repro.kernels.stepped_trsm_syrk import (
    stepped_trsm_syrk_packed_pallas,
    stepped_trsm_syrk_pallas,
)
from repro.sparse import PackedBlocks

HBM_BYTES = 16 * 2**30  # one v5e chip: 16 GB of HBM


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it cannot be loaded here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip. The persistent compilation cache is off
    while these compile: an entry written for a chip that is not attached
    cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def heat2d():
    """feti-heat-2d at full size, pattern only (no dense matrices), with
    the f32 packed preprocessor the chip smoke test runs."""
    fc = get_config("feti-heat-2d")
    prob = decompose_problem(fc.problem, fc.dim, fc.sub_grid,
                             fc.elems_per_sub, assemble_values=False)
    cfg = SchurAssemblyConfig(
        trsm_variant=fc.trsm_variant, syrk_variant=fc.syrk_variant,
        block_size=fc.block_size, rhs_block_size=fc.rhs_block_size,
        storage="packed")
    config = FetiConfig(schur=cfg, dtype="f32", storage="packed",
                        plan_cache=False)
    static, prep = make_cluster_preprocessor(prob, config)
    return prob, static, prep


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


KERNELS = ("trsm", "trsm_packed", "syrk", "fused", "fused_packed")


@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_at_heat2d_shapes(kernel, one_chip, heat2d):
    _, static, _ = heat2d
    index = static["index"]
    bs = bm = 128
    n, m = index.n_pad, 384
    nb, nc, nt = n // bs, m // bm, index.n_blocks
    assert (n, nb) == (4352, 34)

    def S(*shape):
        return _sds(one_chip, shape)

    def I(*shape):
        return _sds(one_chip, shape, jnp.int32)

    lowered = {
        "trsm": lambda: stepped_trsm_pallas.lower(
            S(nb, bs, bs), S(n, n), S(n, m), I(nc), bs=bs, bm=bm),
        "trsm_packed": lambda: stepped_trsm_packed_pallas.lower(
            S(nb, bs, bs), S(nt, bs, bs), I(nb + 1), I(nt), I(nt), S(n, m),
            I(nc), bs=bs, bm=bm),
        "syrk": lambda: stepped_syrk_pallas.lower(S(n, m), I(nc), bs=bs,
                                                  bm=bm),
        "fused": lambda: stepped_trsm_syrk_pallas.lower(
            S(nb, bs, bs), S(n, n), S(n, m), I(nc), bs=bs, bm=bm),
        "fused_packed": lambda: stepped_trsm_syrk_packed_pallas.lower(
            S(nb, bs, bs), S(nt, bs, bs), I(nb + 1), I(nt), I(nt), S(n, m),
            I(nc), bs=bs, bm=bm),
    }[kernel]()
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert vmem_bytes(kernel, n, m, bs, bm, 4) <= VMEM_LIMIT_BYTES


@pytest.mark.parametrize("variant", ["dense", "packed", "fused_dense",
                                     "fused_packed"])
def test_pallas_assembly_batches_over_subdomains(variant, one_chip, heat2d):
    """The production assembler vmaps the kernels over subdomains
    (batched_assemble): the batched kernels must compile too."""
    _, static, _ = heat2d
    index, env = static["index"], static["env"]
    storage = "dense" if variant.endswith("dense") else "packed"
    cfg = dataclasses.replace(
        static["cfg"], use_pallas=True, prune=False, storage=storage,
        fused=variant.startswith("fused"),
        trsm_variant="rhs_split" if storage == "dense" else "factor_split",
        syrk_variant="output_split")
    S = 4
    if storage == "packed":
        L = PackedBlocks(_sds(one_chip, (S, index.n_blocks, 128, 128)),
                         index)
    else:
        L = _sds(one_chip, (S, env.n, env.n))
    Bt = _sds(one_chip, (S, env.n, env.m))
    cp = static["col_perm"][:S]
    icp = static["inv_col_perm"][:S]
    run = jax.jit(lambda L, B: batched_assemble(
        L, B, cp, icp, env, cfg, static["block_mask"]))
    assert "tpu_custom_call" in run.lower(L, Bt).compile().as_text()


def test_f32_prep_fits_one_v5e(one_chip, heat2d):
    """The f32 packed preprocessing program of feti-heat-2d (factorize all
    64 subdomains + assemble every F̃) fits one chip's HBM, as the
    compiler accounts it."""
    prob, static, prep = heat2d
    index = static["index"]
    S, n, m = prob.n_subdomains, prob.subdomains[0].n, prob.m_max
    assert (S, n, m) == (64, 4225, 258)
    compiled = prep.lower(
        _sds(one_chip, (S, index.n_blocks, index.bs, index.bs)),
        _sds(one_chip, (S, n, m))).compile()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes)
    assert total < HBM_BYTES, total
    # the outputs are the packed factor stack (~0.6 GB) and F̃
    assert ma.output_size_in_bytes >= S * index.n_blocks * index.bs**2 * 4
