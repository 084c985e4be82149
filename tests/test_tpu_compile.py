"""Compile the served path for a described TPU v5e, with no chip attached.

The TPU compiler refuses here what it would refuse on the chip: a program
that does not fit the device, a plan that cannot be partitioned, a kernel
Mosaic cannot lower. The widths are chip_smoke.py's (configs/emvb_msmarco.py
at 65,536 passages). The topology is described inside a fixture, never at
import: only one process may load the TPU library, and pytest-xdist workers
all import every test file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.engine import EngineConfig, _retrieve_jit
from repro.core.index import PackedIndex
from repro.kernels import pqinter, prefilter
from repro.launch.serve import make_shardmap_retriever

HBM_BYTES = 16 * 2 ** 30          # one v5e chip
N_DOCS, CAP, D, N_C, M, K_SUB, N_Q = 65_536, 80, 128, 1 << 18, 16, 256, 32
LIST_CAP = 128       # build_index sized 77 and 87 for chip_smoke's corpus
CFG = EngineConfig(n_q=32, nprobe=4, th=0.4, th_r=0.5, n_filter=1024,
                   n_docs=256, k=100)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            described = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure: no topology
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield described
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _index_shapes(n_docs: int, sharding, lead=()) -> PackedIndex:
    """PackedIndex of ShapeDtypeStructs; ``lead`` prepends a shard axis."""
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(lead + shape, dtype, sharding=sharding)

    return PackedIndex(
        centroids=s((N_C, D), jnp.float32), codes=s((n_docs, CAP), jnp.int32),
        doc_lens=s((n_docs,), jnp.int32),
        res_codes=s((n_docs, CAP, M), jnp.uint8),
        pq_codebooks=s((M, K_SUB, D // M), jnp.float32),
        ivf=s((N_C, LIST_CAP), jnp.int32), ivf_lens=s((N_C,), jnp.int32),
        plaid_res=s((n_docs, CAP, D // 4), jnp.uint8),
        plaid_cutoffs=s((3,), jnp.float32), plaid_weights=s((4,), jnp.float32),
        opq_rotation=s((D, D), jnp.float32), pred_words=s((n_docs,), jnp.uint32))


@pytest.mark.parametrize("batch", [1, 8])
def test_retrieve_fits_one_v5e_chip(one_chip, batch):
    """The jnp engine at MS MARCO widths compiles for one chip, and its
    arguments plus temporaries fit the chip's HBM."""
    q = jax.ShapeDtypeStruct((batch, N_Q, D), jnp.float32, sharding=one_chip)
    qm = jax.ShapeDtypeStruct((batch, N_Q), jnp.bool_, sharding=one_chip)
    compiled = _retrieve_jit.lower(_index_shapes(N_DOCS, one_chip), q, CFG,
                                   qm).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def test_shardmap_plan_compiles_on_v5e_2x2(topo):
    """The four-chip serving plan partitions over a 2x2 mesh: one shard of
    16,384 passages per chip, merged by an all-gather."""
    mesh = Mesh(np.array(topo.devices).reshape(4), ("shard",))
    stacked = _index_shapes(N_DOCS // 4, NamedSharding(mesh, P("shard")),
                            lead=(4,))
    rep = NamedSharding(mesh, P())
    q = jax.ShapeDtypeStruct((8, N_Q, D), jnp.float32, sharding=rep)
    qm = jax.ShapeDtypeStruct((8, N_Q), jnp.bool_, sharding=rep)
    compiled = jax.jit(make_shardmap_retriever(mesh, CFG)).lower(
        stacked, q, qm).compile()
    assert "all-gather" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def _sorts_over_last_axis(lowered, n: int):
    """The ``chlo.top_k`` and ``stablehlo.sort`` ops of a lowered module whose
    first operand's last axis has length ``n``."""
    found = []

    def walk(op):
        for region in op.regions:
            for block in region.blocks:
                for inner in block.operations:
                    inner = inner.operation
                    if inner.name in ("chlo.top_k", "stablehlo.sort"):
                        dims = re.match(r"tensor<([0-9x]+)x", str(
                            inner.operands[0].type))
                        if dims and int(dims.group(1).split("x")[-1]) == n:
                            found.append(inner.name)
                    walk(inner)

    walk(lowered.compiler_ir("stablehlo").operation)
    return found


def test_retrieve_sorts_no_centroid_row():
    """Phase 1 picks its nprobe centroids without sorting the n_c scores of a
    query term: the TPU lowers a top_k over them to a full sort of the row.
    Lowered at MS MARCO widths, batch 8, on any backend."""
    row = jax.ShapeDtypeStruct((8, N_Q, N_C), jnp.float32)
    assert _sorts_over_last_axis(
        jax.jit(lambda x: jax.lax.top_k(x, 4)).lower(row), N_C) == [
            "chlo.top_k"]               # the check sees the op it guards
    q = jax.ShapeDtypeStruct((8, N_Q, D), jnp.float32)
    qm = jax.ShapeDtypeStruct((8, N_Q), jnp.bool_)
    lowered = _retrieve_jit.lower(_index_shapes(N_DOCS, None), q, CFG, qm)
    assert _sorts_over_last_axis(lowered, N_C) == []


# Toy widths: Mosaic refuses both megakernels at any width. A change that
# makes one compile turns its strict xfail into a failure; drop the mark.
B, N_C_TOY, N_DOCS_TOY, N_FILTER = 4, 1024, 2048, 256


@pytest.mark.xfail(strict=True, raises=NotImplementedError,
                   reason="Mosaic: 'Reductions over unsigned integers not "
                          "implemented' (the uint32 bit-pack sum); the "
                          "in-kernel jnp.take of the bit table is next")
def test_prefilter_batched_compiles_with_mosaic(one_chip):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    prefilter.prefilter_batched.lower(
        s((B, N_Q, N_C_TOY), jnp.float32), 0.4,
        s((N_DOCS_TOY, CAP), jnp.int32), s((N_DOCS_TOY, CAP), jnp.bool_),
        s((B, N_DOCS_TOY), jnp.bool_), N_FILTER, s((B, N_Q), jnp.bool_),
        interpret=False).compile()


@pytest.mark.xfail(strict=True, raises=NotImplementedError,
                   reason="Mosaic: 'Only 2D gather is supported' (the "
                          "in-kernel CS^T and LUT gathers)")
def test_pqinter_batched_compiles_with_mosaic(one_chip):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pqinter.pqinter_batched.lower(
        s((B, N_C_TOY, N_Q), jnp.float32), s((B, N_Q, M, K_SUB), jnp.float32),
        s((B, N_FILTER, CAP), jnp.int32), s((B, N_FILTER, CAP, M), jnp.uint8),
        s((B, N_FILTER, CAP), jnp.bool_), 0.5, 64, 10,
        s((B, N_Q), jnp.bool_), interpret=False).compile()
