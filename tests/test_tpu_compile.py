"""Compile the main path's kernels for a TPU v5e that is described, not
attached: the fused kNN kernels (f32 and int8), the standalone top-k and
the XLA scan step, at the paper's widths (MS-MARCO d=769, GIST d=960, YFCC
d=4096) and cutoffs (k = 72, 100, 1024, and the serving default k=10,
whose 16-wide queue is narrower than a vector's 128 lanes). The TPU
compiler is installed with jaxlib, so these catch what interpret mode
cannot: lowering rules Mosaic lacks, tiling violations and VMEM overruns.
Nothing runs, so they say nothing about results or speed.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
this file.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.fqsd import make_partition_step
from repro.core.partition import round_up
from repro.core.planner import (
    DatasetStoreMeta,
    EngineConfig,
    PlanRefused,
    plan,
)
from repro.core.quantized import QuantizedDataset
from repro.core.topk import TopK
from repro.kernels.knn import ops as knn_ops
from repro.kernels.topk.ops import topk

M, N = 64, 65536  # one serving batch; the grid length does not change the kernel


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("d,k", [(960, 10), (769, 72), (960, 100),
                                 (4096, 1024)])
def test_fused_f32_kernel_compiles(one_chip, d, k):
    dp = round_up(d, 128)  # the store's padded width
    assert knn_ops.vmem_bytes(k, dp) <= knn_ops.VMEM_LIMIT_BYTES
    fn = jax.jit(lambda q, x, xn: knn_ops.knn(q, x, k, "l2", xn,
                                              interpret=False,
                                              return_stats=True))
    compiled = fn.lower(_spec(one_chip, (M, dp)), _spec(one_chip, (N, dp)),
                        _spec(one_chip, (N,))).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("d,k", [(769, 72), (960, 100)])
def test_fused_int8_kernel_compiles(one_chip, d, k):
    dp = round_up(d, 128)
    assert knn_ops.vmem_bytes(k, dp, rescore_factor=4) \
        <= knn_ops.VMEM_LIMIT_BYTES
    ds = QuantizedDataset(_spec(one_chip, (N, dp), jnp.int8),
                          *(_spec(one_chip, (N,)) for _ in range(4)))
    fn = jax.jit(lambda q, ds, x: knn_ops.knn_int8(q, ds, x, k, 4,
                                                   interpret=False))
    compiled = fn.lower(_spec(one_chip, (M, dp)), ds,
                        _spec(one_chip, (N, dp))).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("k", [72, 1024])
def test_topk_kernel_compiles(one_chip, k):
    fn = jax.jit(lambda s: topk(s, k, interpret=False))
    _assert_kernel(fn.lower(_spec(one_chip, (M, 8192))).compile())


def test_xla_scan_step_compiles_at_highest_precision(one_chip):
    """The streamed f32 step at YFCC width: its distance GEMM must keep
    f32-class precision on the chip (HIGHEST), not one bf16 pass."""
    step = make_partition_step(100, "l2")
    state = TopK(_spec(one_chip, (M, 100)), _spec(one_chip, (M, 100),
                                                   jnp.int32))
    lowered = step.lower(state, _spec(one_chip, (M, 4096)),
                         _spec(one_chip, (16384, 4096)),
                         _spec(one_chip, (16384,)),
                         _spec(one_chip, (), jnp.int32),
                         _spec(one_chip, (), jnp.int32))
    dots = [ln for ln in lowered.as_text().splitlines()
            if "stablehlo.dot_general" in ln]
    assert dots and all("precision = [HIGHEST, HIGHEST]" in ln for ln in dots)
    assert lowered.compile().memory_analysis() is not None


def test_planner_refuses_what_cannot_fit():
    """At k=1024 the int8 kernel's queue is 2 * next_pow2(4 * 1024) = 8192
    wide; the planner refuses it loudly instead of serving another path,
    while the f32 kernel's 1024-wide queue at the same k is planned."""
    meta = DatasetStoreMeta(padded_rows=1 << 20, padded_dim=1024,
                            n_valid=1_000_000, tier="int8")
    cfg = EngineConfig(k=100, backend="pallas")
    assert plan((64, 1024), meta, cfg, "fqsd").executor == "fqsd-int8-pallas"
    with pytest.raises(PlanRefused, match="8192-wide on-chip queue"):
        plan((64, 1024), meta, cfg, "fqsd", k=1024)
    f32 = dataclasses.replace(meta, tier="f32")
    assert plan((64, 1024), f32, cfg, "fqsd", k=1024).executor == "fdsq-pallas"
