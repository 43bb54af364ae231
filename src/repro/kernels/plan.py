"""Declarative kernel launch geometry (grid / block / scratch plans).

Every Pallas kernel family exposes a pure ``plan(...)`` function that
computes the launch geometry — grid, per-operand block shapes + index maps
over the PADDED array shapes, scalar-prefetch operands, and scratch
accumulators — as plain data, *before* any ``pallas_call`` is constructed.
The ``*_tpu`` entry points consume the plan (one source of truth for the
blocking arithmetic), and ``repro.analysis.rules_pallas`` validates the same
plans statically for every arch config: divisibility, index-map bounds at
the grid corners, and fp32 accumulator dtypes — without executing a kernel.

This module is importable without Pallas; the ``as_block_spec`` /
``as_scratch`` converters import it lazily at call time.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Tuple


@dataclass(frozen=True)
class BlockPlan:
    """One pallas_call operand: block tiling of a (padded) array.

    ``index_map`` takes the grid indices (plus one ref argument per
    scalar-prefetch operand, appended) and returns block-unit indices."""
    name: str
    block_shape: Tuple[int, ...]
    index_map: Callable[..., Tuple[int, ...]]
    array_shape: Tuple[int, ...]
    dtype: str = "float32"
    memory_space: str = "vmem"         # "vmem" | "smem" | "any" (HBM, DMA'd
                                       # by the kernel; block = whole array)


@dataclass(frozen=True)
class ScratchPlan:
    """VMEM scratch buffer; ``accumulator=True`` marks running state that
    must accumulate in fp32 regardless of the operand compute dtype.
    ``dtype="dma_semaphore"`` declares DMA semaphores of ``shape``."""
    name: str
    shape: Tuple[int, ...]
    dtype: str = "float32"
    accumulator: bool = False


@dataclass(frozen=True)
class ScalarPrefetchPlan:
    """A scalar-prefetched operand whose values drive index maps.

    ``max_value`` is the inclusive upper bound of the values the operand can
    legally hold (e.g. vocab-1 for label ids) — the static checker evaluates
    index maps at both 0 and ``max_value`` to bound the DMA addresses."""
    name: str
    shape: Tuple[int, ...]
    dtype: str = "int32"
    max_value: int = 0


@dataclass(frozen=True)
class KernelPlan:
    """The full launch geometry of one pallas_call."""
    family: str                        # kernels.FAMILIES key
    entry: str                         # entry-point name within the family
    grid: Tuple[int, ...]
    inputs: Tuple[BlockPlan, ...]
    outputs: Tuple[BlockPlan, ...]
    scratch: Tuple[ScratchPlan, ...] = ()
    scalar_prefetch: Tuple[ScalarPrefetchPlan, ...] = ()

    @property
    def blocks(self) -> Tuple[BlockPlan, ...]:
        return self.inputs + self.outputs


def as_block_spec(bp: BlockPlan):
    """BlockPlan -> pl.BlockSpec (lazy Pallas import)."""
    from jax.experimental import pallas as pl
    if bp.memory_space == "any":
        return pl.BlockSpec(memory_space=pl.ANY)
    if bp.memory_space == "smem":
        from jax.experimental.pallas import tpu as pltpu
        return pl.BlockSpec(bp.block_shape, bp.index_map,
                            memory_space=pltpu.SMEM)
    return pl.BlockSpec(bp.block_shape, bp.index_map)


def as_scratch(sp: ScratchPlan):
    """ScratchPlan -> pltpu scratch shape (lazy Pallas import)."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu
    if sp.dtype == "dma_semaphore":
        return pltpu.SemaphoreType.DMA(sp.shape)
    return pltpu.VMEM(sp.shape, jnp.dtype(sp.dtype))
