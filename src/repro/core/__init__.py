"""Core analytical blocking model (the paper's contribution).

Public API:

    Problem, BlockingString, Loop, Dim     — loop-nest IR
    place_buffers, analyze                 — buffer placement + traffic
    energy_custom, energy_fixed, optimize  — energy model + schedule search
    evaluate_multicore, best_scheme        — coarse-grain parallelism
    matmul_tiles, conv_tiles, flash_tiles  — TPU BlockSpec derivation
"""

from repro.core.loopnest import (BlockingString, Dim, Extents, Loop,
                                 Problem, divisors)
from repro.core.buffers import (Buffer, Operand, operand_bytes,
                                place_buffers, table2_refetch_rate)
from repro.core.access import TrafficReport, analyze
from repro.core.energy import (access_energy_pj, broadcast_energy_pj,
                               sram_area_mm2, MAC_ENERGY_PJ,
                               DRAM_PJ_PER_16B)
from repro.core.hierarchy import (EnergyReport, MemLevel, cache_accesses,
                                  diannao_hierarchy, energy_custom,
                                  energy_fixed, xeon_hierarchy)
from repro.core.optimizer import (OptResult, make_objective, optimize,
                                  optimize_beam, optimize_exhaustive)
from repro.core.multicore import (MulticoreReport, best_scheme,
                                  evaluate_multicore)
from repro.core.fusion import (Epilogue, FusedProblem, FusedTraffic,
                               FusionResult, fused_energy_pj,
                               fused_multicore_dram_bytes, optimize_fused)
from repro.core.gemm_lowering import (direct_blocking_accesses,
                                      gemm_lowering_accesses,
                                      lowered_gemm_problem)
from repro.core.tpu_adapter import (DEVICE_TARGETS, TPU_V5E, TpuTarget,
                                    conv_tile_candidates, conv_tiles,
                                    flash_tiles, layer_sharding_advice,
                                    matmul_tile_candidates, matmul_tiles,
                                    target_for)

__all__ = [
    "BlockingString", "Dim", "Extents", "Loop", "Problem", "divisors",
    "Buffer", "Operand", "operand_bytes", "place_buffers",
    "table2_refetch_rate",
    "TrafficReport", "analyze",
    "access_energy_pj", "broadcast_energy_pj", "sram_area_mm2",
    "MAC_ENERGY_PJ", "DRAM_PJ_PER_16B",
    "EnergyReport", "MemLevel", "cache_accesses", "diannao_hierarchy",
    "energy_custom", "energy_fixed", "xeon_hierarchy",
    "OptResult", "make_objective", "optimize", "optimize_beam",
    "optimize_exhaustive",
    "MulticoreReport", "best_scheme", "evaluate_multicore",
    "Epilogue", "FusedProblem", "FusedTraffic", "FusionResult",
    "fused_energy_pj", "fused_multicore_dram_bytes", "optimize_fused",
    "direct_blocking_accesses", "gemm_lowering_accesses",
    "lowered_gemm_problem",
    "DEVICE_TARGETS", "TPU_V5E", "TpuTarget", "conv_tile_candidates",
    "conv_tiles", "flash_tiles", "layer_sharding_advice",
    "matmul_tile_candidates", "matmul_tiles", "target_for",
]
