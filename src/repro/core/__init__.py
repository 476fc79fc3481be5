"""Core distributed mincut/maxflow library (the paper's contribution).

Public surface:
  Solver, SolverOptions, ProblemHandle      — solver sessions: prepared
                                              handles, warm-start re-solves
                                              (handle.update + solve), and
                                              the unified front-end over the
                                              host-loop / device-resident /
                                              sharded / batched routes
  Problem, build, solve_mincut, SweepConfig — legacy one-shot solver
  solve_mincut_batch, BatchedSolver,
  pack_instances                            — shape-bucketed batched solver
  solve_sharded, make_sharded_sweep        — shard_map distributed solver
  RegionExecutor, Capabilities,
  UnsupportedFeatureError                   — the region-executor interface
                                              every route drives (executor
                                              instances: LocalExecutor,
                                              BatchedExecutor,
                                              ShardedExecutor,
                                              StreamingExecutor — the
                                              out-of-core route, see
                                              repro.stream)
  region_reduction                          — Alg. 5 preprocessing
  SolveSupervisor, CheckpointPolicy,
  FaultPlan, SolveCheckpoint                — resilience layer: sweep-
                                              boundary checkpoint/resume,
                                              supervised retry with fault
                                              injection, degradation ladder
  validate_problem, CertificateError,
  NonConvergence                            — structured input validation
                                              and solve diagnostics
"""

from repro.core.api import (BatchCacheInfo, BatchedSolver, MincutResult,
                            solve_mincut, solve_mincut_batch)
from repro.core.executor import (BatchedExecutor, Capabilities,
                                 LocalExecutor, RegionExecutor,
                                 ShardedExecutor, StreamingExecutor,
                                 UnsupportedFeatureError)
from repro.core.graph import (BatchMeta, BatchState, FlowState, GraphMeta,
                              GraphUpdate, Layout, PackedBatch, Problem,
                              ProblemValidationError, apply_update,
                              bucket_shape_for, build, init_labels,
                              pack_built, pack_instances, validate_problem)
from repro.core.invariants import (CertificateError, NonConvergence,
                                   Violation, invariant_report)
from repro.core.resilience import (CheckpointMismatchError, CheckpointPolicy,
                                   FaultPlan, InjectedFault, PreemptionError,
                                   RetryPolicy, SolveCheckpoint,
                                   SolveSupervisor, SupervisorReport,
                                   VmemOverflowError, fault_injection,
                                   latest_checkpoint, load_checkpoint,
                                   save_checkpoint)
from repro.core.dtypes import DTYPE_POLICIES, KernelDtypes
from repro.core.partition import bfs_partition, block_partition, grid_partition
from repro.core.reduction import region_reduction
from repro.core.solver import (ProblemHandle, Solver, SolverCacheInfo,
                               SolverOptions)
from repro.core.sweep import SweepConfig, SweepStats, cut_value, extract_cut, solve

__all__ = [
    "BatchCacheInfo", "BatchMeta", "BatchState", "BatchedExecutor",
    "BatchedSolver", "Capabilities", "CertificateError",
    "CheckpointMismatchError", "CheckpointPolicy", "DTYPE_POLICIES",
    "FaultPlan", "FlowState", "GraphMeta", "GraphUpdate", "InjectedFault",
    "KernelDtypes", "Layout",
    "LocalExecutor", "MincutResult", "NonConvergence",
    "PackedBatch", "PreemptionError", "Problem", "ProblemHandle",
    "ProblemValidationError", "RegionExecutor", "RetryPolicy",
    "ShardedExecutor", "SolveCheckpoint", "SolveSupervisor", "Solver",
    "StreamingExecutor",
    "SolverCacheInfo", "SolverOptions", "SupervisorReport", "SweepConfig",
    "SweepStats", "UnsupportedFeatureError", "Violation",
    "VmemOverflowError", "apply_update",
    "bfs_partition", "block_partition", "bucket_shape_for",
    "build", "cut_value", "extract_cut", "fault_injection",
    "grid_partition", "init_labels", "invariant_report",
    "latest_checkpoint", "load_checkpoint",
    "pack_built", "pack_instances",
    "region_reduction", "save_checkpoint", "solve", "solve_mincut",
    "solve_mincut_batch", "validate_problem",
]
