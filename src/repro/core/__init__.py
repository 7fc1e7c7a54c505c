"""Core: the paper's contribution — dynamic-asymmetry-aware DAG scheduling.

Public surface:
  places      — ExecutionPlace / ResourcePartition / Topology (+ presets)
  ptt         — Performance Trace Table (online EMA model, 1:4 weighting)
  task        — Task / TaskType + the paper's kernel cost models
  dag         — synthetic / kmeans / heat DAG builders
  schedulers  — RWS, RWSM-C, FA, FAM-C, DA, DAM-C, DAM-P (Algorithm 1)
  queues      — split HIGH-FIFO/LOW-LIFO WSQs + AQs (shared by both engines)
  lifecycle   — engine-agnostic scheduling kernel (wake/place/steal/commit)
  interference— co-running apps + DVFS speed profiles
  preemption  — seeded pod-slice revoke/restore episode models
  faults      — seeded task-level fault injection + recovery policy
  shards      — sharded control plane (per-pod kernels + global rebalancer)
  simulator   — discrete-event engine (paper-scale evaluation)
  multirun    — batched multi-run engine (sweeps fanned across host cores)
  runtime     — threaded executor running real payloads (JAX kernels)
  metrics     — throughput / placement / worktime aggregation
"""
from .dag import (DAG, chain_dag, decode_pool_dag, heat_dag, kmeans_dag,
                  mixed_dag, synthetic_dag)
from .faults import (Fault, FaultModel, RecoveryPolicy, mmpp_faults,
                     task_faults)
from .lifecycle import SchedulingKernel, ptt_observe, split_by_priority
from .interference import (BackgroundApp, LoadCoupledGovernor,
                           PeriodicProfile, SpeedProfile, SpeedProfileBase,
                           TraceProfile, burst_episodes, corun_chain,
                           corun_socket, dvfs_denver, governor_profile,
                           mmpp_burst_episodes, mmpp_on_off,
                           mmpp_state_timeline, random_walk_trace,
                           renewal_on_off)
from .metrics import RequestRecord, RunMetrics, Span, TaskRecord
from .multirun import (RunSpec, default_workers, run_cell, run_cells,
                       shutdown_pool)
from .places import ExecutionPlace, LiveView, ResourcePartition, Topology, \
    haswell, haswell_cluster, tpu_pod_slices, tx2, tx2_xl
from .preemption import (PreemptionModel, mmpp_preemption,
                         pod_slice_preemption, prune_full_outages,
                         sub_slice_preemption)
from .ptt import PTT, PTTBank
from .queues import BatchingConfig, SplitWSQ, WorkQueues
from .runtime import ThreadedRuntime, run_threaded
from .schedulers import ALL_SCHEDULERS, Scheduler, make_scheduler
from .shards import (GlobalRebalancer, ShardedControlPlane, ShardingSpec,
                     make_control_plane)
from .simulator import Simulator, simulate
from .task import (Priority, Task, TaskType, batch_bucket, copy_type,
                   kmeans_map_type, kmeans_reduce_type, matmul_type,
                   mpi_exchange_type, stencil_type)

__all__ = [
    "DAG", "chain_dag", "decode_pool_dag", "heat_dag", "kmeans_dag",
    "mixed_dag", "synthetic_dag",
    "BackgroundApp", "PeriodicProfile", "SpeedProfile", "SpeedProfileBase",
    "TraceProfile", "burst_episodes", "corun_chain", "corun_socket",
    "dvfs_denver", "governor_profile", "LoadCoupledGovernor",
    "mmpp_burst_episodes", "mmpp_on_off", "mmpp_state_timeline",
    "random_walk_trace", "renewal_on_off",
    "RequestRecord", "RunMetrics", "Span", "TaskRecord", "ExecutionPlace",
    "LiveView",
    "ResourcePartition", "Topology", "haswell", "haswell_cluster",
    "tpu_pod_slices", "tx2", "tx2_xl",
    "PreemptionModel", "mmpp_preemption", "pod_slice_preemption",
    "prune_full_outages", "sub_slice_preemption",
    "GlobalRebalancer", "ShardedControlPlane", "ShardingSpec",
    "make_control_plane",
    "Fault", "FaultModel", "RecoveryPolicy", "mmpp_faults", "task_faults",
    "SchedulingKernel", "ptt_observe", "split_by_priority",
    "BatchingConfig", "SplitWSQ", "WorkQueues", "batch_bucket",
    "PTT", "PTTBank", "ThreadedRuntime",
    "run_threaded", "ALL_SCHEDULERS", "Scheduler", "make_scheduler",
    "RunSpec", "default_workers", "run_cell", "run_cells", "shutdown_pool",
    "Simulator", "simulate", "Priority", "Task", "TaskType", "copy_type",
    "kmeans_map_type", "kmeans_reduce_type", "matmul_type",
    "mpi_exchange_type", "stencil_type",
]
