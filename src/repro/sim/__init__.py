from repro.sim.execmodel import (ExecModelConfig, ExecutionModel, StageBatch,
                                 StageCost, StageCostBatch,
                                 cached_execution_model)
from repro.sim.requests import Request, WorkloadConfig, generate
from repro.sim.scheduler import ReplicaScheduler, SchedulerConfig
from repro.sim.simulator import (SimConfig, SimResult, energy_report,
                                 run_simulation)
from repro.sim.trace import StageTrace, StageTraceBuilder
from repro.sim.defaults import INTEGRATION_DEFAULT, PAPER_DEFAULT, PAPER_PUE

__all__ = [
    "ExecModelConfig", "ExecutionModel", "StageBatch", "StageCost",
    "StageCostBatch", "cached_execution_model",
    "Request", "WorkloadConfig", "generate",
    "ReplicaScheduler", "SchedulerConfig",
    "SimConfig", "SimResult", "energy_report", "run_simulation",
    "StageTrace", "StageTraceBuilder",
    "INTEGRATION_DEFAULT", "PAPER_DEFAULT", "PAPER_PUE",
]
