from .knobs import (
    INFLUENTIAL_KNOBS, SPARK35_DEFAULTS, SPARK35_INFLUENTIAL_KNOBS, spark35_space, spark_space,
)
from .model import HardwareScenario, QueryProfile, SparkCostModel, SCENARIOS
from .workload import SparkWorkload, make_task_id
from .tasks import TaskSpec, all_task_specs, build_knowledge_base, generate_history

__all__ = [
    "spark_space", "spark35_space", "INFLUENTIAL_KNOBS",
    "SPARK35_INFLUENTIAL_KNOBS", "SPARK35_DEFAULTS",
    "HardwareScenario", "QueryProfile", "SparkCostModel", "SCENARIOS",
    "SparkWorkload", "make_task_id",
    "TaskSpec", "all_task_specs", "build_knowledge_base", "generate_history",
]
