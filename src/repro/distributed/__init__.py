from repro.distributed.sharding import (  # noqa: F401
    ShardingPlan, logical_rules, shard, spec_for, set_rules, active_rules,
    params_shardings,
)
from repro.distributed.pipeline import bubble_fraction, gpipe  # noqa: F401
from repro.distributed.faults import (  # noqa: F401
    FaultInjectingRun, HeartbeatCoordinator,
)
