from paddle_tpu.incubate.distributed.models.moe.moe_layer import (  # noqa: F401
    ExpertFFN, GShardGate, MoELayer, NaiveGate, SigmoidGate, SwitchGate,
)
from paddle_tpu.incubate.distributed.models.moe.held_experts import (  # noqa: F401
    HeldExpertsMoE,
)
