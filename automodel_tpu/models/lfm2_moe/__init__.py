from automodel_tpu.models.lfm2_moe.model import (  # noqa: F401
    Lfm2MoeConfig,
    Lfm2MoeForCausalLM,
)
from automodel_tpu.models.lfm2_moe.state_dict_adapter import (  # noqa: F401
    Lfm2MoeStateDictAdapter,
)
