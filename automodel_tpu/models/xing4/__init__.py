from automodel_tpu.models.xing4.model import (  # noqa: F401
    Xing4Config,
    Xing4ForCausalLM,
)
from automodel_tpu.models.xing4.state_dict_adapter import (  # noqa: F401
    Xing4StateDictAdapter,
)
