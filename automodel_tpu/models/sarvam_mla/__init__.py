from automodel_tpu.models.sarvam_mla.model import SarvamMlaConfig, SarvamMlaForCausalLM
from automodel_tpu.models.sarvam_mla.state_dict_adapter import SarvamMlaStateDictAdapter

__all__ = ["SarvamMlaConfig", "SarvamMlaForCausalLM", "SarvamMlaStateDictAdapter"]
