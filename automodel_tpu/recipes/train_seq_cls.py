"""Sequence-classification recipe (GLUE-style).

Parity: reference train_seq_cls.py (recipes/llm/train_seq_cls.py:439). Reuses
the finetune recipe skeleton with a classification head + CE-over-labels
loss; datasets must yield {input_ids, attention_mask, label}.

YAML additions over train_ft: model.num_labels
"""

from __future__ import annotations

import logging

import jax

from automodel_tpu.config.loader import ConfigNode
from automodel_tpu.data.collators import seq_cls_collater
from automodel_tpu.data.loader import BATCH_KEY_SPECS
from automodel_tpu.models.common.config import BackendConfig, TransformerConfig
from automodel_tpu.models.llama.seq_cls import (
    LlamaForSequenceClassification,
    make_seq_cls_loss,
)
from automodel_tpu.optim.builders import init_opt_state
from automodel_tpu.recipes.train_ft import TrainFinetuneRecipeForNextTokenPrediction
from automodel_tpu.training.train_state import TrainState
from automodel_tpu.training.train_step import build_eval_step

logger = logging.getLogger(__name__)

BATCH_KEY_SPECS.setdefault("attention_mask", ("batch", "seq"))
BATCH_KEY_SPECS.setdefault("label", ("batch",))


class TrainSeqClsRecipe(TrainFinetuneRecipeForNextTokenPrediction):
    def setup(self) -> None:
        super().setup()
        cfg = self.cfg
        mcfg = cfg.model
        num_labels = int(mcfg.get("num_labels", 2))
        backend = BackendConfig(**dict(mcfg.get("backend", {}) or {}))
        hf = mcfg.get("hf_config")
        tcfg = TransformerConfig.from_hf(
            hf.to_dict() if isinstance(hf, ConfigNode) else hf
        )
        model = LlamaForSequenceClassification(tcfg, num_labels, backend)
        # reuse backbone params from the auto-model; add the score head
        params = dict(self.auto.params)
        params.pop("lm_head", None)
        head = model.init(jax.random.key(cfg.get("seed", 42) + 7))
        params["score"] = head["score"]
        from automodel_tpu.parallel.plans import shard_params

        params = shard_params(self.mesh_ctx, params, model.sharding_rules)
        self.model = model
        opt_state = init_opt_state(self.optimizer, params, self.mesh_ctx)
        self.state = TrainState.create(params, opt_state)
        self.loss_fn = make_seq_cls_loss(model)
        self.train_step = self._make_train_step(self.loss_fn)
        self.eval_step = build_eval_step(self.loss_fn)
        logger.info("seq-cls: %d labels", num_labels)

    def _build_dataloader(self, dataset_cfg, dl_cfg):
        dl = dict(dl_cfg or {})
        dl.setdefault("collate_fn", seq_cls_collater)
        return super()._build_dataloader(dataset_cfg, dl)


def main(cfg: ConfigNode) -> dict:
    r = TrainSeqClsRecipe(cfg)
    r.setup()
    return r.run_train_validation_loop()
