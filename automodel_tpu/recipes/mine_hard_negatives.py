"""Hard-negative mining for biencoder training.

Parity: reference recipes/biencoder/mine_hard_negatives.py (1,320 LoC) —
embed a document corpus and a query set with a (trained) biencoder, take the
top-k·buffer most similar documents per query, drop the query's annotated
positives, drop near-positives above a margin threshold derived from the
MINIMUM positive score (``abs``: min_pos - margin; ``perc``: min_pos ·
margin — reference :1046-1051), keep ``num_negatives``, and write a JSONL
training file with the mined negatives and their scores.

TPU-native shape: embedding runs as one jitted batch fn over the dp mesh;
similarity search is exact chunked matmul + ``lax.top_k`` on device (no ANN
dependency — the reference also does exact search on GPU); the
filter/emit stage is host-side numpy over the small top-k candidate sets.

YAML:
  model: {hf_config | pretrained_model_name_or_path, backend, pooling}
  data: {queries: <dataset/_target_ or list>, corpus: <...>}
    queries yield {"input_ids": [...], "pos_doc_ids": [ids]}
    corpus  yield {"id": ..., "input_ids": [...]}
  mining: {num_negatives, hard_neg_margin, hard_neg_margin_type,
           topk_buffer_multiplier, embed_batch_size, max_length}
  output_path: mined.jsonl
"""

from __future__ import annotations

import json
import logging
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from automodel_tpu import auto_model
from automodel_tpu.config.loader import ConfigNode
from automodel_tpu.models.biencoder import LlamaBidirectionalModel
from automodel_tpu.parallel.mesh import MeshConfig, build_mesh

logger = logging.getLogger(__name__)

_DEFAULTS = {
    "num_negatives": 4,
    "hard_neg_margin": 0.95,
    "hard_neg_margin_type": "perc",
    "topk_buffer_multiplier": 2,
    "embed_batch_size": 32,
    "max_length": 128,
}


def _pad_to(rows: list[list[int]], length: int, pad_id: int = 0):
    ids = np.full((len(rows), length), pad_id, np.int32)
    mask = np.zeros((len(rows), length), np.int32)
    for i, r in enumerate(rows):
        r = list(r)[:length]
        ids[i, : len(r)] = r
        mask[i, : len(r)] = 1
    return ids, mask


class MineHardNegativesRecipe:
    def __init__(self, cfg: ConfigNode):
        self.cfg = cfg

    def setup(self) -> None:
        cfg = self.cfg
        self.mesh_ctx = build_mesh(MeshConfig.from_section(cfg.get("distributed")))

        mcfg = cfg.model
        backend = dict(mcfg.get("backend", {}) or {})
        if mcfg.get("pretrained_model_name_or_path"):
            auto = auto_model.from_pretrained(
                mcfg.pretrained_model_name_or_path, self.mesh_ctx, backend
            )
        else:
            hf = mcfg.get("hf_config")
            auto = auto_model.from_config(
                hf.to_dict() if isinstance(hf, ConfigNode) else dict(hf),
                self.mesh_ctx, backend, seed=cfg.get("seed", 42),
            )
        self.model = LlamaBidirectionalModel(
            auto.model.config, auto.model.backend,
            pooling=mcfg.get("pooling", "avg"),
            normalize=True,  # mining scores are cosine similarities
        )
        params = dict(auto.params)
        params.pop("lm_head", None)
        self.params = params
        self.constrain = auto.constrain

        m = {**_DEFAULTS, **dict(cfg.get("mining", {}) or {})}
        self.mining = m
        if m["hard_neg_margin_type"] not in ("perc", "abs"):
            raise ValueError(
                f"hard_neg_margin_type {m['hard_neg_margin_type']!r}; "
                "must be 'perc' or 'abs'"
            )

        model, constrain = self.model, self.constrain

        @jax.jit
        def embed(params, ids, mask):
            return model(params, ids, attention_mask=mask, constrain=constrain)

        self._embed = embed

    def _embed_rows(self, rows: list[list[int]]) -> np.ndarray:
        bs = int(self.mining["embed_batch_size"])
        L = int(self.mining["max_length"])
        out = []
        for i in range(0, len(rows), bs):
            chunk = rows[i : i + bs]
            pad = bs - len(chunk)  # fixed batch → one compiled shape
            ids, mask = _pad_to(chunk + [[0]] * pad, L)
            emb = np.asarray(self._embed(self.params, jnp.asarray(ids), jnp.asarray(mask)))
            out.append(emb[: len(chunk)])
        return np.concatenate(out, 0)

    def mine(self) -> list[dict]:
        cfg = self.cfg
        data = cfg.get("data")
        queries = list(self._materialize(data.get("queries")))
        corpus = list(self._materialize(data.get("corpus")))
        if not queries or not corpus:
            raise ValueError(
                f"mining needs non-empty data: {len(queries)} queries, "
                f"{len(corpus)} corpus documents"
            )
        m = self.mining
        logger.info("mining: %d queries over %d documents", len(queries), len(corpus))

        doc_ids = [d["id"] for d in corpus]
        doc_pos = {d: i for i, d in enumerate(doc_ids)}
        d_emb = self._embed_rows([list(d["input_ids"]) for d in corpus])
        q_emb = self._embed_rows([list(q["input_ids"]) for q in queries])

        k = min(
            len(corpus),
            int(m["num_negatives"]) * int(m["topk_buffer_multiplier"])
            + max((len(q.get("pos_doc_ids", [])) for q in queries), default=0),
        )

        # chunked exact search: matmul + top_k per query chunk ON DEVICE —
        # never materializes the full [Q, N] score matrix (a 100k x 1M
        # corpus would be 400GB)
        d_dev = jnp.asarray(d_emb)

        @jax.jit
        def search(qc):
            s = qc @ d_dev.T
            return jax.lax.top_k(s, k)

        qchunk = max(int(m["embed_batch_size"]) * 8, 256)
        ts_parts, ti_parts = [], []
        for i in range(0, len(q_emb), qchunk):
            ts, ti = search(jnp.asarray(q_emb[i : i + qchunk]))
            ts_parts.append(np.asarray(ts))
            ti_parts.append(np.asarray(ti))
        top_scores = np.concatenate(ts_parts, 0)
        top_idx = np.concatenate(ti_parts, 0)

        results = []
        margin = float(m["hard_neg_margin"])
        for qi, q in enumerate(queries):
            pos = [doc_pos[d] for d in q.get("pos_doc_ids", []) if d in doc_pos]
            pos_scores = [float(q_emb[qi] @ d_emb[p]) for p in pos]
            min_pos = min(pos_scores) if pos_scores else 0.0
            thr = (
                min_pos - margin
                if m["hard_neg_margin_type"] == "abs"
                else min_pos * margin
            )
            negs, neg_scores = [], []
            for s, di in zip(top_scores[qi], top_idx[qi]):
                if int(di) in pos:
                    continue
                if pos_scores and float(s) >= thr:
                    continue  # too close to a positive → likely false negative
                negs.append(doc_ids[int(di)])
                neg_scores.append(float(s))
                if len(negs) >= int(m["num_negatives"]):
                    break
            results.append(
                {
                    "query_input_ids": list(q["input_ids"]),
                    "pos_doc_ids": list(q.get("pos_doc_ids", [])),
                    "neg_doc_ids": negs,
                    "neg_scores": neg_scores,
                    "pos_scores": pos_scores,
                }
            )

        out_path = cfg.get("output_path")
        if out_path:
            with open(out_path, "w") as f:
                for r in results:
                    f.write(json.dumps(r) + "\n")
            logger.info("wrote %d mined rows to %s", len(results), out_path)
        return results

    @staticmethod
    def _materialize(node: Any):
        if node is None:
            raise ValueError("data.queries and data.corpus are required")
        if isinstance(node, ConfigNode):
            return node.maybe_instantiate()
        return node


def main(cfg: ConfigNode) -> list[dict]:
    recipe = MineHardNegativesRecipe(cfg)
    recipe.setup()
    return recipe.mine()
