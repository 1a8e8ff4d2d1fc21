"""HF <-> native adapter for LFM2-MoE (``Lfm2MoeForCausalLM``).

HF keys are per layer, ``model.layers.{i}.``: ``operator_norm``, ``ffn_norm``;
``conv.{in_proj,conv,out_proj}`` on a conv layer, ``self_attn.{q,k,v}_proj``,
``self_attn.out_proj``, ``self_attn.{q,k}_layernorm`` on an attention layer;
``feed_forward.{w1,w3,w2}`` (gate, up, down) on a dense layer,
``feed_forward.gate``, ``feed_forward.expert_bias`` and
``feed_forward.experts.{j}.{w1,w3,w2}`` on an expert layer. The final norm is
``model.embedding_norm``; the head is tied to ``model.embed_tokens``. The
native tree is unstacked too (models/lfm2_moe/model.py), so the mapping is
leaf for leaf but for the experts (stacked ``[E, D, 2I]`` gate|up, ``[E, I,
D]`` down) and the conv taps (``[D, 1, K]`` -> ``[D, K]``).
"""

from __future__ import annotations

from typing import Any, Callable, Iterator

import numpy as np

from automodel_tpu.models.lfm2_moe.model import Lfm2MoeConfig, layer_name


def _t(x: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(np.asarray(x).T)


def _same(x: np.ndarray) -> np.ndarray:
    return np.asarray(x)


def _taps_in(x: np.ndarray) -> np.ndarray:
    return np.asarray(x)[:, 0, :]


def _taps_out(x: np.ndarray) -> np.ndarray:
    return np.asarray(x)[:, None, :]


# (native path under the layer, HF suffix, HF -> native, native -> HF)
_CONV = [
    (("conv", "in_proj", "kernel"), "conv.in_proj.weight", _t, _t),
    (("conv", "weight"), "conv.conv.weight", _taps_in, _taps_out),
    (("conv", "out_proj", "kernel"), "conv.out_proj.weight", _t, _t),
]
_ATTN = [
    (("attn", "q_proj", "kernel"), "self_attn.q_proj.weight", _t, _t),
    (("attn", "k_proj", "kernel"), "self_attn.k_proj.weight", _t, _t),
    (("attn", "v_proj", "kernel"), "self_attn.v_proj.weight", _t, _t),
    (("attn", "o_proj", "kernel"), "self_attn.out_proj.weight", _t, _t),
    (("attn", "q_norm", "scale"), "self_attn.q_layernorm.weight", _same, _same),
    (("attn", "k_norm", "scale"), "self_attn.k_layernorm.weight", _same, _same),
]
_NORMS = [
    (("operator_norm", "scale"), "operator_norm.weight", _same, _same),
    (("ffn_norm", "scale"), "ffn_norm.weight", _same, _same),
]
_DENSE = [
    (("mlp", "gate_proj", "kernel"), "feed_forward.w1.weight", _t, _t),
    (("mlp", "up_proj", "kernel"), "feed_forward.w3.weight", _t, _t),
    (("mlp", "down_proj", "kernel"), "feed_forward.w2.weight", _t, _t),
]


class Lfm2MoeStateDictAdapter:
    def __init__(self, config: Lfm2MoeConfig):
        self.config = config

    def _plain(self, i: int) -> list:
        c = self.config
        plans = list(_NORMS)
        plans += _CONV if c.layer_types[i] == "conv" else _ATTN
        if i < c.moe.num_dense_layers:
            plans += _DENSE
        return plans

    def _is_expert_layer(self, i: int) -> bool:
        return i >= self.config.moe.num_dense_layers

    def iter_from_hf(self, get_tensor: Callable[[str], np.ndarray]):
        c = self.config
        yield ("embed", "embedding"), get_tensor("model.embed_tokens.weight")
        yield ("final_norm", "scale"), get_tensor("model.embedding_norm.weight")
        if not c.tie_embeddings:
            yield ("lm_head", "kernel"), _t(get_tensor("lm_head.weight"))
        for i in range(c.num_layers):
            hf, at = f"model.layers.{i}.", ("layers", layer_name(i))
            for path, suffix, load, _ in self._plain(i):
                yield (*at, *path), load(get_tensor(hf + suffix))
            if not self._is_expert_layer(i):
                continue
            ff = hf + "feed_forward."
            yield (*at, "moe", "router", "weight"), _t(get_tensor(ff + "gate.weight"))
            if c.moe.expert_bias:
                yield (*at, "moe", "router", "bias"), np.asarray(
                    get_tensor(ff + "expert_bias"), np.float32
                )
            experts = range(c.moe.num_experts)
            yield (*at, "moe", "experts", "gate_up"), np.stack([
                np.concatenate([
                    _t(get_tensor(f"{ff}experts.{j}.w1.weight")),
                    _t(get_tensor(f"{ff}experts.{j}.w3.weight")),
                ], -1)
                for j in experts
            ], 0)
            yield (*at, "moe", "experts", "down"), np.stack(
                [_t(get_tensor(f"{ff}experts.{j}.w2.weight")) for j in experts], 0
            )

    def from_hf(self, get_tensor: Callable[[str], np.ndarray]) -> dict:
        from automodel_tpu.checkpoint.hf_io import assemble_tree

        return assemble_tree(self.iter_from_hf(get_tensor))

    def to_hf(self, params: Any) -> Iterator[tuple[str, np.ndarray]]:
        c = self.config
        yield "model.embed_tokens.weight", np.asarray(params["embed"]["embedding"])
        yield "model.embedding_norm.weight", np.asarray(params["final_norm"]["scale"])
        if not c.tie_embeddings:
            yield "lm_head.weight", _t(params["lm_head"]["kernel"])
        for i in range(c.num_layers):
            hf, lp = f"model.layers.{i}.", params["layers"][layer_name(i)]
            for path, suffix, _, save in self._plain(i):
                node = lp
                for k in path:
                    node = node[k]
                yield hf + suffix, save(node)
            if not self._is_expert_layer(i):
                continue
            ff, mp = hf + "feed_forward.", lp["moe"]
            yield ff + "gate.weight", _t(mp["router"]["weight"])
            if c.moe.expert_bias:
                yield ff + "expert_bias", np.asarray(mp["router"]["bias"])
            gu, dn = np.asarray(mp["experts"]["gate_up"]), np.asarray(mp["experts"]["down"])
            width = dn.shape[1]
            for j in range(c.moe.num_experts):
                yield f"{ff}experts.{j}.w1.weight", _t(gu[j, :, :width])
                yield f"{ff}experts.{j}.w3.weight", _t(gu[j, :, width:])
                yield f"{ff}experts.{j}.w2.weight", _t(dn[j])

    def to_hf_shapes(self):
        """(key, None) pairs without needing params — mirrors to_hf's keys."""
        c = self.config
        yield "model.embed_tokens.weight", None
        yield "model.embedding_norm.weight", None
        if not c.tie_embeddings:
            yield "lm_head.weight", None
        for i in range(c.num_layers):
            hf = f"model.layers.{i}."
            for _, suffix, _, _ in self._plain(i):
                yield hf + suffix, None
            if not self._is_expert_layer(i):
                continue
            yield hf + "feed_forward.gate.weight", None
            if c.moe.expert_bias:
                yield hf + "feed_forward.expert_bias", None
            for j in range(c.moe.num_experts):
                for n in ("w1", "w3", "w2"):
                    yield f"{hf}feed_forward.experts.{j}.{n}.weight", None

    def hf_keys(self) -> list[str]:
        return [k for k, _ in self.to_hf_shapes()]
