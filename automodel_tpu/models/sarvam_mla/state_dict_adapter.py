"""HF <-> native adapter for Sarvam-105B (``sarvam_mla``).

The names are DeepSeek-V3's, ASSUMED (no checkpoint was read: the benchmark
configuration's ``assumed`` says so): per layer ``model.layers.{i}.``,
``input_layernorm``, ``post_attention_layernorm``, ``self_attn.{q_proj,
kv_a_proj_with_mqa, kv_a_layernorm, kv_b_proj, o_proj}``; ``mlp.{gate,up,
down}_proj`` on the dense layers; ``mlp.gate.weight``,
``mlp.gate.e_score_correction_bias``, ``mlp.experts.{j}.{gate,up,down}_proj``
and ``mlp.shared_experts.{gate,up,down}_proj`` on the expert layers;
``model.embed_tokens``, ``model.norm``, ``lm_head``. The native tree is
unstacked (models/sarvam_mla/model.py), so the mapping is leaf for leaf but
for the experts (stacked ``[E, D, 2I]`` gate|up, ``[E, I, D]`` down). With
``MoEConfig.held_experts`` only the held range's experts are read and written.
"""

from __future__ import annotations

from typing import Any, Callable, Iterator

import numpy as np

from automodel_tpu.models.sarvam_mla.model import SarvamMlaConfig, layer_name


def _t(x) -> np.ndarray:
    return np.ascontiguousarray(np.asarray(x).T)


def _same(x) -> np.ndarray:
    return np.asarray(x)


# (native path under the layer, HF suffix, the codec both ways)
_BLOCK = [
    (("input_norm", "scale"), "input_layernorm.weight", _same),
    (("post_attn_norm", "scale"), "post_attention_layernorm.weight", _same),
    (("attn", "q_proj", "kernel"), "self_attn.q_proj.weight", _t),
    (("attn", "kv_a_proj", "kernel"), "self_attn.kv_a_proj_with_mqa.weight", _t),
    (("attn", "kv_a_norm", "scale"), "self_attn.kv_a_layernorm.weight", _same),
    (("attn", "kv_b_proj", "kernel"), "self_attn.kv_b_proj.weight", _t),
    (("attn", "o_proj", "kernel"), "self_attn.o_proj.weight", _t),
]
_DENSE = [
    (("mlp", f"{n}_proj", "kernel"), f"mlp.{n}_proj.weight", _t) for n in ("gate", "up", "down")
]
_MOE = [
    (("moe", "router", "weight"), "mlp.gate.weight", _t),
    *[(("moe", "shared", f"{n}_proj", "kernel"), f"mlp.shared_experts.{n}_proj.weight", _t)
      for n in ("gate", "up", "down")],
]
_BIAS = (("moe", "router", "bias"), "mlp.gate.e_score_correction_bias", _same)
_TOP = [
    (("embed", "embedding"), "model.embed_tokens.weight", _same),
    (("final_norm", "scale"), "model.norm.weight", _same),
    (("lm_head", "kernel"), "lm_head.weight", _t),
]


class SarvamMlaStateDictAdapter:
    def __init__(self, config: SarvamMlaConfig):
        self.config = config
        lo, hi = config.moe.held_experts or (0, config.moe.num_experts)
        self.experts = list(range(lo, hi))

    def _plain(self, i: int) -> list:
        c = self.config
        if i < c.moe.num_dense_layers:
            return _BLOCK + _DENSE
        return _BLOCK + _MOE + ([_BIAS] if c.moe.expert_bias else [])

    def _expert_keys(self, i: int, j: int) -> tuple[str, str, str]:
        base = f"model.layers.{i}.mlp.experts.{j}"
        return (f"{base}.gate_proj.weight", f"{base}.up_proj.weight", f"{base}.down_proj.weight")

    def iter_from_hf(self, get_tensor: Callable[[str], np.ndarray]):
        c = self.config
        for path, key, load in _TOP:
            yield path, load(get_tensor(key))
        for i in range(c.num_layers):
            hf, at = f"model.layers.{i}.", ("layers", layer_name(i))
            for path, suffix, load in self._plain(i):
                yield (*at, *path), load(get_tensor(hf + suffix))
            if i < c.moe.num_dense_layers:
                continue
            keys = [self._expert_keys(i, j) for j in self.experts]
            yield (*at, "moe", "experts", "gate_up"), np.stack([
                np.concatenate([_t(get_tensor(g)), _t(get_tensor(u))], -1) for g, u, _ in keys
            ], 0)
            yield (*at, "moe", "experts", "down"), np.stack(
                [_t(get_tensor(d)) for _, _, d in keys], 0
            )

    def from_hf(self, get_tensor: Callable[[str], np.ndarray]) -> dict:
        from automodel_tpu.checkpoint.hf_io import assemble_tree

        return assemble_tree(self.iter_from_hf(get_tensor))

    def to_hf(self, params: Any) -> Iterator[tuple[str, np.ndarray]]:
        c = self.config

        def leaf(tree, path):
            for k in path:
                tree = tree[k]
            return tree

        for path, key, dump in _TOP:
            yield key, dump(leaf(params, path))
        for i in range(c.num_layers):
            hf, lp = f"model.layers.{i}.", params["layers"][layer_name(i)]
            for path, suffix, dump in self._plain(i):
                yield hf + suffix, dump(leaf(lp, path))
            if i < c.moe.num_dense_layers:
                continue
            gu = np.asarray(lp["moe"]["experts"]["gate_up"])
            dn = np.asarray(lp["moe"]["experts"]["down"])
            width = dn.shape[1]
            for e, j in enumerate(self.experts):
                g, u, d = self._expert_keys(i, j)
                yield g, _t(gu[e, :, :width])
                yield u, _t(gu[e, :, width:])
                yield d, _t(dn[e])

    def hf_keys(self) -> list[str]:
        c = self.config
        keys = [key for _, key, _ in _TOP]
        for i in range(c.num_layers):
            keys += [f"model.layers.{i}.{suffix}" for _, suffix, _ in self._plain(i)]
            if i >= c.moe.num_dense_layers:
                for j in self.experts:
                    keys += list(self._expert_keys(i, j))
        return keys
