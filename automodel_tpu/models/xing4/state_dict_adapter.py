"""HF <-> native adapter for Xing4.0 (hyper-connections + MLA + sparse experts
+ one multi-token-prediction module).

The native tree stacks layers by kind (model.py); HF keys are per layer. The
attention, MLP and expert keys are DeepSeek-V3's; the multi-token-prediction
module is layer ``num_hidden_layers + k`` as DeepSeek-V3 stores it (``enorm``,
``hnorm``, ``eh_proj``, ``shared_head.norm``; the embedding and the head are
the main model's and are not written twice). The hyper-connection maps are
``attn_hc`` / ``mlp_hc`` ``.phi [n C, n + n + n^2]``, ``.b``, ``.alpha``: those
names are this repo's (no checkpoint was read; the config file's ``assumed``
says so). With ``MoEConfig.held_experts`` only the held range's experts are
read and written, under their published numbers.
"""

from __future__ import annotations

from typing import Any, Callable, Iterator

import numpy as np

from automodel_tpu.models.xing4.model import Xing4Config


def _t(x: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(x.T)


# (native path under the stack, HF suffix, (native <- hf, hf <- native))
_PLAIN = (lambda x: x, lambda x: x)
_LINEAR = (_t, _t)

_LAYER = [
    (("input_norm", "scale"), "input_layernorm.weight", _PLAIN),
    (("post_attn_norm", "scale"), "post_attention_layernorm.weight", _PLAIN),
    *(((f"{s}_hc", leaf), f"{s}_hc.{leaf}", _PLAIN)
      for s in ("attn", "mlp") for leaf in ("phi", "b", "alpha")),
]
_MLA = [
    (("q_a_proj", "kernel"), "self_attn.q_a_proj.weight", _LINEAR),
    (("q_a_norm", "scale"), "self_attn.q_a_layernorm.weight", _PLAIN),
    (("q_b_proj", "kernel"), "self_attn.q_b_proj.weight", _LINEAR),
    (("kv_a_proj", "kernel"), "self_attn.kv_a_proj_with_mqa.weight", _LINEAR),
    (("kv_a_norm", "scale"), "self_attn.kv_a_layernorm.weight", _PLAIN),
    (("kv_b_proj", "kernel"), "self_attn.kv_b_proj.weight", _LINEAR),
    (("o_proj", "kernel"), "self_attn.o_proj.weight", _LINEAR),
]
_MLA_NO_Q_LORA = [(("q_proj", "kernel"), "self_attn.q_proj.weight", _LINEAR), *_MLA[3:]]
_DENSE = [((f"{n}_proj", "kernel"), f"mlp.{n}_proj.weight", _LINEAR)
          for n in ("gate", "up", "down")]
_MOE = [
    (("router", "weight"), "mlp.gate.weight", _LINEAR),
    (("router", "bias"), "mlp.gate.e_score_correction_bias", _PLAIN),
    *(((("shared", f"{n}_proj", "kernel")), f"mlp.shared_experts.{n}_proj.weight", _LINEAR)
      for n in ("gate", "up", "down")),
]
_MTP = [
    (("enorm", "scale"), "enorm.weight", _PLAIN),
    (("hnorm", "scale"), "hnorm.weight", _PLAIN),
    (("eh_proj", "kernel"), "eh_proj.weight", _LINEAR),
    (("final_norm", "scale"), "shared_head.norm.weight", _PLAIN),
]


def _leaf(tree: Any, path: tuple):
    for k in path:
        tree = tree[k]
    return tree


class Xing4StateDictAdapter:
    def __init__(self, config: Xing4Config):
        self.config = c = config
        L, nd, M = c.num_layers, c.moe.num_dense_layers, c.num_mtp_modules
        mla = _MLA if c.q_lora_rank else _MLA_NO_Q_LORA
        main, mtp = list(range(L)), list(range(L, L + M))
        # (native prefix, native stack, its table, the HF layers it holds, in order)
        self.stacks = [
            ((), "layers", _LAYER, main),
            ((), "mla", mla, main),
            ((), "dense_mlp", _DENSE, main[:nd]),
            ((), "moe", _MOE, main[nd:]),
            (("mtp",), None, _MTP, mtp),
            (("mtp",), "layers", _LAYER, mtp),
            (("mtp",), "mla", mla, mtp),
            (("mtp",), "moe", _MOE, mtp),
        ]
        lo, hi = c.moe.held_experts or (0, c.moe.num_experts)
        self.experts = list(range(lo, hi))

    def _top(self):
        yield ("embed", "embedding"), "model.embed_tokens.weight", _PLAIN
        yield ("final_norm", "scale"), "model.norm.weight", _PLAIN
        if not self.config.tie_embeddings:
            yield ("lm_head", "kernel"), "lm_head.weight", _LINEAR

    def _tables(self):
        """(native path of a stacked leaf, HF suffix, codec, HF layers)."""
        for prefix, stack, table, layers in self.stacks:
            if layers:
                for path, suffix, codec in table:
                    yield (*prefix, *((stack,) if stack else ()), *path), suffix, codec, layers

    def _expert_stacks(self):
        """(native path of the stack holding ``experts``, its HF layers)."""
        for prefix, stack, _, layers in self.stacks:
            if stack == "moe" and layers:
                yield (*prefix, "moe", "experts"), layers

    def _expert_keys(self, i: int, j: int) -> tuple[str, str, str]:
        base = f"model.layers.{i}.mlp.experts.{j}"
        return (f"{base}.gate_proj.weight", f"{base}.up_proj.weight", f"{base}.down_proj.weight")

    def iter_from_hf(self, get_tensor: Callable[[str], np.ndarray]):
        for path, key, (load, _) in self._top():
            yield path, load(get_tensor(key))
        for path, suffix, (load, _), layers in self._tables():
            yield path, np.stack([load(get_tensor(f"model.layers.{i}.{suffix}")) for i in layers], 0)
        for path, layers in self._expert_stacks():
            gus, dns = [], []
            for i in layers:
                keys = [self._expert_keys(i, j) for j in self.experts]
                gus.append(np.stack([np.concatenate(
                    [_t(get_tensor(g)), _t(get_tensor(u))], -1) for g, u, _ in keys], 0))
                dns.append(np.stack([_t(get_tensor(d)) for _, _, d in keys], 0))
            yield (*path, "gate_up"), np.stack(gus, 0)
            yield (*path, "down"), np.stack(dns, 0)

    def from_hf(self, get_tensor: Callable[[str], np.ndarray]) -> dict:
        from automodel_tpu.checkpoint.hf_io import assemble_tree

        return assemble_tree(self.iter_from_hf(get_tensor))

    def to_hf(self, params: Any) -> Iterator[tuple[str, np.ndarray]]:
        for path, key, (_, dump) in self._top():
            yield key, dump(np.asarray(_leaf(params, path)))
        for path, suffix, (_, dump), layers in self._tables():
            leaf = np.asarray(_leaf(params, path))
            for row, i in enumerate(layers):
                yield f"model.layers.{i}.{suffix}", dump(leaf[row])
        for path, layers in self._expert_stacks():
            gu = np.asarray(_leaf(params, (*path, "gate_up")))
            dn = np.asarray(_leaf(params, (*path, "down")))
            I = dn.shape[2]
            for row, i in enumerate(layers):
                for e, j in enumerate(self.experts):
                    g, u, d = self._expert_keys(i, j)
                    yield g, _t(gu[row, e, :, :I])
                    yield u, _t(gu[row, e, :, I:])
                    yield d, _t(dn[row, e])

    def hf_keys(self) -> list[str]:
        keys = [key for _, key, _ in self._top()]
        for _, suffix, _, layers in self._tables():
            keys += [f"model.layers.{i}.{suffix}" for i in layers]
        for _, layers in self._expert_stacks():
            for i in layers:
                for j in self.experts:
                    keys += list(self._expert_keys(i, j))
        return keys
