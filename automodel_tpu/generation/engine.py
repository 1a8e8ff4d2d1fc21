"""GenerationEngine: slot-based batched decoding over an AutoModel.

The facade ties the pieces together: ``from_pretrained``/``from_config`` →
MeshContext-sharded KV cache → jitted prefill → jitted while_loop decode →
detokenize. Each prompt owns a **slot** (a batch row) with its own length,
position offset and stop state; slots are padded to a common prompt length
(the packed segment-ids prefill masks the pads) and decode one token per
slot per step.

Also the `automodel_tpu generate` CLI entry point (``main``): YAML drives
model/mesh exactly like the training recipes, a ``generation:`` section
drives the engine, ``--prompt`` rides the ordinary dotted-override parser.
Without a tokenizer (tiny from-config models) prompts are whitespace- or
comma-separated token ids and completions print as token ids — the same
end-to-end path, minus the vocab.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import time
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from automodel_tpu.generation import kv_cache
from automodel_tpu.generation.loop import build_decode_fn, build_prefill_fn
from automodel_tpu.generation.sampling import SamplingConfig, sample
from automodel_tpu.training.rng import sampling_key

logger = logging.getLogger(__name__)


class GenerationUnsupported(ValueError):
    """The model family has no KV-cache decode path (benchmark/eval callers
    turn this into a null-with-recorded-reason leg, never a silent skip)."""


@dataclasses.dataclass(frozen=True)
class GenerationConfig:
    """The `generation:` YAML section."""

    max_new_tokens: int = 64
    max_length: Optional[int] = None  # hard context cap (prompt + new)
    temperature: float = 1.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    greedy: bool = False
    eos_token_id: Any = None  # int | [int] | None
    pad_token_id: int = 0
    seed: int = 0
    # pad prompts up to a multiple so repeated calls reuse one compiled
    # prefill instead of retracing per prompt length
    pad_to_multiple: int = 16

    @classmethod
    def from_dict(cls, d: Optional[dict]) -> "GenerationConfig":
        d = dict(d or {})
        d.pop("_target_", None)
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})

    @property
    def eos_ids(self) -> tuple:
        e = self.eos_token_id
        if e is None:
            return ()
        return tuple(e) if isinstance(e, (list, tuple)) else (int(e),)

    @property
    def sampling(self) -> SamplingConfig:
        return SamplingConfig(
            temperature=0.0 if self.greedy else self.temperature,
            top_k=self.top_k,
            top_p=self.top_p,
        )


def _model_max_positions(mcfg: Any) -> Optional[int]:
    for attr in ("max_position_embeddings", "n_positions"):
        v = getattr(mcfg, attr, None)
        if v:
            return int(v)
    return None


def _ring_window(mcfg: Any) -> Optional[int]:
    """Ring layout is only sound when EVERY layer is windowed with the same
    window (mistral-style). Mixed stacks (qwen2 max_window_layers, gemma
    alternating) keep the full layout; per-layer masks come from the tags."""
    window = getattr(mcfg, "sliding_window", None)
    if window is None:
        return None
    if getattr(mcfg, "max_window_layers", 0):
        return None
    return int(window)


class GenerationEngine:
    """Facade over (AutoModel, GenerationConfig[, tokenizer]).

    ``generate_ids`` takes/returns token ids (always available);
    ``generate`` adds tokenizer encode/decode around it. Pass ``params``
    explicitly to decode with weights other than the AutoModel's initial
    tree (train_ft's in-training eval generation passes the live
    ``state.params``)."""

    def __init__(self, auto: Any, config: Optional[GenerationConfig] = None, tokenizer: Any = None):
        layout = kv_cache.layout_of(auto.model)
        if layout is None:
            raise GenerationUnsupported(
                f"{type(auto.model).__name__} states no cache layout "
                "(cache_layout()): it has no decode path; cache-capable "
                "families: llama-generic (llama/qwen2/qwen3/mistral/phi3), "
                "gpt2, qwen3_moe, lfm2_moe (paged serving only)"
            )
        if kv_cache.recurrent_kinds(layout, held=("kv",)):
            raise GenerationUnsupported(
                f"{type(auto.model).__name__} keeps "
                f"{'/'.join(kv_cache.recurrent_kinds(layout, held=('kv',)))} state beside K/V: "
                "the contiguous generation cache holds per-head K/V alone "
                "(no recurrent-state row a sequence); serve it through the "
                "paged path (`automodel serve`, serving/engine.py)"
            )
        self.auto = auto
        self.model = auto.model
        self.config = config or GenerationConfig()
        self.tokenizer = tokenizer
        mcfg = self.model.config
        self._num_layers = int(mcfg.num_layers)
        self._num_kv_heads = int(mcfg.num_kv_heads)
        self._head_dim = int(mcfg.head_dim)
        self._window = _ring_window(mcfg)
        self._max_positions = _model_max_positions(mcfg)
        self._cache_dtype = self.model.backend.compute_jnp_dtype

        constrain = auto.constrain

        def apply(params, ids, **kw):
            return self.model(params, ids, constrain=constrain, **kw)

        self._prefill = build_prefill_fn(apply)
        self._decode = build_decode_fn(
            apply,
            self.config.sampling,
            self.config.max_new_tokens,
            eos_ids=self.config.eos_ids,
            pad_id=self.config.pad_token_id,
        )
        # per-host deterministic base stream; the decode loop folds the
        # step index in per token (training/rng.sampling_key)
        self._base_key = sampling_key(self.config.seed)
        # cost attribution (telemetry/profiling/): when armed, the next
        # generate_ids also records measured FLOPs/bytes of the prefill and
        # decode programs (abstract host trace; decode's while body counts
        # once = per-token cost)
        self.collect_program_costs = False
        self.program_costs: dict = {}

    # -- cache ---------------------------------------------------------------
    def _make_cache(
        self, batch: int, prompt_len: int, lengths: np.ndarray
    ) -> kv_cache.KVCache:
        total = prompt_len + self.config.max_new_tokens
        hard_cap = self.config.max_length or self._max_positions
        if hard_cap and total > hard_cap and self._window is None:
            raise ValueError(
                f"prompt ({prompt_len}) + max_new_tokens "
                f"({self.config.max_new_tokens}) = {total} exceeds the "
                f"context limit {hard_cap}"
            )
        capacity = total if self._window is None else min(total, self._window)
        if (
            self._window is not None
            and prompt_len > capacity
            and int(lengths.min()) < prompt_len
        ):
            # ring prefill writes only the padded tail [S-C, S): a short
            # slot's pad positions would evict real in-window history (the
            # worst case loses the slot's ENTIRE window) — reject loudly
            # rather than decode garbage (kv_cache.py ring caveat)
            raise ValueError(
                f"ragged prompt batch (lengths {int(lengths.min())}..."
                f"{int(lengths.max())}, padded {prompt_len}) wraps the ring "
                f"cache (window {capacity}): short slots would lose "
                "in-window history. Use equal-length prompts or prompts "
                "that fit the window"
            )
        cache = kv_cache.init_cache(
            self._num_layers, batch, capacity,
            self._num_kv_heads, self._head_dim,
            dtype=self._cache_dtype, window=self._window,
        )
        return kv_cache.place_cache(cache, self.auto.mesh_ctx)

    # -- generation ----------------------------------------------------------
    def generate_ids(
        self, prompts: Sequence[Sequence[int]], params: Any = None
    ) -> dict:
        """prompts: per-slot token-id lists → dict with per-slot completions
        (``tokens``) and timing stats (``ttft_s``, ``decode_tps``, ...)."""
        if not prompts:
            raise ValueError("generate_ids needs at least one prompt")
        if any(len(p) == 0 for p in prompts):
            raise ValueError("empty prompt (every slot needs >= 1 token)")
        params = self.auto.params if params is None else params
        B = len(prompts)
        lengths = np.array([len(p) for p in prompts], np.int32)
        m = max(int(self.config.pad_to_multiple), 1)
        S = int(-(-int(lengths.max()) // m) * m)
        ids = np.full((B, S), self.config.pad_token_id, np.int32)
        for b, p in enumerate(prompts):
            ids[b, : len(p)] = np.asarray(p, np.int32)

        cache = self._make_cache(B, S, lengths)
        cache_bytes = cache.nbytes
        if self.collect_program_costs and "prefill" not in self.program_costs:
            self._record_cost(
                "prefill", self._prefill,
                params, jnp.asarray(ids), jnp.asarray(lengths), cache,
            )
        t0 = time.perf_counter()
        last_logits, cache = self._prefill(
            params, jnp.asarray(ids), jnp.asarray(lengths), cache
        )
        first = sample(
            last_logits, jax.random.fold_in(self._base_key, 0),
            self.config.sampling,
        )
        first = jax.block_until_ready(first)
        ttft_s = time.perf_counter() - t0

        if self.collect_program_costs and "decode" not in self.program_costs:
            self._record_cost(
                "decode", self._decode, params, cache, first, self._base_key
            )
        t1 = time.perf_counter()
        result, cache = self._decode(params, cache, first, self._base_key)
        result = jax.device_get(result)
        decode_s = time.perf_counter() - t1

        tokens = np.asarray(result["tokens"])
        n_gen = np.asarray(result["n_generated"])
        steps = int(result["steps"])
        # decode throughput counts the tokens the DECODE program produced
        # (the first token came out of prefill and is charged to ttft)
        decode_tokens = int(n_gen.sum()) - B
        completions = [tokens[b, : int(n_gen[b])].tolist() for b in range(B)]
        return {
            "tokens": completions,
            "n_generated": n_gen.tolist(),
            "gen_tokens": int(n_gen.sum()),
            "prefill_tokens": int(lengths.sum()),
            "decode_steps": steps,
            "ttft_s": ttft_s,
            "decode_s": decode_s,
            "decode_tps": decode_tokens / decode_s if decode_s > 0 else 0.0,
            "cache_bytes": cache_bytes,
        }

    def _record_cost(self, name: str, jit_fn, *args) -> None:
        from automodel_tpu.telemetry.profiling import record_program_cost

        record_program_cost(self.program_costs, name, jit_fn, *args)

    def generate(self, prompts: Sequence[str], params: Any = None) -> dict:
        """Text in, text out (requires a tokenizer). Returns the
        ``generate_ids`` dict plus ``texts``."""
        if self.tokenizer is None:
            raise ValueError(
                "generate() needs a tokenizer; use generate_ids() or "
                "configure generation.tokenizer"
            )
        encoded = [
            self.tokenizer(p, add_special_tokens=True)["input_ids"]
            if callable(self.tokenizer)
            else self.tokenizer.encode(p)
            for p in prompts
        ]
        out = self.generate_ids(encoded, params=params)
        out["texts"] = [
            self.tokenizer.decode(t, skip_special_tokens=True)
            for t in out["tokens"]
        ]
        return out


# -- CLI ----------------------------------------------------------------------


def _parse_id_prompt(p: str) -> Optional[list[int]]:
    toks = p.replace(",", " ").split()
    try:
        return [int(t) for t in toks] if toks else None
    except ValueError:
        return None


def resolve_tokenizer(tok_cfg: Any, fallback_path: Optional[str] = None) -> Any:
    """The generation.tokenizer resolution ladder, shared by the generate
    CLI and train_ft's in-training eval sampling: a ``_target_`` ConfigNode
    instantiates, a path string goes through data.tokenizer.build_tokenizer,
    otherwise ``fallback_path`` (the model checkpoint's own tokenizer) is
    tried; unresolvable → None (token-id mode), with a warning."""
    from automodel_tpu.config.loader import ConfigNode

    if isinstance(tok_cfg, ConfigNode):
        return tok_cfg.instantiate()
    from automodel_tpu.data.tokenizer import build_tokenizer

    path = tok_cfg if isinstance(tok_cfg, str) else fallback_path
    if not path:
        return None
    try:
        return build_tokenizer(path)
    except Exception as e:
        logger.warning("no tokenizer from %s (%s); token-id mode", path, e)
        return None


def build_auto_from_model_section(
    mcfg: Any, mesh_ctx: Any, seed: int = 0
) -> Any:
    """AutoModel from a ``model:``-shaped section (``pretrained_model_name_
    or_path`` or ``hf_config`` + ``backend``) on an EXISTING mesh — the
    tail of the `generate`/`serve` CLI ladder, also how the serving
    engine builds its speculative-decoding draft model
    (``serving.speculative.draft:``, same schema) onto the target's mesh."""
    from automodel_tpu import auto_model

    get = mcfg.get if hasattr(mcfg, "get") else dict(mcfg).get
    backend = dict(get("backend", {}) or {})
    if get("pretrained_model_name_or_path"):
        return auto_model.from_pretrained(
            get("pretrained_model_name_or_path"), mesh_ctx, backend
        )
    hf = get("hf_config")
    if hf is None:
        raise ValueError(
            "model section needs pretrained_model_name_or_path or hf_config"
        )
    return auto_model.from_config(
        hf.to_dict() if hasattr(hf, "to_dict") else dict(hf),
        mesh_ctx, backend, seed=seed,
    )


def build_auto_from_cfg(cfg: Any) -> Any:
    """Model + mesh from the same YAML sections the recipes use — shared by
    the `generate` and `serve` CLIs (serving/server.py)."""
    from automodel_tpu.config.loader import ConfigNode
    from automodel_tpu.parallel.mesh import MeshConfig, build_mesh

    dist = cfg.get("distributed", ConfigNode())
    platform = dist.get("platform", None)
    devices = jax.devices(platform) if platform else None
    mesh_ctx = build_mesh(MeshConfig.from_section(dist), devices=devices)
    return build_auto_from_model_section(
        cfg.model, mesh_ctx, seed=cfg.get("seed", 0)
    )


def main(cfg: Any) -> int:
    """`automodel_tpu generate -c cfg.yaml [--prompt '...']`"""
    from automodel_tpu.loggers.log_utils import setup_logging

    setup_logging()
    auto = build_auto_from_cfg(cfg)
    mcfg = cfg.model

    gen_section = dict(cfg.get("generation", {}) or {})
    gen_config = GenerationConfig.from_dict(gen_section)
    tokenizer = resolve_tokenizer(
        gen_section.get("tokenizer"), mcfg.get("pretrained_model_name_or_path")
    )
    engine = GenerationEngine(auto, gen_config, tokenizer=tokenizer)

    prompts = cfg.get("prompt") or gen_section.get("prompts")
    prompt_ids = gen_section.get("prompt_ids")
    if prompts is None and prompt_ids is None:
        print("no prompt: pass --prompt '...' or set generation.prompts / generation.prompt_ids")
        return 2
    if isinstance(prompts, str):
        prompts = [prompts]
    prompts = list(prompts or [])

    if prompt_ids is not None:
        out = engine.generate_ids([list(map(int, p)) for p in prompt_ids])
        texts = [" ".join(map(str, t)) for t in out["tokens"]]
        shown = [" ".join(map(str, p)) for p in prompt_ids]
    elif tokenizer is not None:
        out = engine.generate(prompts)
        texts, shown = out["texts"], prompts
    else:
        ids = [_parse_id_prompt(p) for p in prompts]
        if any(i is None for i in ids):
            print(
                "no tokenizer available: prompts must be token ids "
                "(e.g. --prompt '1 2 3') or configure generation.tokenizer"
            )
            return 2
        out = engine.generate_ids(ids)
        texts = [" ".join(map(str, t)) for t in out["tokens"]]
        shown = prompts
    for p, t in zip(shown, texts):
        print(f"prompt: {p}")
        print(f"completion: {t}")
    stats = {k: out[k] for k in (
        "ttft_s", "decode_tps", "gen_tokens", "prefill_tokens",
        "decode_steps", "cache_bytes",
    )}
    print(json.dumps({"event": "generation", **stats}))
    return 0
