"""Weights from ``--seed``: made on the device in one jitted call, in the type
the configuration serves them in, on the shards the program's plan names.

The program hands over only the SHAPE of its parameter tree
(``auto_model.from_config(abstract=True)``); every value is the benchmark's,
so the reference can be given the same numbers without taking anything the
program has made. Scales of norms are 1 + 0.1 N(0,1) and the router's
correction bias 0.05 N(0,1) so that a skipped norm or a forgotten bias shows;
matrices are N(0,1)/sqrt(fan_in); the embedding N(0, 0.02).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """Any whole number up to a little over 2**31 (and beyond)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def path_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


CHUNK_ELEMENTS = 1 << 26  # the float32 noise of one chunk stays at 256 MB


def _values(key, name: str, shape, fan_in: int, dtype):
    noise = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("scale"):
        w = 1.0 + 0.1 * noise
    elif name.endswith("router/bias"):
        w = 0.05 * noise
    elif name.endswith("embedding"):
        w = 0.02 * noise
    elif fan_in:
        w = noise / jnp.sqrt(jnp.float32(fan_in))
    else:
        w = jnp.zeros(shape, jnp.float32)
    return w.astype(dtype)


def _leaf(key, name: str, shape, dtype):
    """One leaf; a large one is drawn chunk by chunk (``lax.map``), so that
    its float32 noise never exists whole beside the weights."""
    fan_in = shape[-2] if len(shape) >= 2 else 0
    size = 1
    for d in shape:
        size *= d
    if size <= CHUNK_ELEMENTS:
        return _values(key, name, shape, fan_in, dtype)
    axis = next(i for i, d in enumerate(shape) if d > 1)
    n = next(d for d in range(1, shape[axis] + 1)
             if shape[axis] % d == 0 and size // d <= CHUNK_ELEMENTS or d == shape[axis])
    chunk = (*shape[:axis], shape[axis] // n, *shape[axis + 1:])
    parts = jax.lax.map(
        lambda k: _values(k, name, chunk, fan_in, dtype), jax.random.split(key, n)
    )
    return jnp.moveaxis(parts, 0, axis).reshape(shape)


def make(abstract_tree, seed: int, reference_layout: bool = False):
    """``abstract_tree``: ShapeDtypeStructs (with shardings, or none). With
    ``reference_layout`` the same numbers come out under the reference's
    per-layer names (``to_reference``), restructured inside the same jitted
    call so that no second copy of the weights ever exists."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract_tree)
    names = [path_name(p) for p, _ in leaves]
    shardings = [getattr(a, "sharding", None) for _, a in leaves]

    def build(key):
        flat = [
            _leaf(jax.random.fold_in(key, i), names[i], a.shape, a.dtype)
            for i, (_, a) in enumerate(leaves)
        ]
        tree = jax.tree_util.tree_unflatten(treedef, flat)
        return to_reference(tree) if reference_layout else tree

    out_shardings = None
    if not reference_layout and all(s is not None for s in shardings):
        out_shardings = jax.tree_util.tree_unflatten(treedef, shardings)
    return jax.jit(build, out_shardings=out_shardings)(seed_key(seed))


def to_reference(tree: dict) -> dict:
    """The program's stacked tree -> the reference's per-layer naming. Only a
    restructure: every number stays as ``make`` drew it."""
    if "dense_layers" in tree:
        raise ValueError("the reference has no dense-prefix layers")
    ml = tree["moe_layers"]
    n_layers = ml["input_norm"]["scale"].shape[0]
    layers = []
    for i in range(n_layers):
        attn, moe = ml["attn"], ml["moe"]
        lp = {
            "attn_norm": ml["input_norm"]["scale"][i],
            "mlp_norm": ml["post_attn_norm"]["scale"][i],
            "q": attn["q_proj"]["kernel"][i],
            "k": attn["k_proj"]["kernel"][i],
            "v": attn["v_proj"]["kernel"][i],
            "o": attn["o_proj"]["kernel"][i],
            "router": moe["router"]["weight"][i],
            "gate_up": moe["experts"]["gate_up"][i],
            "down": moe["experts"]["down"][i],
        }
        if "q_norm" in attn:
            lp["q_norm"] = attn["q_norm"]["scale"][i]
            lp["k_norm"] = attn["k_norm"]["scale"][i]
        if "bias" in moe["router"]:
            lp["router_bias"] = moe["router"]["bias"][i]
        layers.append(lp)
    return {
        "embed": tree["embed"]["embedding"],
        "head": tree["lm_head"]["kernel"],
        "final_norm": tree["final_norm"]["scale"],
        "layers": layers,
    }


REFERENCE_NAMES = {
    "attn_norm": "moe_layers/input_norm/scale",
    "mlp_norm": "moe_layers/post_attn_norm/scale",
    "q": "moe_layers/attn/q_proj/kernel", "k": "moe_layers/attn/k_proj/kernel",
    "v": "moe_layers/attn/v_proj/kernel", "o": "moe_layers/attn/o_proj/kernel",
    "q_norm": "moe_layers/attn/q_norm/scale", "k_norm": "moe_layers/attn/k_norm/scale",
    "router": "moe_layers/moe/router/weight", "router_bias": "moe_layers/moe/router/bias",
    "gate_up": "moe_layers/moe/experts/gate_up", "down": "moe_layers/moe/experts/down",
}


def by_program_name(ref_tree: dict, program_tree: dict, norms: bool) -> dict:
    """Per-leaf values laid out like ``to_reference`` -> keyed by the
    program's leaf names. ``norms``: the values are per-leaf norms, and a
    stacked leaf's is the root of its layers' sum of squares; otherwise they
    are arrays, stacked along the program's leading layer axis."""
    import numpy as np

    def stack(key):
        vals = [np.asarray(lp[key], np.float64) for lp in ref_tree["layers"]]
        if norms:
            return float(np.sqrt(np.sum(np.square(vals))))
        return np.stack(vals)

    one = (lambda a: float(a)) if norms else (lambda a: np.asarray(a, np.float64))
    out = {
        "embed/embedding": one(ref_tree["embed"]),
        "lm_head/kernel": one(ref_tree["head"]),
        "final_norm/scale": one(ref_tree["final_norm"]),
    }
    for key in ref_tree["layers"][0]:
        out[REFERENCE_NAMES[key]] = stack(key)
    have = {path_name(p) for p, _ in jax.tree_util.tree_flatten_with_path(program_tree)[0]}
    if set(out) ^ have:
        raise ValueError(f"leaf names differ between program and reference: {sorted(set(out) ^ have)}")
    return out
