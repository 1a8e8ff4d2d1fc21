"""Jitted paged-KV programs: chunk prefill, paged decode (fused Pallas
kernel or XLA-gather fallback), and the speculative draft/verify pair.

The pool is a :class:`PagedKV`, laid out by the KIND of state each layer
keeps (the model's ``cache_layout()``, generation/kv_cache.py): for the
``L_kv`` layers that keep per-head K/V, k/v block arrays ``[L_kv, NB, BS,
N_kv, H]`` in the model's compute dtype, or int8 with per-(token row, kv
head) fp32 scales ``[L_kv, NB, BS, N_kv]`` riding alongside
(``serving.kv_cache_dtype: int8`` — roughly half the bytes per resident
token, so ~2× the sequences per chip on the same HBM budget); and for the
``L_conv`` layers that keep a short conv's last inputs, ONE slot-indexed
array ``state [L_conv, slots, taps - 1, C]`` beside them (None when every
layer keeps K/V). A model whose layers keep ONE latent row a token
(multi-head latent attention, ``latent``) gets a pool of ONE side instead:
``k`` ``[L, NB, BS, W]`` (``W`` = 512 + 64 padded to whole lanes), ``v`` None;
its chunk program and its decode program both write and read that pool in
place through the tables (``ops/latent_attention``), no gathered view ever
exists, and the block tables and their allocation are the K/V pool's.
Recurrent state is not paged: it has one fixed size a slot.
A prompt's chunk reads its slot's row as the positions before its first
token (zeros when that token is position 0: the reset on slot reuse is that
test, on the traced offset) and writes back the inputs at its last REAL
positions; a decode step shifts one input into every ACTIVE slot's row and
hands an inactive slot's row (free, or between the chunks of its prompt)
back untouched.

Programs, each compiled once per static shape and donating the pool:

- **chunk prefill** — one prompt chunk (static padded length, traced
  offset) for ONE sequence through the model's cached-attend path over the
  gathered (dequantized) view; the whole table scatters back
  quantize-on-write. Chunking is what lets a long prompt interleave with
  the running decode wave.
- **paged decode** — one token per active slot. Two backends, selected by
  ``serving.decode_kernel`` (``auto``: fused wherever the kernel can run —
  TPU or interpret mode — else gather):

  * ``fused`` — the model's attention runs the Pallas paged kernel
    (ops/paged_attention.py) that indexes the pool IN PLACE through the
    per-slot block tables (scalar-prefetch DMA per block, int8 dequant
    in-kernel); the only pool write is the one token row's scatter. No
    gather → contiguous view → scatter-back round trip.
  * ``gather`` — the historical XLA path (block-table gather → the
    unchanged cached-attend → single-token scatter-back), kept as the
    fallback where the kernel cannot run (the CPU).

- **draft propose / verify** — speculative decoding (Leviathan et al.
  2023): the draft model proposes ``spec_k`` tokens per slot (``spec_k``
  cheap decode steps over its OWN parallel pool, sharing the target's
  block tables so rollback is shared bookkeeping, and one more forward
  that only writes the last draft's K/V), then ONE batched
  verify forward pushes ``[cur, d_1..d_k]`` through the target —
  a chunk-shaped cached attend at per-slot offsets — and the rejection
  rule (generation.sampling.speculative_verify) commits the accepted
  prefix + one correction/bonus token. Rollback is a LENGTH DECREMENT:
  K/V of rejected tokens stays in the pool but sits past the committed
  length, which every attend masks out and the next round overwrites —
  no copies, no block churn.

View-position invariant (full layout only): slot j of a sequence's
view/table holds absolute position j, so admission sizes tables with
enough headroom for ``max(prefill_chunk, spec_k + 1)`` writes past
``max_seq_len`` (ServeConfig.table_blocks).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from automodel_tpu.generation import kv_cache
from automodel_tpu.generation.sampling import (
    SamplingConfig,
    sample,
    sample_with_logprobs,
    speculative_verify,
)
from automodel_tpu.ops.paged_attention import dequantize_kv, quantize_kv_rows


def _logits_of(primary: Any) -> jnp.ndarray:
    return primary[0] if isinstance(primary, tuple) else primary


def _expert_counts_of(primary: Any) -> Optional[jnp.ndarray]:
    """Rows each expert was routed, ``[L_moe, E]``, of a model whose aux
    states them (``MoEModelAux.expert_counts``), else None."""
    return getattr(primary[1], "expert_counts", None) if isinstance(primary, tuple) else None


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedKV:
    """The HBM pool, one array a KIND of layer state. ``k``/``v`` cover the
    layers that keep K/V (``L`` = their count, not the model's depth): each
    either a raw array ``[L, NB, BS, N_kv, H]`` or, when quantized, a
    ``(values int8, scales fp32 [L, NB, BS, N_kv])`` pair — the same pytree
    shape the model's layer scan slices per layer. ``state`` covers the
    layers that keep a short conv's last inputs: ``[L_conv, slots, taps -
    1, C]``, one row a slot, or None for a K/V-only layout. A LATENT pool
    (every paged layer keeps one shared row a token) has one side: ``k``
    ``[L, NB, BS, W]`` and ``v`` None."""

    k: Any
    v: Any
    state: Any = None

    @property
    def quantized(self) -> bool:
        return isinstance(self.k, tuple)

    @property
    def latent(self) -> bool:
        return self.v is None

    @property
    def values_shape(self) -> tuple:
        return (self.k[0] if self.quantized else self.k).shape

    @property
    def nbytes(self) -> int:
        return int(sum(x.nbytes for x in jax.tree.leaves((self.k, self.v, self.state))))


def init_pool(
    num_layers: int,
    num_blocks: int,
    block_size: int,
    num_kv_heads: int,
    head_dim: int,
    dtype=jnp.bfloat16,
    quantized: bool = False,
    state_shape: Optional[tuple] = None,
) -> PagedKV:
    """Zeroed pool over ``num_layers`` K/V layers; ``quantized`` stores int8
    values + fp32 row scales. ``state_shape`` ``(L_conv, slots, taps - 1,
    C)``: the recurrent layers' slot-indexed state, in ``dtype``."""
    shape = (num_layers, num_blocks, block_size, num_kv_heads, head_dim)
    state = None if state_shape is None else jnp.zeros(state_shape, dtype)
    if quantized:
        sshape = shape[:-1]

        def side():
            return (jnp.zeros(shape, jnp.int8), jnp.zeros(sshape, jnp.float32))

        return PagedKV(k=side(), v=side(), state=state)
    return PagedKV(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype), state=state)


def layout_pool(
    layout, slots: int, num_blocks: int, block_size: int, dtype=jnp.bfloat16,
    quantized: bool = False, mesh_ctx=None,
) -> PagedKV:
    """The pool a model's ``cache_layout()`` asks for: K/V blocks for its
    ``kv`` layers only, a state row a slot for its ``conv`` layers.
    ``mesh_ctx``: the mesh ``place_pool`` will shard it over."""
    kv = kv_cache.one_geometry(layout, "kv")
    conv = kv_cache.one_geometry(layout, "conv")
    latent = kv_cache.one_geometry(layout, "latent")
    if latent is not None:
        if kv is not None or conv is not None or quantized:
            raise NotImplementedError(
                "a latent pool holds latent rows alone, in the model's compute "
                "dtype: no K/V or conv layer beside them, no int8"
            )
        shape = (
            len(kv_cache.layers_of(layout, "latent")), num_blocks, block_size,
            kv_cache.lane_padded(latent.head_dim),
        )
        return PagedKV(k=jnp.zeros(shape, dtype), v=None)
    if kv is None:
        raise NotImplementedError("cache layout without a K/V or latent layer")
    return init_pool(
        len(kv_cache.layers_of(layout, "kv")), num_blocks, block_size,
        # heads narrower than a lane row are packed side by side
        *kv_cache.packed_heads(kv.heads, kv.head_dim, quantized, mesh_ctx),
        dtype=dtype, quantized=quantized,
        state_shape=None if conv is None else (
            len(kv_cache.layers_of(layout, "conv")), int(slots),
            conv.taps - 1, conv.channels,
        ),
    )


def pool_shardings(
    mesh_ctx, num_kv_heads: int, quantized: bool, with_state: bool = False
) -> PagedKV:
    """Where the pool lives: KV heads over the tensor axes (each TP shard
    owns its heads' blocks — the same no-cache-collective decode layout as
    generation.kv_cache.place_cache); blocks are NOT batch-sharded (every
    sequence's table may point anywhere in the pool). Non-divisible axes
    are dropped (replicated). Int8 scales shard on the same kv-head axis. A
    recurrent state (``with_state``), small and read whole by every shard,
    is replicated. → a PagedKV of NamedShardings, leaf for leaf the pool's
    shape."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    names = kv_cache.usable_axes(mesh_ctx, num_kv_heads, "tensor")
    val_s = NamedSharding(mesh_ctx.mesh, P(None, None, None, names, None))
    scale_s = NamedSharding(mesh_ctx.mesh, P(None, None, None, names))
    side = (val_s, scale_s) if quantized else val_s
    state = NamedSharding(mesh_ctx.mesh, P()) if with_state else None
    return PagedKV(k=side, v=side, state=state)


def place_pool(pool: PagedKV, mesh_ctx) -> PagedKV:
    """Shard the pool onto the mesh as ``pool_shardings`` says."""
    if mesh_ctx is None:
        return pool
    if pool.latent:  # one row for every head: each shard reads it whole
        return jax.device_put(pool, mesh_ctx.replicated())
    return jax.device_put(
        pool,
        pool_shardings(
            mesh_ctx, pool.values_shape[3], pool.quantized, pool.state is not None
        ),
    )


# -- gather / scatter (the XLA fallback path + chunk prefill) ----------------


def _block_rows(pool: jnp.ndarray, tables: jnp.ndarray) -> tuple:
    """A pool of several layers ``[L, NB, BS, ...]`` as rows of whole blocks
    ``[L * NB, BS * N_kv, H]`` (the same bytes in the same order: no copy),
    and the rows ``tables`` [...] names in every layer, ``[L, *tables.shape]``.
    A gather or scatter along axis 1 of the 5-D pool makes the compiler
    relayout the WHOLE pool around the program (two copies a side, 10 ms a
    chunk program at 3 x 0.8 GB) once a layer's second-minor dim is under 8
    rows (4 packed KV heads); whole-block rows index the leading axis and
    keep the layout the array arrived in."""
    L, NB, BS, Nkv = pool.shape[:4]  # values [L, NB, BS, N_kv, H]; int8 scales have no H
    rows = pool.reshape(L * NB, BS * Nkv, *pool.shape[4:])
    first = (jnp.arange(L, dtype=tables.dtype) * NB).reshape(L, *(1,) * tables.ndim)
    return rows, first + tables[None]


def _take_blocks(pool: jnp.ndarray, tables: jnp.ndarray) -> jnp.ndarray:
    """``pool[:, tables]``: [L, NB, BS, ...] -> [L, *tables.shape, BS, ...]."""
    rows, at = _block_rows(pool, tables)
    return rows[at].reshape(pool.shape[0], *tables.shape, *pool.shape[2:])


def _set_blocks(pool: jnp.ndarray, table: jnp.ndarray, new: jnp.ndarray) -> jnp.ndarray:
    """``pool[:, table] = new`` for ``pool`` [L, NB, ...], ``new`` [L, n, ...]."""
    rows, at = _block_rows(pool, table)
    return rows.at[at].set(new.reshape(*at.shape, *rows.shape[1:])).reshape(pool.shape)


def _gather_side(side, tables: jnp.ndarray, dtype) -> jnp.ndarray:
    """One pool side + tables [B, NBseq] → contiguous view
    [L, B, Cv, Nkv, H] in ``dtype`` (int8 blocks dequantize here)."""
    if isinstance(side, tuple):
        vals, scales = side
        L, _, BS, Nkv, H = vals.shape
        B, NBseq = tables.shape
        g = dequantize_kv(_take_blocks(vals, tables), _take_blocks(scales, tables), dtype)
        return g.reshape(L, B, NBseq * BS, Nkv, H)
    L, _, BS, Nkv, H = side.shape
    B, NBseq = tables.shape
    return _take_blocks(side, tables).reshape(L, B, NBseq * BS, Nkv, H)


def _scatter_rows(side, rows: jnp.ndarray, blk: jnp.ndarray, off: jnp.ndarray):
    """Scatter written token rows [L, B, S, Nkv, H] back into one pool side
    at (blk, off) [B, S] — quantize-on-write when the side is int8."""
    with jax.named_scope("kv_write"):
        if isinstance(side, tuple):
            vals, scales = side
            q, s = quantize_kv_rows(rows)
            return (vals.at[:, blk, off].set(q), scales.at[:, blk, off].set(s))
        return side.at[:, blk, off].set(rows.astype(side.dtype))


def _scatter_table(side, new: jnp.ndarray, table: jnp.ndarray):
    """Scatter a whole single-sequence view [L, NBseq, BS, Nkv, H] back
    (chunk prefill): fresh blocks carry the chunk's new K/V; shared prefix
    blocks rewrite their own bytes (quantize∘dequantize is idempotent, so
    int8 prefix blocks are bit-identical); padded table entries write to
    scratch block 0."""
    with jax.named_scope("kv_write"):
        if isinstance(side, tuple):
            vals, scales = side
            q, s = quantize_kv_rows(new)
            return (_set_blocks(vals, table, q), _set_blocks(scales, table, s))
        return _set_blocks(side, table, new.astype(side.dtype))


# gather scatter-back targets resolve through the SAME helper the fused
# path's paged_ctx uses — the two backends can never write to different cells
_write_targets = kv_cache.paged_write_targets


# -- KV extraction / injection (disaggregated prefill→decode handoff) --------


def _refuse_latent(pool: PagedKV, what: str) -> None:
    if pool.latent:
        raise NotImplementedError(
            f"{what}: block rows of a latent pool (one side, no V) are not "
            "shipped yet; spill, hand-off and peer fetch are refused for a "
            "latent layout (ServeConfig.check_layout)"
        )


def extract_blocks(pool: PagedKV, blocks) -> tuple:
    """Pull one request's block rows out of the pool to host memory —
    ``(k, v)``, each ``[L, nb, BS, Nkv, H]`` (or ``(int8 values, fp32
    scales)`` pairs for quantized pools). The prefill replica ships exactly
    these bytes; positions past the prompt inside the last block are junk
    the receiver's attend masks out (and the first decode write overwrites
    the next row before it is ever attended)."""
    import numpy as np

    _refuse_latent(pool, "extract_blocks")
    idx = jnp.asarray(np.asarray(blocks, np.int32))

    def side(s):
        if isinstance(s, tuple):
            return (
                np.asarray(jax.device_get(s[0][:, idx])),
                np.asarray(jax.device_get(s[1][:, idx])),
            )
        return np.asarray(jax.device_get(s[:, idx]))

    return side(pool.k), side(pool.v)


@functools.lru_cache(maxsize=32)
def _inject_fn(nb: int, quantized: bool):
    """Jitted whole-block scatter for a KV handoff — donated pool, one
    compiled program per (block count, quantization). Block counts follow
    prompt lengths, so a production front should bucket prompts to bound
    compile churn (docs/serving.md); the handoff itself is correct at any
    nb."""

    @functools.partial(jax.jit, donate_argnums=(0,))
    def inject(pool: PagedKV, table, k_rows, v_rows):
        def side(s, rows):
            if isinstance(s, tuple):
                return (
                    s[0].at[:, table].set(jnp.asarray(rows[0], s[0].dtype)),
                    s[1].at[:, table].set(jnp.asarray(rows[1], s[1].dtype)),
                )
            return s.at[:, table].set(jnp.asarray(rows, s.dtype))

        return PagedKV(
            k=side(pool.k, k_rows), v=side(pool.v, v_rows), state=pool.state
        )

    return inject


def inject_blocks(pool: PagedKV, blocks, kv: dict) -> PagedKV:
    """Scatter shipped block rows ``kv = {"k": rows, "v": rows}`` into the
    pool cells named by ``blocks`` — the receiving half of the prefill→
    decode handoff. Int8 payloads land their (values, scales) pairs as-is
    (no requantization: the round trip is bit-identical by construction);
    the scatter rides the same ``.at[:, table]`` cell addressing as chunk
    prefill's scatter-back, so sender and receiver land rows in the same
    cells for the same table."""
    import numpy as np

    _refuse_latent(pool, "inject_blocks")
    table = jnp.asarray(np.asarray(blocks, np.int32))
    fn = _inject_fn(int(table.shape[0]), pool.quantized)
    return fn(pool, table, kv["k"], kv["v"])


# -- host-side KV payload surgery (spill tier + /kv_fetch) --------------------
# Numpy-only helpers over the ``{"k": rows|(values, scales), "v": ...}``
# payload shape ``extract_blocks``/``inject_blocks`` speak: the host spill
# tier parks ONE block per chain hash, and reload/peer-fetch re-assembles a
# consecutive run back into one inject — so payloads need slicing and
# concatenation along the block axis (axis 1) without touching a device.


def split_kv_blocks(kv: dict) -> list[dict]:
    """One payload per block: ``[L, nb, ...]`` arrays → nb ``[L, 1, ...]``
    payloads (copies, so a parked block never pins the whole extract)."""
    import numpy as np

    def slice_side(s, i):
        if isinstance(s, tuple):
            return tuple(np.ascontiguousarray(a[:, i : i + 1]) for a in s)
        return np.ascontiguousarray(s[:, i : i + 1])

    first = kv["k"][0] if isinstance(kv["k"], tuple) else kv["k"]
    return [
        {"k": slice_side(kv["k"], i), "v": slice_side(kv["v"], i)}
        for i in range(int(first.shape[1]))
    ]


def concat_kv_blocks(payloads: list[dict]) -> dict:
    """Inverse of :func:`split_kv_blocks`: re-assemble consecutive
    single-block payloads into one ``[L, nb, ...]`` inject payload."""
    import numpy as np

    if not payloads:
        raise ValueError("concat_kv_blocks: empty payload list")

    def cat_side(name):
        first = payloads[0][name]
        if isinstance(first, tuple):
            return tuple(
                np.concatenate([p[name][j] for p in payloads], axis=1)
                for j in range(len(first))
            )
        return np.concatenate([p[name] for p in payloads], axis=1)

    return {"k": cat_side("k"), "v": cat_side("v")}


def bucket_blocks(n: int) -> int:
    """Next power of two ≥ n — the block-count buckets the spill/reload
    paths pad to. Extract/inject compile one XLA program per distinct
    block count; eviction batches and reload runs have arbitrary sizes, so
    unbucketed calls would compile (and on a busy host, stall TTFT) per
    novel size. Buckets bound the program count to log2(pool)."""
    if n < 1:
        raise ValueError(f"bucket_blocks({n})")
    b = 1
    while b < n:
        b <<= 1
    return b


def pad_kv_blocks(kv: dict, nb: int) -> dict:
    """Pad a payload out to ``nb`` blocks by repeating its last block row.
    The caller aims the padding rows at the scratch block (id 0), whose
    contents are junk by contract — so a bucketed inject is bit-identical
    to an exact one everywhere that is ever attended."""
    import numpy as np

    def pad_side(s):
        if isinstance(s, tuple):
            return tuple(pad_side(a) for a in s)
        short = nb - int(s.shape[1])
        if short <= 0:
            return s
        reps = np.repeat(s[:, -1:], short, axis=1)
        return np.concatenate([s, reps], axis=1)

    return {"k": pad_side(kv["k"]), "v": pad_side(kv["v"])}


def kv_nbytes(kv: dict) -> int:
    """Host bytes a payload occupies (scales included) — the spill tier's
    budget currency."""
    total = 0
    for side in (kv["k"], kv["v"]):
        for arr in side if isinstance(side, tuple) else (side,):
            total += int(arr.nbytes)
    return total


# -- forward cores -----------------------------------------------------------


def _gather_forward(
    apply: Callable, params, pool: PagedKV, tables, lengths, tokens, active,
    compute_dtype, block_size: int,
):
    """tokens [B, S] at per-slot offsets through the GATHERED view (chunk
    cached-attend), scattering the S written rows back. → (logits [B,S,V]
    fp32, new pool, expert counts [L_moe, E] | None). S = 1 is the classic
    paged decode step."""
    B, S = tokens.shape
    NBseq = tables.shape[1]
    BS = pool.values_shape[2]
    lengths = lengths.astype(jnp.int32)
    with jax.named_scope("attn"):  # the view attention reads, its plan, positions
        view = kv_cache.KVCache(
            k=_gather_side(pool.k, tables, compute_dtype),
            v=_gather_side(pool.v, tables, compute_dtype),
            pos=jnp.full((B, NBseq * BS), -1, jnp.int32),  # chunk_ctx retags
            lengths=jnp.zeros((B,), jnp.int32),
            state=pool.state,
        )
        fed = jnp.where(active, S, 0).astype(jnp.int32)
        kvc, ctx = kv_cache.chunk_ctx(view, S, lengths, fed)
        if pool.state is not None:  # batch row b is slot b
            ctx = kv_cache.with_state_plan(ctx, lengths, fed)
        positions = lengths[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    primary, new_view = apply(
        params, tokens, position_ids=positions, cache=(kvc, ctx)
    )
    with jax.named_scope("lm_head"):
        logits = _logits_of(primary).astype(jnp.float32)
    with jax.named_scope("kv_write"):
        b_idx = jnp.arange(B)
        rows_k = new_view.k[:, b_idx[:, None], positions]  # [L, B, S, Nkv, H]
        rows_v = new_view.v[:, b_idx[:, None], positions]
        blk, off = _write_targets(tables, lengths, S, active, block_size)
    return logits, PagedKV(
        k=_scatter_rows(pool.k, rows_k, blk, off),
        v=_scatter_rows(pool.v, rows_v, blk, off),
        state=new_view.state,
    ), _expert_counts_of(primary)


def _fused_forward(
    apply: Callable, params, pool: PagedKV, tables, lengths, tokens, active,
    block_size: int, interpret: bool, gather: bool = False,
):
    """tokens [B, S] through the paged-mode cache: per-layer writes scatter
    the S rows straight into the pool slices (quantize-on-write) and
    attention runs the fused Pallas kernel over the pool via the tables —
    no view is ever materialized. → (logits [B,S,V] fp32, new pool, expert
    counts [L_moe, E] | None). A LATENT pool always comes this way, its
    prompts' chunks too (``S`` = the chunk); ``gather`` then has its decode
    attention gather the tables' rows in XLA instead of running the kernel."""
    B, S = tokens.shape
    lengths = lengths.astype(jnp.int32)
    kvc = kv_cache.KVCache(
        k=pool.k, v=pool.v,
        pos=jnp.zeros((B, 1), jnp.int32), lengths=lengths, state=pool.state,
    )
    with jax.named_scope("kv_write"):  # the write targets
        kvc, ctx = kv_cache.paged_ctx(
            kvc, tables, lengths, S, active, block_size, interpret=interpret
        )
        ctx = dataclasses.replace(ctx, paged_gather=bool(gather))
    if pool.state is not None:  # batch row b is slot b; inactive slots feed nothing
        ctx = kv_cache.with_state_plan(
            ctx, lengths, jnp.where(active, S, 0).astype(jnp.int32)
        )
    with jax.named_scope("attn"):
        positions = lengths[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    primary, new_kvc = apply(
        params, tokens, position_ids=positions, cache=(kvc, ctx)
    )
    with jax.named_scope("lm_head"):
        logits = _logits_of(primary).astype(jnp.float32)
    return (
        logits, PagedKV(k=new_kvc.k, v=new_kvc.v, state=new_kvc.state),
        _expert_counts_of(primary),
    )


def _make_forward(
    apply: Callable, backend: str, block_size: int, compute_dtype,
    interpret: bool,
) -> Callable:
    fused = functools.partial(
        _fused_forward, apply, block_size=block_size, interpret=interpret
    )
    if backend == "fused":
        return fused
    gathered = functools.partial(
        _gather_forward, apply,
        compute_dtype=compute_dtype, block_size=block_size,
    )

    def forward(params, pool, *args):
        # a latent pool has no gathered VIEW: the gather is its attention's own
        if pool.latent:
            return fused(params, pool, *args, gather=True)
        return gathered(params, pool, *args)

    return forward


# -- programs ----------------------------------------------------------------


def build_chunk_prefill_fn(
    apply: Callable, chunk_len: int, compute_dtype=None, *,
    interpret: bool = False, gather: bool = False,
) -> Callable:
    """→ jitted ``chunk(params, pool, table [NBseq], chunk_ids [chunk_len],
    start, real_len[, slot])`` → ``(last_logits [V] fp32, pool)`` for ONE
    sequence. ``start`` is the absolute position of the chunk's first token
    (= the prefix-cache hit length for the first chunk); ``real_len`` the
    unpadded chunk length; ``last_logits`` the logits of token ``start +
    real_len - 1`` (the first-token sample source once the whole prompt is
    in). ``slot`` (only for a pool with recurrent state) is the state row
    the sequence owns: read as the positions before ``start`` (zeros when
    ``start == 0``), written back at the chunk's last real positions.
    Always the gathered-view path: prefill is compute-bound and one
    compiled program serves every offset. A LATENT pool has no view: its
    chunk writes its rows through the table and attends the prefix in place
    (``_fused_forward`` with one sequence of ``chunk_len`` tokens; rows past
    ``real_len`` land past the prompt's end, where no query of the chunk and
    no later one looks before a decode step has overwritten them);
    ``interpret`` / ``gather`` are that path's, as the decode program's."""

    @functools.partial(jax.jit, donate_argnums=(1,))
    def chunk(params, pool: PagedKV, table, chunk_ids, start, real_len, slot=None):
        if pool.latent:
            logits, pool, _ = _fused_forward(
                apply, params, pool, table[None, :], start[None], chunk_ids[None, :],
                jnp.ones((1,), bool), block_size=pool.values_shape[2],
                interpret=interpret, gather=gather,
            )
            return logits[0, real_len - 1], pool
        L, _, BS, Nkv, H = pool.values_shape
        NBseq = table.shape[0]
        cd = compute_dtype or (
            pool.k.dtype if not pool.quantized else jnp.bfloat16
        )
        tables = table[None, :]
        with jax.named_scope("attn"):  # the view attention reads, its plan, positions
            view = kv_cache.KVCache(
                k=_gather_side(pool.k, tables, cd),
                v=_gather_side(pool.v, tables, cd),
                pos=jnp.full((1, NBseq * BS), -1, jnp.int32),
                lengths=jnp.zeros((1,), jnp.int32),
                state=pool.state,
            )
            kvc, ctx = kv_cache.chunk_ctx(
                view, chunk_len, start[None].astype(jnp.int32),
                real_len[None].astype(jnp.int32),
            )
            if pool.state is not None:
                ctx = kv_cache.with_state_plan(
                    ctx, start[None], real_len[None], rows=slot[None]
                )
            positions = (
                start.astype(jnp.int32) + jnp.arange(chunk_len, dtype=jnp.int32)
            )[None, :]
        primary, new_view = apply(
            params, chunk_ids[None, :], position_ids=positions, cache=(kvc, ctx)
        )
        with jax.named_scope("lm_head"):
            logits = _logits_of(primary)[0].astype(jnp.float32)  # [chunk_len, V]
            last = logits[real_len - 1]
        with jax.named_scope("kv_write"):
            newk = new_view.k.reshape(L, NBseq, BS, Nkv, H)
            newv = new_view.v.reshape(L, NBseq, BS, Nkv, H)
        return last, PagedKV(
            k=_scatter_table(pool.k, newk, table),
            v=_scatter_table(pool.v, newv, table),
            state=new_view.state,
        )

    return chunk


def build_paged_decode_fn(
    apply: Callable,
    sampling: SamplingConfig,
    pad_id: int = 0,
    *,
    backend: str = "gather",
    block_size: int = 16,
    compute_dtype=jnp.bfloat16,
    interpret: bool = False,
    with_logprobs: bool = False,
    expert_units: Optional[Callable] = None,
    token_sharding=None,
) -> Callable:
    """→ jitted ``step(params, pool, tables [B, NBseq], lengths [B], cur
    [B], active [B] bool, key, step_idx, prev_tokens [B], take_prev [B]
    bool)`` → ``(next_tokens [B], units, pool)``, or ``(next_tokens [B],
    logprobs [B] fp32, units, pool)`` when ``with_logprobs`` — the sampled
    token's log-probability under the RAW distribution (see
    ``sample_with_logprobs``), masked to 0.0 on inactive slots. A row of
    ``take_prev`` feeds ``prev_tokens[b]`` (an earlier step's
    ``next_tokens``, which may never have left the device) in place of
    ``cur[b]``: the scheduler launches a step before it has read the last
    one's tokens. ``token_sharding`` (on a mesh) pins where ``next_tokens``
    come back: what stands in for ``prev_tokens`` while no step is unread is
    placed the same, so the two are ONE compiled program. ``units`` ``[2] int32``: the (live, grid) work units of
    the fused expert forward summed over this step's expert layers, by
    ``expert_units(counts [L_moe, E])`` from the rows the step routed each
    expert (ops/fused_expert_mlp.work_units); zeros without it or for a
    model that states no counts.

    One continuous-batching decode step: every ACTIVE slot advances one
    token (its K/V written at ``(table[len // BS], len % BS)``, one input
    shifted into its row of a recurrent state); inactive slots (free, or
    mid-prefill) compute junk that is masked from the sampled output,
    scattered into scratch block 0 and kept out of the recurrent state
    (their rows come back as they were: the decode steps that run between a
    prompt's chunks must not touch what its chunks carry). Stop-token/length
    bookkeeping is the host scheduler's job — this program is stateless."""
    forward = _make_forward(apply, backend, block_size, compute_dtype, interpret)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def step(
        params, pool, tables, lengths, cur, active, key, step_idx,
        prev_tokens, take_prev,
    ):
        cur = jnp.where(take_prev, prev_tokens, cur)
        logits, pool, counts = forward(
            params, pool, tables, lengths, cur[:, None], active
        )
        units = jnp.zeros((2,), jnp.int32)
        if expert_units is not None and counts is not None:
            units = jnp.stack(expert_units(counts)).astype(jnp.int32)
        def placed(nxt):
            nxt = jnp.where(active, nxt, jnp.int32(pad_id))
            if token_sharding is None:
                return nxt
            return jax.lax.with_sharding_constraint(nxt, token_sharding)

        with jax.named_scope("sample"):
            skey = jax.random.fold_in(key, step_idx)
            if with_logprobs:
                nxt, logp = sample_with_logprobs(logits[:, -1], skey, sampling)
                logp = jnp.where(active, logp, jnp.float32(0.0))
                return placed(nxt), logp, units, pool
            nxt = sample(logits[:, -1], skey, sampling)
            return placed(nxt), units, pool

    return step


def build_draft_propose_fn(
    draft_apply: Callable,
    sampling: SamplingConfig,
    spec_k: int,
    pad_id: int = 0,
    *,
    backend: str = "gather",
    block_size: int = 16,
    compute_dtype=jnp.bfloat16,
    interpret: bool = False,
) -> Callable:
    """→ jitted ``propose(draft_params, draft_pool, tables, lengths, cur,
    active, key, step_idx)`` → ``(draft_tokens [B, k], draft_logits
    [B, k, V] fp32, draft_pool)``: ``spec_k`` sequential draft decode
    steps inside one program, each writing the draft's K/V at the shared
    block-table positions, then one forward of ``d_k`` for its K/V alone
    (a fully accepted round commits it, and the next round attends it).
    Draft keys fold ``(step, 1 + i)`` so proposal streams never collide
    with the verify correction stream."""
    forward = _make_forward(
        draft_apply, backend, block_size, compute_dtype, interpret
    )

    @functools.partial(jax.jit, donate_argnums=(1,))
    def propose(params, pool, tables, lengths, cur, active, key, step_idx):
        kstep = jax.random.fold_in(key, step_idx)
        toks, logs = [], []
        length, c = lengths.astype(jnp.int32), cur
        for i in range(spec_k):
            logits, pool, _ = forward(
                params, pool, tables, length, c[:, None], active
            )
            lg = logits[:, -1]
            with jax.named_scope("sample"):
                nxt = sample(lg, jax.random.fold_in(kstep, 1 + i), sampling)
                nxt = jnp.where(active, nxt, jnp.int32(pad_id))
            toks.append(nxt)
            logs.append(lg)
            length = length + 1
            c = nxt
        # the last draft's own K/V: a round that accepts all k commits k + 1
        # tokens, and the next round's proposals attend position len + k
        _, pool, _ = forward(params, pool, tables, length, c[:, None], active)
        return jnp.stack(toks, axis=1), jnp.stack(logs, axis=1), pool

    return propose


def build_verify_fn(
    apply: Callable,
    sampling: SamplingConfig,
    spec_k: int,
    pad_id: int = 0,
    *,
    backend: str = "gather",
    block_size: int = 16,
    compute_dtype=jnp.bfloat16,
    interpret: bool = False,
) -> Callable:
    """→ jitted ``verify(params, pool, tables, lengths, cur, drafts
    [B, k], draft_logits [B, k, V], active, key, step_idx)`` →
    ``(tokens [B, k+1], n_commit [B], pool)``: ONE batched forward over
    the fed chunk ``[cur, d_1..d_k]`` at per-slot offsets (the verify
    attend is chunk-shaped — per-query causal masks over the paged
    cache), then the rejection rule. The pool keeps the K/V of every fed
    token; rejected tails sit past the committed length the host keeps,
    masked out of all future attends — rollback without copies."""
    forward = _make_forward(apply, backend, block_size, compute_dtype, interpret)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def verify(
        params, pool, tables, lengths, cur, drafts, draft_logits, active,
        key, step_idx,
    ):
        fed = jnp.concatenate([cur[:, None], drafts], axis=1)  # [B, k+1]
        logits, pool, _ = forward(params, pool, tables, lengths, fed, active)
        with jax.named_scope("sample"):
            kstep = jax.random.fold_in(jax.random.fold_in(key, step_idx), 0)
            toks, n = speculative_verify(
                logits, draft_logits, drafts, kstep, sampling
            )
            n = jnp.where(active, n, 0).astype(jnp.int32)
            toks = jnp.where(active[:, None], toks, jnp.int32(pad_id))
        return toks, n, pool

    return verify
