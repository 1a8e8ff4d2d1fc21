"""Mesh-sharded KV cache pytree.

Layout (Pope et al. §3.2, the contiguous-cache formulation): per-layer keys
and values stacked on a leading layer axis — ``[L, B, C, N_kv, H]`` — so the
decode step threads the cache through the SAME ``lax.scan`` over stacked
layer params the training forward uses (cache slices ride the scan as
xs/ys; compile time stays constant in depth). Keys are stored POST-RoPE, so
decode never re-rotates history.

Two layouts, one code path:

- **full**: capacity = prompt + max_new_tokens; every position owns a slot.
- **ring**: homogeneous sliding-window models (mistral-style) cap capacity
  at the window — slot = position % capacity, old tokens are overwritten
  exactly when the window would mask them anyway.

Validity is governed by per-slot **position tags** (``pos [B, C]``, -1 =
empty), not by the write itself: padded-prompt junk is written (the scatter
is dense) but tagged -1, and the attention mask derives from tags —
``tag >= 0 & tag <= q_pos & (q_pos - tag < window)`` — which makes full,
ring, and mixed-window-per-layer masking one expression.

Writes are ``dynamic_update_slice``: prefill writes the whole prompt block
at offset 0 (ring: the last-C tail, rolled into slot order), decode writes
one token per slot at its own offset (vmapped dus → per-slot scatter).

Ring caveat (documented in docs/generation.md): prompts right-padded past a
slot's true length write junk into ring slots; junk is never ATTENDED (tag
-1) but, once the ring has wrapped during PREFILL (S_padded > capacity), a
pad position p evicts real position p - C that a short slot still needed —
in the worst case (len <= S_padded - C) a slot's entire in-window history.
The engine therefore rejects ragged batches whose padded prompt wraps the
ring (equal-length batches, or ragged ones fitting the window, are exact).

Cache layout: a model states, per layer, WHAT it keeps between a sequence's
tokens (``model.cache_layout()`` -> one :class:`LayerCache` a layer): per-head
K/V (``kv``), ONE latent row a token that every head shares (``latent``:
multi-head latent attention's normed compression beside its rotated shared
key, 512 + 64 wide), or the last ``taps - 1`` inputs of a short causal conv
(``conv``), a fixed-size recurrent state. The engines read that layout, never
a family name. The paged serving path (serving/) holds blocks of K/V for
``kv`` layers or of latent rows for ``latent`` layers (``PAGED_KINDS``: what
grows with the sequence) and, beside them, one state row a slot for the
others (``KVCache.state``); this contiguous cache holds K/V alone, so the
in-training GenerationEngine refuses a layout with any other kind.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class LayerCache:
    """What ONE layer keeps for a sequence. ``kv``: ``heads`` x ``head_dim``
    keys and values a token (grows with the sequence, paged). ``conv``: the
    ``taps - 1`` last inputs of a depthwise conv over ``channels`` (one fixed
    size a sequence, indexed by slot). ``latent``: ONE row of ``head_dim``
    numbers a token (``heads`` 1), shared by every query head (paged); its
    first ``rank`` numbers are the compression the values are read from."""

    kind: str  # "kv" | "latent" | "conv" (| "delta": stated, not served)
    heads: int = 0
    head_dim: int = 0
    channels: int = 0
    taps: int = 0
    rank: int = 0


def kv_layer(heads: int, head_dim: int) -> LayerCache:
    return LayerCache("kv", heads=int(heads), head_dim=int(head_dim))


def latent_layer(width: int, rank: int) -> LayerCache:
    """One row a token: ``width`` = the latent ``rank`` + the shared rotary
    key (DeepSeek-style latent attention, 512 + 64)."""
    return LayerCache("latent", heads=1, head_dim=int(width), rank=int(rank))


def conv_layer(channels: int, taps: int) -> LayerCache:
    return LayerCache("conv", channels=int(channels), taps=int(taps))


def uniform_kv_layout(cfg) -> tuple:
    """Every layer keeps per-head K/V (llama, gpt2, the qwen3_moe family)."""
    return (kv_layer(cfg.num_kv_heads, cfg.head_dim),) * int(cfg.num_layers)


def layout_of(model) -> Optional[tuple]:
    """The layout a model states, or None for a model with no decode path."""
    fn = getattr(model, "cache_layout", None)
    return tuple(fn()) if callable(fn) else None


def layers_of(layout, kind: str) -> list:
    return [c for c in layout if c.kind == kind]


# what grows with the sequence and lives in blocks: "blocks + a length" IS
# the sequence's state
PAGED_KINDS = ("kv", "latent")


def recurrent_kinds(layout, held: tuple = PAGED_KINDS) -> list[str]:
    """The kinds of fixed-size per-sequence state in a layout (neither K/V
    nor latent rows): what "state = blocks + a length" does not describe.
    ``held=("kv",)``: every kind that is not per-head K/V, which is what the
    contiguous generation cache and a speculative draft's pool do not hold."""
    return sorted({c.kind for c in layout if c.kind not in held})


def one_geometry(layout, kind: str) -> Optional[LayerCache]:
    """The one size every layer of ``kind`` has (a pool is one array a kind),
    None when the layout has no such layer."""
    found = sorted(set(layers_of(layout, kind)), key=repr)
    if len(found) > 1:
        raise NotImplementedError(
            f"cache layout: {kind} layers of different sizes in one pool: {found}"
        )
    return found[0] if found else None


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    """The cache pytree. ``window`` is static metadata (ring layout when it
    equals the capacity and the model's layers are homogeneously windowed);
    everything else is arrays so the whole object jits/shards cleanly."""

    k: jnp.ndarray  # [L, B, C, N_kv, H], post-RoPE
    v: jnp.ndarray  # [L, B, C, N_kv, H]
    pos: jnp.ndarray  # [B, C] int32 position tags; -1 = empty slot
    lengths: jnp.ndarray  # [B] int32 tokens committed per slot
    window: Optional[int] = dataclasses.field(
        default=None, metadata={"static": True}
    )
    # serving/ only: the recurrent layers' state, one row a slot —
    # [L_state, slots, taps - 1, channels] for a layout with ``conv`` layers
    # (then k/v cover the ``kv`` layers alone); None for a K/V-only layout
    state: Any = None

    @property
    def capacity(self) -> int:
        return self.k.shape[2]

    @property
    def num_layers(self) -> int:
        return self.k.shape[0]

    @property
    def batch(self) -> int:
        return self.k.shape[1]

    @property
    def nbytes(self) -> int:
        """Global logical cache footprint (telemetry census semantics)."""
        return int(self.k.nbytes + self.v.nbytes + self.pos.nbytes + self.lengths.nbytes)

    def replace(self, **kw) -> "KVCache":
        return dataclasses.replace(self, **kw)


def init_cache(
    num_layers: int,
    batch: int,
    capacity: int,
    num_kv_heads: int,
    head_dim: int,
    dtype=jnp.bfloat16,
    window: Optional[int] = None,
) -> KVCache:
    """Empty cache. ``window`` (homogeneous sliding-window models) caps the
    useful capacity — callers pass ``capacity=min(window, total_len)`` to get
    the ring layout; a larger capacity still works, it just wastes HBM."""
    shape = (num_layers, batch, capacity, num_kv_heads, head_dim)
    return KVCache(
        k=jnp.zeros(shape, dtype),
        v=jnp.zeros(shape, dtype),
        pos=jnp.full((batch, capacity), -1, jnp.int32),
        lengths=jnp.zeros((batch,), jnp.int32),
        window=window,
    )


def usable_axes(mesh_ctx, dim: int, logical: str):
    """The mesh axes a LOGICAL axis resolves to, IF their product divides
    ``dim`` — else None (replicate). The shared drop-to-replicated rule for
    placing inference caches/pools: tiny eval batches or non-dividing KV
    heads on big meshes must degrade, not crash."""
    import numpy as np

    axes = mesh_ctx.resolve((logical,))
    names = axes[0] if len(axes) else None
    if names is None:
        return None
    names = names if isinstance(names, tuple) else (names,)
    deg = int(np.prod([mesh_ctx.mesh.shape[a] for a in names]))
    return names if deg > 0 and dim % deg == 0 else None


def place_cache(cache: KVCache, mesh_ctx) -> KVCache:
    """Shard a host-built cache onto the mesh: batch over the data axes,
    KV heads over tensor — the Pope et al. decode layout where each TP
    shard holds its own heads' cache and no cache collective ever runs.
    Axes that don't divide the cache dims are dropped (replicated) — tiny
    eval batches on big meshes must not crash generation."""
    if mesh_ctx is None:
        return cache

    b_ax = usable_axes(mesh_ctx, cache.batch, "batch")
    t_ax = usable_axes(mesh_ctx, cache.k.shape[3], "tensor")
    from jax.sharding import NamedSharding, PartitionSpec as P

    kv_s = NamedSharding(mesh_ctx.mesh, P(None, b_ax, None, t_ax, None))
    host_s = NamedSharding(mesh_ctx.mesh, P(None, None))
    return cache.replace(
        k=jax.device_put(cache.k, kv_s),
        v=jax.device_put(cache.v, kv_s),
        pos=jax.device_put(cache.pos, host_s),
        lengths=jax.device_put(cache.lengths, NamedSharding(mesh_ctx.mesh, P(None))),
    )


@dataclasses.dataclass
class CacheContext:
    """Per-forward write/attend plan, derived ONCE per model call and closed
    over by the layer scan (only the k/v slices ride the scan as xs/ys —
    tags and positions are shared by every layer).

    ``mode``: 'prefill' (attend normally over the incoming block, write it),
    'decode' (write one token per slot, attend the query over the cache),
    'chunk' (serving/: write a prompt CHUNK at each slot's own offset and
    attend the chunk's queries over the whole cache under per-query tag
    masks — the chunked-prefill path that lets a long prompt interleave
    with a running decode wave instead of stalling it), or 'paged'
    (serving/: the cache IS the block pool ``[NB, BS, Nkv, H]`` per layer —
    no gathered view exists; writes scatter token rows through the per-slot
    block tables and attention runs the fused Pallas paged kernel
    (ops/paged_attention.py) that indexes the pool in place, dequantizing
    int8 blocks on the fly).
    """

    mode: str  # "prefill" | "decode" | "chunk" | "paged"
    capacity: int
    q_pos: jnp.ndarray  # [B] decode query position / [B] prompt lengths
    pos: jnp.ndarray  # [B, C] tags AFTER this call's write
    slots: Optional[jnp.ndarray] = None  # [B] decode write slot
    prompt_len: int = 0  # static padded prompt/chunk length (prefill/chunk)
    start: Optional[jnp.ndarray] = None  # [B] chunk write offset (absolute)
    # paged mode only: per-slot block tables + precomputed write targets
    # (inactive slots already routed to scratch block 0 by paged_ctx)
    tables: Optional[jnp.ndarray] = None  # [B, NBseq] int32
    write_block: Optional[jnp.ndarray] = None  # [B, S] int32
    write_off: Optional[jnp.ndarray] = None  # [B, S] int32
    paged_interpret: bool = False  # run the Pallas kernel interpreted (CPU)
    # a latent pool's decode attention through an XLA gather of the tables'
    # rows instead of the kernel (``serving.decode_kernel: gather``)
    paged_gather: bool = False
    # a stack that is NOT scanned (layers of several kinds, models/lfm2_moe)
    # hands ``write``/``attend`` the WHOLE stacked sides and names the K/V
    # layer here (``at_layer``): the paged write scatters into the stacked
    # pool in place and the kernel indexes the layer through its block
    # spec, so no per-layer slice of the pool is ever copied out
    layer: Optional[int] = None
    # recurrent state plan (``with_state_plan``; serving/ only): the state
    # rows these sequences own (None: row b is batch row b), the absolute
    # position of the first token fed (0: the state is reset, not read) and
    # the count of REAL tokens fed (0: the state is left as it is)
    state_rows: Optional[jnp.ndarray] = None  # [B] int32
    state_start: Optional[jnp.ndarray] = None  # [B] int32
    state_len: Optional[jnp.ndarray] = None  # [B] int32

    def at_layer(self, layer: int) -> "CacheContext":
        return dataclasses.replace(self, layer=int(layer))

    # -- recurrent (conv) state ------------------------------------------------
    def conv_prev(self, layer_state: jnp.ndarray) -> jnp.ndarray:
        """One conv layer's state ``[rows, taps - 1, C]`` -> the inputs at
        the ``taps - 1`` positions before this call's first token, ``[B,
        taps - 1, C]``: the slot's state, or zeros for a sequence that
        starts at position 0. That IS the reset on slot reuse: whatever the
        previous tenant left in the row is never read."""
        if self.state_start is None:
            raise NotImplementedError(
                f"cache mode {self.mode!r} carries no recurrent state: a "
                "conv layer decodes through the paged serving path only"
            )
        held = layer_state if self.state_rows is None else layer_state[self.state_rows]
        return jnp.where((self.state_start > 0)[:, None, None], held, 0)

    def conv_next(self, prev: jnp.ndarray, u: jnp.ndarray) -> jnp.ndarray:
        """The state after this call: the last ``taps - 1`` inputs up to the
        last REAL token (``state_len``, not the padded end). ``prev`` [B,
        taps - 1, C] from ``conv_prev``, ``u`` [B, S, C] this call's inputs.
        A sequence that fed nothing (inactive in a decode wave, e.g. between
        the chunks of its prompt) gets ``prev`` back."""
        seq = jnp.concatenate([prev, u.astype(prev.dtype)], axis=1)
        take = lambda s, n: jax.lax.dynamic_slice_in_dim(s, n, prev.shape[1], axis=0)
        return jax.vmap(take)(seq, self.state_len)

    def write_state(self, state: jnp.ndarray, new: list) -> jnp.ndarray:
        """All state layers at once: ``new``, one ``[B, taps - 1, C]`` a
        layer (``conv_next``), into ``state`` [L_state, rows, taps - 1, C] at
        this call's rows (every row when batch row b is slot b)."""
        with jax.named_scope("state_write"):
            new = jnp.stack(new).astype(state.dtype)
            if self.state_rows is None:
                return new
            return state.at[:, self.state_rows].set(new)

    @property
    def decode(self) -> bool:
        return self.mode == "decode"

    @property
    def attends_cache(self) -> bool:
        """True when the attention path must attend over the CACHE under the
        position-tag mask (decode, chunked prefill, paged decode/verify)
        instead of over the incoming block (ordinary whole-prompt
        prefill)."""
        return self.mode in ("decode", "chunk", "paged")

    # -- writes --------------------------------------------------------------
    def write(
        self, ck: jnp.ndarray, cv: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Write this layer's new keys/values. ck/cv: [B, C, N_kv, H];
        k/v: [B, S, N_kv, H] (S = prompt length in prefill, chunk length in
        chunk mode, 1 in decode). Paged mode: ck/cv are the layer's POOL
        slice — ``[NB, BS, N_kv, H]``, or ``(int8 values, fp32 scales)``
        when the pool is quantized — and the write scatters the S token
        rows through the block table (quantize-on-write for int8). With
        ``layer`` set (``at_layer``) ck/cv are the whole STACKED sides and
        come back whole."""
        k, v = pack_rows(k, ck), pack_rows(v, cv)  # a lane-packed pool's rows
        if self.mode == "paged":
            return (
                _paged_scatter(ck, k, self.write_block, self.write_off, self.layer),
                _paged_scatter(cv, v, self.write_block, self.write_off, self.layer),
            )
        if self.layer is not None:
            # a gathered view (chunk mode): one sequence's blocks, small
            nk, nv = dataclasses.replace(self, layer=None).write(
                layer_slice(ck, self.layer), layer_slice(cv, self.layer), k, v
            )
            put = lambda side, new: side.at[self.layer].set(new)
            return jax.tree.map(put, ck, nk), jax.tree.map(put, cv, nv)
        if self.mode == "chunk":
            # per-slot chunk write at the slot's own absolute offset (full
            # layout only: position == slot). dynamic_update_slice takes
            # traced starts, so one compiled program serves every offset.
            write = jax.vmap(
                lambda cb, nb, s: jax.lax.dynamic_update_slice(cb, nb, (s, 0, 0))
            )
            return (
                write(ck, k.astype(ck.dtype), self.start),
                write(cv, v.astype(cv.dtype), self.start),
            )
        if self.mode == "prefill":
            S, C = self.prompt_len, self.capacity
            if S <= C:
                ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, 0, 0, 0))
                cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, 0, 0, 0))
            else:
                # ring: only the last C positions survive; position p lands
                # in slot p % C, which for the contiguous tail [S-C, S) is a
                # roll — a dense overwrite, no scatter
                shift = (S - C) % C
                ck = jnp.roll(k[:, S - C :].astype(ck.dtype), shift, axis=1)
                cv = jnp.roll(v[:, S - C :].astype(cv.dtype), shift, axis=1)
            return ck, cv
        # decode: one token per slot at its own offset
        write = jax.vmap(
            lambda cb, nb, s: jax.lax.dynamic_update_slice(cb, nb, (s, 0, 0))
        )
        return (
            write(ck, k.astype(ck.dtype), self.slots),
            write(cv, v.astype(cv.dtype), self.slots),
        )

    def write_latent(self, pool: jnp.ndarray, rows: jnp.ndarray) -> jnp.ndarray:
        """Latent rows ``[B, S, W]`` (the normed compression beside the
        rotated shared key) into layer ``layer`` of the stacked latent pool
        ``[L, NB, BS, W]``, in place at the paged write targets. One side:
        keys and values are both read from this row."""
        if self.mode != "paged" or self.layer is None:
            raise NotImplementedError(
                f"cache mode {self.mode!r} holds no latent rows: a latent "
                "layer decodes through the paged serving path only"
            )
        with jax.named_scope("kv_write"), jax.named_scope("latent_write"):
            at = (self.layer, self.write_block, self.write_off)
            pad = pool.shape[-1] - rows.shape[-1]  # the pool's lane padding
            rows = jnp.pad(rows.astype(pool.dtype), ((0, 0), (0, 0), (0, pad)))
            return pool.at[at].set(rows)

    # -- attend --------------------------------------------------------------
    def attend(
        self,
        q: jnp.ndarray,
        layer_kv: tuple,
        *,
        sliding_window: Optional[int] = None,
        scale: Optional[float] = None,
        logits_soft_cap: Optional[float] = None,
        mesh_ctx=None,
    ) -> jnp.ndarray:
        """Cache-attending attention for this mode — the single dispatch
        point the model attention blocks call when ``attends_cache``.
        ``layer_kv`` is the layer's just-written cache pair from ``write``.
        Decode/chunk: ``sdpa_decode`` over the (gathered) cache under the
        position-tag mask. Paged: the fused Pallas kernel indexes the block
        pool in place through the tables (ops/paged_attention.py), inside a
        shard_map when ``mesh_ctx`` (BackendConfig.mesh_ctx) spans several
        devices."""
        if self.mode == "paged":
            from automodel_tpu.ops import paged_attention as _pa

            ck, cv = layer_kv
            kq, ks = ck if isinstance(ck, tuple) else (ck, None)
            vq, vs = cv if isinstance(cv, tuple) else (cv, None)
            return _pa.paged_attend(
                q, kq, vq, self.tables, self.q_pos, ks, vs,
                scale=scale, sliding_window=sliding_window,
                logits_soft_cap=logits_soft_cap,
                interpret=self.paged_interpret, mesh_ctx=mesh_ctx,
                **({} if self.layer is None else {"layer": self.layer}),
            )
        from automodel_tpu.ops.attention import sdpa_decode

        if self.layer is not None:
            layer_kv = (layer_slice(layer_kv[0], self.layer),
                        layer_slice(layer_kv[1], self.layer))
        return sdpa_decode(
            q, unpack_heads(layer_kv[0], q.shape[-1]), unpack_heads(layer_kv[1], q.shape[-1]),
            kv_mask=self.attend_mask(sliding_window),
            scale=scale, logits_soft_cap=logits_soft_cap,
        )

    def attend_mask(self, sliding_window: Optional[int] = None) -> jnp.ndarray:
        """Valid-slot mask for cache-attending modes. Decode: ``[B, C]`` —
        which cache slots the single query may attend. Chunk: ``[B, S, C]``
        — per-QUERY validity (query s sits at absolute position start+s, so
        later chunk tokens attend earlier ones causally through the cache).
        Per-layer ``sliding_window`` (mixed full/windowed stacks) narrows
        the mask; the ring layout needs no extra handling in decode because
        eviction and window expiry coincide by construction."""
        tags = self.pos
        if self.mode == "chunk":
            q_abs = self.start[:, None] + jnp.arange(
                self.prompt_len, dtype=jnp.int32
            )[None, :]  # [B, S]
            valid = (tags >= 0)[:, None, :] & (
                tags[:, None, :] <= q_abs[:, :, None]
            )
            if sliding_window is not None:
                valid = valid & (
                    q_abs[:, :, None] - tags[:, None, :] < sliding_window
                )
            return valid
        q = self.q_pos[:, None]
        valid = (tags >= 0) & (tags <= q)
        if sliding_window is not None:
            valid = valid & (q - tags < sliding_window)
        return valid


def prefill_ctx(cache: KVCache, prompt_len: int, lengths: jnp.ndarray) -> tuple[KVCache, CacheContext]:
    """Plan the prompt write: returns the cache with tags/lengths updated
    (k/v update per layer inside the model) and the shared context."""
    C = cache.capacity
    S = int(prompt_len)
    if S <= C:
        written = jnp.arange(S, dtype=jnp.int32)  # slot j holds position j
        tags = jnp.where(
            written[None, :] < lengths[:, None], written[None, :], -1
        )
        pos = jax.lax.dynamic_update_slice(cache.pos, tags.astype(jnp.int32), (0, 0))
    else:
        # ring tail [S-C, S): slot j holds position S-C + ((j-(S-C)) % C)
        j = jnp.arange(C, dtype=jnp.int32)
        written = S - C + ((j - (S - C)) % C)
        pos = jnp.where(
            written[None, :] < lengths[:, None], written[None, :], -1
        ).astype(jnp.int32)
    new_cache = cache.replace(pos=pos, lengths=lengths.astype(jnp.int32))
    ctx = CacheContext(
        mode="prefill", capacity=C, q_pos=lengths.astype(jnp.int32),
        pos=pos, prompt_len=S,
    )
    return new_cache, ctx


def chunk_ctx(
    cache: KVCache, chunk_len: int, start: jnp.ndarray, real_len: jnp.ndarray
) -> tuple[KVCache, CacheContext]:
    """Plan a chunked-prefill call (serving/): ``chunk_len`` (static, padded)
    tokens per slot, written at absolute positions ``[start, start+real_len)``
    of a FULL-layout cache (chunking a ring layout is unsupported — the
    serving engine keeps windowed models on the full layout and lets the
    per-layer window masks narrow attention instead). Positions at or past
    ``start + real_len`` are tagged -1, so chunk padding is written but never
    attended and the next chunk overwrites it. ``start``/``real_len``: [B]
    int32 (traced — one compiled program serves every offset)."""
    C = cache.capacity
    j = jnp.arange(C, dtype=jnp.int32)
    end = (start + real_len).astype(jnp.int32)
    pos = jnp.where(j[None, :] < end[:, None], j[None, :], -1).astype(jnp.int32)
    new_cache = cache.replace(pos=pos, lengths=end)
    ctx = CacheContext(
        mode="chunk", capacity=C, q_pos=end, pos=pos,
        prompt_len=int(chunk_len), start=start.astype(jnp.int32),
    )
    return new_cache, ctx


LANES = 128  # the TPU's lane width: a narrower minor dim is padded to it


def packed_heads(
    heads: int, head_dim: int, quantized: bool = False, mesh_ctx=None
) -> tuple[int, int]:
    """The trailing ``(heads, width)`` a paged pool is ALLOCATED with. Heads
    narrower than the lane width are packed side by side into rows of 128
    (``[8, 64]`` -> ``[4, 128]``, a plain row-major reshape): a pool whose
    minor dim is 64 is laid out by the compiler with another dim minor-most,
    and the paged kernel, which needs heads minor-most, then gets a
    relayout copy of the whole pool before and after every program, on
    twice the memory. Writes pack the new rows (``pack_rows``), the
    gathered-view attention unpacks (``unpack_heads``), the paged kernel's
    wrapper pads the queries to match (ops/paged_attention.py).

    Packing never changes what the pool MEANS elsewhere: an int8 pool keeps
    one scale a (token row, kv head), which a packed row of two heads could
    not, so it is left unpacked; and on a mesh the heads are packed only if
    the packed count shards over the tensor axes exactly as the heads did."""
    pack = LANES // head_dim if head_dim < LANES and LANES % head_dim == 0 else 1
    if pack == 1 or quantized or heads % pack:
        return heads, head_dim
    if mesh_ctx is not None and usable_axes(
        mesh_ctx, heads // pack, "tensor"
    ) != usable_axes(mesh_ctx, heads, "tensor"):
        return heads, head_dim
    return heads // pack, head_dim * pack


def lane_padded(width: int) -> int:
    """The minor dim a latent pool is ALLOCATED with: ``width`` up to whole
    lane rows (576 -> 640). The chip stores the padding whatever the shape
    says, and the decode kernel copies a page out of HBM only in whole tiles;
    the padding lanes hold zeros and meet zeros in the query."""
    return -(-int(width) // LANES) * LANES


def pack_rows(new: jnp.ndarray, like) -> jnp.ndarray:
    """Token rows ``[..., N_kv, H]`` in the trailing shape of the cache side
    they are written into (its values, for an int8 pair)."""
    tail = (like[0] if isinstance(like, tuple) else like).shape[-2:]
    return new if new.shape[-2:] == tail else new.reshape(*new.shape[:-2], *tail)


def unpack_heads(side: jnp.ndarray, head_dim: int) -> jnp.ndarray:
    """A cache side ``[..., heads', width]`` back as ``[..., N_kv, H]``."""
    if side.shape[-1] == head_dim:
        return side
    return side.reshape(*side.shape[:-2], -1, head_dim)


def layer_slice(side, i: int):
    """Layer ``i`` of a cache side — a plain ``[L, ...]`` array or the
    paged-int8 ``(values, scales)`` pair (models' per-layer loop path)."""
    return jax.tree.map(lambda x: x[i], side)


def layer_range(side, start: int, stop: Optional[int] = None):
    """Layers ``[start:stop]`` of a cache side, pytree-aware (the mixed
    dense/MoE stacks scan disjoint layer ranges)."""
    return jax.tree.map(lambda x: x[start:stop], side)


def stack_layer_sides(sides: list):
    """Inverse of ``layer_slice`` over a per-layer list (pytree-aware
    ``jnp.stack``)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *sides)


def concat_layer_sides(parts: list):
    """Concatenate per-range cache sides back into one ``[L, ...]`` side
    (pytree-aware ``jnp.concatenate`` — the inverse of ``layer_range``)."""
    if len(parts) == 1:
        return parts[0]
    return jax.tree.map(lambda *xs: jnp.concatenate(xs), *parts)


def _paged_scatter(side, new, blk: jnp.ndarray, off: jnp.ndarray,
                   layer: Optional[int] = None):
    """Scatter ``new`` [B, S, Nkv, H] token rows into one layer's pool slice
    at (blk, off) [B, S] — the paged write. ``side`` is the raw pool array
    [NB, BS, Nkv, H] or, when the pool is int8, ``(values, scales)`` with
    quantize-on-write (ops/paged_attention.quantize_kv_rows). With ``layer``
    the side is the whole stacked pool ``[L, NB, BS, Nkv, H]`` and the rows
    land in that layer of it, in place."""
    at = (blk, off) if layer is None else (layer, blk, off)
    if isinstance(side, tuple):
        from automodel_tpu.ops.paged_attention import quantize_kv_rows

        vals, scales = side
        q, s = quantize_kv_rows(new)
        return (
            vals.at[at].set(q),
            scales.at[at].set(s.astype(scales.dtype)),
        )
    return side.at[at].set(new.astype(side.dtype))


def with_state_plan(
    ctx: CacheContext,
    start: jnp.ndarray,
    real_len: jnp.ndarray,
    rows: Optional[jnp.ndarray] = None,
) -> CacheContext:
    """Add the recurrent-state plan to a chunk or paged context (serving/):
    ``start`` [B] the absolute position of each sequence's first fed token,
    ``real_len`` [B] its real tokens this call (0 = not fed: inactive),
    ``rows`` [B] the state rows (None: row b is batch row b)."""
    return dataclasses.replace(
        ctx,
        state_start=start.astype(jnp.int32),
        state_len=real_len.astype(jnp.int32),
        state_rows=None if rows is None else rows.astype(jnp.int32),
    )


def paged_write_targets(
    tables: jnp.ndarray,
    lengths: jnp.ndarray,
    q_len: int,
    active: jnp.ndarray,
    block_size: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(block, offset) ``[B, S]`` for S token rows written at absolute
    positions ``lengths..lengths+S-1`` through the block tables; inactive
    slots route to scratch block 0. The ONE spelling of paged write-target
    math — both the fused path (``paged_ctx``) and the gather path's
    scatter-back (serving/paged.py) resolve targets here, so the two
    backends can never write token rows to different cells."""
    pos = lengths[:, None].astype(jnp.int32) + jnp.arange(q_len, dtype=jnp.int32)[None, :]
    idx = jnp.clip(pos // block_size, 0, tables.shape[1] - 1)
    blk = jnp.where(
        active[:, None], jnp.take_along_axis(tables, idx, axis=1), 0
    ).astype(jnp.int32)
    off = jnp.where(active[:, None], pos % block_size, 0).astype(jnp.int32)
    return blk, off


def paged_ctx(
    cache: KVCache,
    tables: jnp.ndarray,
    lengths: jnp.ndarray,
    q_len: int,
    active: jnp.ndarray,
    block_size: int,
    interpret: bool = False,
) -> tuple[KVCache, CacheContext]:
    """Plan a paged decode/verify call (serving/ fused path): ``q_len``
    tokens per slot (1 for decode, spec_k+1 for the speculative verify
    forward) written at absolute positions ``[lengths, lengths + q_len)``
    straight into the BLOCK POOL through the per-slot ``tables`` —
    ``cache.k``/``cache.v`` here are the pool arrays ``[L, NB, BS, Nkv,
    H]`` (or ``(values, scales)`` pairs when int8), not a gathered view.
    Inactive slots write to scratch block 0. Validity needs no position
    tags: the kernel masks ``pos <= lengths + qi`` directly."""
    blk, off = paged_write_targets(tables, lengths, q_len, active, block_size)
    ctx = CacheContext(
        mode="paged", capacity=cache.capacity if not isinstance(cache.k, tuple) else 0,
        q_pos=lengths.astype(jnp.int32), pos=cache.pos,
        prompt_len=int(q_len), tables=tables.astype(jnp.int32),
        write_block=blk, write_off=off, paged_interpret=bool(interpret),
    )
    return cache.replace(lengths=lengths.astype(jnp.int32) + q_len), ctx


def decode_ctx(cache: KVCache) -> tuple[KVCache, CacheContext]:
    """Plan a single-token step: the new token sits at position lengths[b],
    slot lengths[b] % C; its tag is set BEFORE attention so the token
    attends to itself."""
    C = cache.capacity
    q_pos = cache.lengths
    slots = (q_pos % C).astype(jnp.int32)
    pos = jax.vmap(lambda row, s, p: row.at[s].set(p))(cache.pos, slots, q_pos)
    new_cache = cache.replace(pos=pos, lengths=cache.lengths + 1)
    ctx = CacheContext(
        mode="decode", capacity=C, q_pos=q_pos, pos=pos, slots=slots
    )
    return new_cache, ctx
