"""Paged KV cache: a shared pool of token blocks + per-sequence block tables.

The software analogue of EPAC's distributed L2 under programmable address
interleaving: physical storage is a pool of fixed-size blocks shared by
all decode slots, and a per-sequence *block table* maps logical token
positions to physical blocks. Sequences grow block-by-block and release
blocks on retirement, so cache memory scales with ``sum(len_i)`` instead
of ``num_slots * max_len``.

Layout per full-attention layer stack (count = layers in the scan group):

    k_pool, v_pool: (count, num_blocks, block_size, n_kv_heads, head_dim)

All layers of a sequence share ONE block table (same logical->physical
map, per-layer pools), the standard paged-attention arrangement.

Physical block 0 is reserved as the *null block*: retired/empty slots
point their table entries at it, so the shape-stable decode step can
scatter their (discarded) K/V writes somewhere harmless and the kernel's
prefetch index map never sees an out-of-range id. The allocator never
hands block 0 to a live sequence.

Device-side state is a pure pytree (functional updates under jit); the
``BlockAllocator`` is host-side bookkeeping owned by the scheduler.
"""

from __future__ import annotations

import collections
import dataclasses

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib

NULL_BLOCK = 0
NULL_ARENA = 0


def blocks_for(n_tokens: int, block_size: int) -> int:
    return -(-n_tokens // block_size)


def rollback_tail(blocks: list, n_tokens: int, block_size: int) -> list:
    """Split off the blocks a sequence no longer needs after a rewind.

    The speculative verify step appends up to K+1 tokens to a slot's
    blocks and then rewinds the length pointer over the rejected tail —
    the paged cache's rollback is *just that pointer move* (rejected
    K/V stay in place, invisible past the length, overwritten in place
    when the sequence genuinely reaches those positions). What remains
    is returning surplus whole blocks: mutates ``blocks`` down to
    ``blocks_for(n_tokens)`` entries and returns the cut tail for
    ``BlockAllocator.free`` — no block contents are copied, ever.
    """
    keep = blocks_for(n_tokens, block_size)
    tail = blocks[keep:]
    del blocks[keep:]
    return tail


@dataclasses.dataclass(frozen=True)
class PagedLayout:
    """Static geometry of the paged cache (jit-static, hashable)."""

    num_slots: int           # decode batch width B
    num_blocks: int          # pool size incl. reserved null block 0
    block_size: int          # tokens per block
    max_len: int             # per-sequence position cap

    def __post_init__(self):
        assert self.num_blocks >= 2, "need >= 1 allocatable block + null"

    @property
    def max_blocks_per_seq(self) -> int:
        return blocks_for(self.max_len, self.block_size)

    @property
    def usable_blocks(self) -> int:
        return self.num_blocks - 1        # block 0 is the null block


class BlockAllocator:
    """Refcounting allocator over physical blocks 1..num_blocks-1.

    Every block is in exactly ONE of four states, and the partition is
    asserted after every transition (``check_invariant``):

    * **owned** — refcount >= 1: referenced by live slot tables. A block
      shared by N slots (prefix caching) carries refcount N; ``free``
      decrements and only the last reference releases the block.
    * **cached** LRU — refcount 0 but registered in a prefix index
      (``register``): kept resident so a future admission can re-hit it
      (``share`` revives it), reclaimed oldest-first ONLY when the free
      list runs dry (``on_evict`` tells the index to unlink it).
    * **free** — a plain FIFO: ``free`` appends to the tail, ``alloc``
      pops from the head, so a preempted victim's blocks are the LAST
      ones recycled and a resumed request can still re-hit its own
      just-evicted prefix (the old LIFO stack handed them straight to
      the preemptor, in reverse order).
    * the reserved null block 0 — never allocated, never freed.

    ``can_admit`` applies a free-block *watermark* so new sequences
    leave growth headroom, and ``select_victim`` encodes the preemption
    order (LIFO — the most recently admitted sequence is evicted first,
    so the oldest admission always runs to completion and the engine
    cannot livelock)."""

    def __init__(self, layout: PagedLayout, watermark: int = 0,
                 on_evict=None):
        self.layout = layout
        self.watermark = watermark
        self.on_evict = on_evict           # called with each reclaimed
        self._free = collections.deque(range(1, layout.num_blocks))
        self._refs: dict[int, int] = {}    # block -> live reference count
        self._cached: set[int] = set()     # registered in a prefix index
        # refcount-0 cached blocks, insertion-ordered: oldest first
        self._lru: collections.OrderedDict[int, None] = \
            collections.OrderedDict()

    @property
    def free_count(self) -> int:
        """Blocks allocatable right now (the plain free list plus the
        reclaimable cached LRU)."""
        return len(self._free) + len(self._lru)

    @property
    def used_count(self) -> int:
        """Blocks with at least one live reference."""
        return len(self._refs)

    @property
    def lru_count(self) -> int:
        """Unreferenced cached blocks awaiting re-hit or reclaim."""
        return len(self._lru)

    def refcount(self, b: int) -> int:
        return self._refs.get(b, 0)

    def can_alloc(self, n: int) -> bool:
        return n <= self.free_count

    def can_admit(self, n: int, *, strict: bool = True) -> bool:
        """Admission check for a NEW sequence needing ``n`` blocks now.

        ``strict`` keeps ``watermark`` blocks free as growth headroom for
        already-running sequences; callers pass ``strict=False`` when
        nothing else is running (the watermark must never starve a sole
        request — progress beats headroom)."""
        if not strict:
            return n <= self.free_count
        return n + self.watermark <= self.free_count

    @staticmethod
    def select_victim(candidates: list[tuple[int, int]]) -> int:
        """Pick the preemption victim from ``(slot, admission_ticket)``
        pairs: LIFO — highest ticket (latest admission) loses."""
        if not candidates:
            raise ValueError("no preemption candidates")
        return max(candidates, key=lambda c: c[1])[0]

    def alloc(self, n: int) -> list[int]:
        """Claim ``n`` exclusively-owned blocks (refcount 1 each),
        reclaiming the oldest unreferenced cached blocks only after the
        plain free list is exhausted."""
        if n > self.free_count:
            raise MemoryError(f"paged pool exhausted: want {n}, "
                              f"free {self.free_count}")
        out = []
        for _ in range(n):
            if self._free:
                b = self._free.popleft()
            else:                          # reclaim the oldest cached
                b, _ = self._lru.popitem(last=False)
                self._cached.discard(b)
                if self.on_evict is not None:
                    self.on_evict(b)
            self._refs[b] = 1
            out.append(b)
        self.check_invariant()
        return out

    def free(self, blocks: list[int]):
        """Drop one reference per block. The LAST reference releases the
        block: to the cached LRU when a prefix index registered it, else
        to the tail of the FIFO free list."""
        for b in blocks:
            if b == NULL_BLOCK:
                raise ValueError("freeing the reserved null block")
            r = self._refs.get(b, 0)
            if r <= 0:
                raise ValueError(f"double-free of block {b}")
            if r > 1:
                self._refs[b] = r - 1
            else:
                del self._refs[b]
                if b in self._cached:
                    self._lru[b] = None    # most recent at the tail
                else:
                    self._free.append(b)
        self.check_invariant()

    def share(self, b: int):
        """Take one more reference on a resident block: bump a live
        block's refcount, or revive an unreferenced cached block out of
        the LRU (a prefix-cache hit). Raises on free/unknown blocks."""
        if b in self._refs:
            self._refs[b] += 1
        elif b in self._lru:
            del self._lru[b]
            self._refs[b] = 1
        else:
            raise ValueError(f"sharing unreferenced block {b}")
        self.check_invariant()

    def register(self, b: int):
        """Mark a LIVE block as indexed by a prefix cache: when its last
        reference drops it parks in the LRU instead of the free list."""
        if b not in self._refs:
            raise ValueError(f"registering non-live block {b}")
        self._cached.add(b)

    def must_cow(self, b: int) -> bool:
        """True when an in-place write to ``b`` would be observable
        outside the writer: another slot holds a reference, or a prefix
        index could hand the block to a future admission."""
        return self._refs.get(b, 0) > 1 or b in self._cached

    def check_invariant(self):
        """owned ⊎ cached-LRU ⊎ free must partition blocks 1..N-1 (and
        the cached set may only mark resident blocks)."""
        owned, lru, free = set(self._refs), set(self._lru), set(self._free)
        if (owned & lru) or (owned & free) or (lru & free):
            raise AssertionError(
                f"allocator states overlap: owned∩lru={owned & lru} "
                f"owned∩free={owned & free} lru∩free={lru & free}")
        universe = set(range(1, self.layout.num_blocks))
        if (owned | lru | free) != universe:
            raise AssertionError(
                f"allocator lost blocks: missing "
                f"{universe - (owned | lru | free)}, "
                f"foreign {(owned | lru | free) - universe}")
        if not self._cached <= (owned | lru):
            raise AssertionError(
                f"cached marks non-resident blocks: "
                f"{self._cached - (owned | lru)}")
        if any(r < 1 for r in self._refs.values()):
            raise AssertionError("non-positive refcount")


class _PrefixNode:
    __slots__ = ("chunk", "block", "parent", "children")

    def __init__(self, chunk, block, parent):
        self.chunk = chunk
        self.block = block
        self.parent = parent              # None for root-level nodes
        self.children: dict = {}


class PrefixIndex:
    """Host-side trie mapping block-size token chunks to pool blocks.

    Each node keys one FULL block of token ids on the path from the
    sequence start and names the physical block whose K/V holds exactly
    those positions — K/V content for an attention layer depends only on
    the token ids and absolute positions of the prefix, so two requests
    sharing a prompt prefix can share the physical blocks (the serving
    analogue of EPAC's interleaved L2: one physical pool, many tiles'
    address maps pointing into it).

    The index is pure host bookkeeping and holds NO references of its
    own: the ``BlockAllocator`` keeps indexed blocks resident (cached
    LRU) and calls ``evict_block`` when it reclaims one. Insertion is
    first-wins — a chunk already indexed keeps its original block, and
    later copies of the same content stay private to their slot (they
    free normally). Evicting a node orphans its descendants: they can
    no longer be matched (matching walks from the root) and age out of
    the allocator's LRU like any other cold block.
    """

    def __init__(self, block_size: int):
        self.block_size = block_size
        self.children: dict = {}          # root: chunk tuple -> node
        self._by_block: dict[int, _PrefixNode] = {}

    def __len__(self) -> int:
        return len(self._by_block)

    def match(self, tokens) -> list[int]:
        """Physical blocks of the longest indexed chain of FULL
        block-size chunks prefixing ``tokens`` (possibly empty)."""
        bs = self.block_size
        out: list[int] = []
        kids = self.children
        for c in range(len(tokens) // bs):
            node = kids.get(tuple(tokens[c * bs:(c + 1) * bs]))
            if node is None:
                break
            out.append(node.block)
            kids = node.children
        return out

    def insert(self, tokens, blocks) -> list[int]:
        """Index ``blocks[c]`` under the c-th full chunk of ``tokens``
        (first-wins). Returns the block ids newly indexed — the caller
        must ``register`` exactly those with the allocator."""
        bs = self.block_size
        new: list[int] = []
        kids = self.children
        parent = None
        for c in range(min(len(tokens) // bs, len(blocks))):
            chunk = tuple(tokens[c * bs:(c + 1) * bs])
            node = kids.get(chunk)
            if node is None:
                node = _PrefixNode(chunk, blocks[c], parent)
                kids[chunk] = node
                self._by_block[blocks[c]] = node
                new.append(blocks[c])
            parent = node
            kids = node.children
        return new

    def evict_block(self, b: int):
        """Unlink the node indexing block ``b`` (allocator reclaim
        callback). Descendants become unmatchable orphans and are
        unlinked the same way when their blocks are reclaimed."""
        node = self._by_block.pop(b, None)
        if node is None:
            return
        kids = self.children if node.parent is None \
            else node.parent.children
        if kids.get(node.chunk) is node:
            del kids[node.chunk]


class CrossArena:
    """Refcounting allocator over cross-KV arena rows 1..num_arenas.

    Encoder-decoder requests carry STATIC per-request cross-attention
    K/V (a pure function of the encoder features, written once at
    admission by the encoder forward and read-only for the request's
    whole decode life). That state lives in a fixed *arena*: one row of
    ``(L, A+1, Hkv, enc_len, hd)`` per resident request, with row 0
    reserved as the null row (retired slots point at it; its contents
    are never read). This class is the host-side bookkeeping — the
    cross-pool analogue of ``BlockAllocator``, with the same refcount
    discipline so rows are SHAREABLE like prefix blocks: two live
    requests built from the *same* encoder-feature array (``key`` is the
    caller's identity key, e.g. ``id(features)``) share one row, because
    the encoder is deterministic and the row is write-once.

    States partition rows 1..A (asserted by ``check_invariant``):
    **owned** (refcount >= 1, keyed) ⊎ **free** (FIFO). There is no LRU
    tier — a row's content is recomputable from the request's features,
    so an unreferenced row is returned immediately.
    """

    def __init__(self, num_arenas: int):
        self.num_arenas = num_arenas
        self._free = collections.deque(range(1, num_arenas + 1))
        self._refs: dict[int, int] = {}      # row -> live reference count
        self._key_of: dict[int, object] = {}  # row -> identity key
        self._by_key: dict[object, int] = {}  # identity key -> row

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        """Arena rows with at least one live reference."""
        return len(self._refs)

    def refcount(self, a: int) -> int:
        return self._refs.get(a, 0)

    def can_admit(self, n: int) -> bool:
        """True when ``n`` fresh (non-shared) rows are allocatable."""
        return n <= len(self._free)

    def lookup(self, key) -> int:
        """Row currently holding ``key``'s cross-KV, or ``NULL_ARENA``."""
        return self._by_key.get(key, NULL_ARENA)

    def alloc(self, key=None) -> int:
        """Claim one exclusively-owned row (refcount 1), keyed for later
        ``lookup`` sharing when ``key`` is given."""
        if not self._free:
            raise MemoryError("cross-KV arena exhausted")
        a = self._free.popleft()
        self._refs[a] = 1
        if key is not None:
            self._key_of[a] = key
            self._by_key[key] = a
        self.check_invariant()
        return a

    def share(self, a: int) -> int:
        """Take one more reference on a live row (same-features request
        admitted while the original is resident). Raises on free rows —
        unlike pool blocks there is no LRU to revive from."""
        if a not in self._refs:
            raise ValueError(f"sharing unreferenced arena row {a}")
        self._refs[a] += 1
        return a

    def free(self, a: int):
        """Drop one reference; the LAST reference returns the row to the
        FIFO free list and unlinks its identity key."""
        if a == NULL_ARENA:
            raise ValueError("freeing the reserved null arena row")
        r = self._refs.get(a, 0)
        if r <= 0:
            raise ValueError(f"double-free of arena row {a}")
        if r > 1:
            self._refs[a] = r - 1
        else:
            del self._refs[a]
            key = self._key_of.pop(a, None)
            if key is not None:
                self._by_key.pop(key, None)
            self._free.append(a)
        self.check_invariant()

    def check_invariant(self):
        """owned ⊎ free must partition rows 1..A; key maps must mirror
        each other and only name owned rows."""
        owned, free = set(self._refs), set(self._free)
        if owned & free:
            raise AssertionError(f"arena states overlap: {owned & free}")
        universe = set(range(1, self.num_arenas + 1))
        if (owned | free) != universe:
            raise AssertionError(
                f"arena lost rows: missing {universe - (owned | free)}, "
                f"foreign {(owned | free) - universe}")
        if not set(self._key_of) <= owned:
            raise AssertionError("keys on non-owned arena rows")
        if {self._by_key[k]: k for k in self._by_key} != self._key_of:
            raise AssertionError("arena key maps out of sync")
        if any(r < 1 for r in self._refs.values()):
            raise AssertionError("non-positive arena refcount")


def head_shard_ok(cfg, tp_size: int) -> bool:
    """True when the head-sharded pool layout is exact for this model:
    each device of the TP axis owns a whole kv-head shard of every block
    (and the matching query-head groups), so the per-device paged
    attention needs no collective. GQA group alignment follows from both
    divisibilities: device i's query heads [i*Hq/t, (i+1)*Hq/t) map onto
    exactly its kv heads [i*Hkv/t, (i+1)*Hkv/t)."""
    return (tp_size > 1 and cfg.n_heads % tp_size == 0
            and cfg.n_kv_heads % tp_size == 0)


# ---------------------------------------------------------------------------
# Pool format: PoolSpec + KV quantization
# ---------------------------------------------------------------------------

KV_DTYPES = ("bf16", "int8", "fp8")

# fp8 e4m3 saturates at +-448; values past it cast to NaN, not inf, so
# the quantizer must clip BEFORE the dtype cast.
_FP8_MAX = 448.0


@dataclasses.dataclass(frozen=True)
class PoolSpec:
    """Static description of one paged pool's physical block format.

    The single source of truth for how K/V blocks are stored: the
    payload dtype (``bf16`` keeps the model compute dtype; ``int8`` /
    ``fp8`` store a low-precision payload plus per-(token-row, kv-head)
    f32 scales as extra ``k_scale``/``v_scale`` pool leaves), the block
    geometry, the physical head dim (``padded_head_dim`` pads blocks to
    the TPU lane width so real-hardware tiling is honest — 0 means
    unpadded), and whether the pool is head-sharded over TP. Frozen and
    hashable, so it rides through jit as a static argument and through
    ``transport.MigrationPacket`` as the format tag both ends must
    agree on. ``kv_dtype="bf16"`` with no padding reproduces today's
    pool tree byte-for-byte (no scale leaves, same shapes) — the
    bit-identity contract for the fp path.
    """

    kv_dtype: str = "bf16"                # "bf16" | "int8" | "fp8"
    scale_layout: str = "token_head"      # scales per (token row, kv head)
    block_size: int = 16
    n_kv_heads: int = 1
    head_dim: int = 64
    padded_head_dim: int = 0              # 0 = no lane padding
    head_sharded: bool = False

    def __post_init__(self):
        if self.kv_dtype not in KV_DTYPES:
            raise ValueError(f"kv_dtype must be one of {KV_DTYPES}, "
                             f"got {self.kv_dtype!r}")
        if self.padded_head_dim and self.padded_head_dim < self.head_dim:
            raise ValueError("padded_head_dim < head_dim")

    @property
    def quantized(self) -> bool:
        return self.kv_dtype != "bf16"

    @property
    def store_dtype(self):
        """Payload dtype blocks are stored in (None = the cache dtype)."""
        if self.kv_dtype == "int8":
            return jnp.int8
        if self.kv_dtype == "fp8":
            return jnp.float8_e4m3fn
        return None

    @property
    def qmax(self) -> float:
        """Largest representable payload magnitude (scale denominator)."""
        return 127.0 if self.kv_dtype == "int8" else _FP8_MAX

    @property
    def pool_head_dim(self) -> int:
        """Physical last-axis width of pool blocks (lane-padded or not)."""
        return self.padded_head_dim or self.head_dim


def make_pool_spec(cfg, layout: PagedLayout, *, kv_dtype: str = "bf16",
                   padded_head_dim: int = 0,
                   head_sharded: bool = False) -> PoolSpec:
    """Build the ``PoolSpec`` for a model config + paged layout."""
    return PoolSpec(kv_dtype=kv_dtype, block_size=layout.block_size,
                    n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
                    padded_head_dim=padded_head_dim,
                    head_sharded=head_sharded)


def quantize_kv(x, spec: PoolSpec):
    """Quantize K or V rows to the spec's payload dtype + scales.

    x: (..., Hkv, D) fp rows. Returns ``(payload, scale)`` with payload
    shaped like x in ``spec.store_dtype`` and scale ``(..., Hkv)`` f32 —
    one absmax scale per (token row, kv head), so a one-token decode
    append is self-contained and never requantizes its block. Zero rows
    keep scale 0 with a divide guard (payload 0, dequant exact). int8
    rounds to nearest; fp8 clips to +-448 before the cast (overflow
    would produce NaN, not saturation)."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1) / spec.qmax
    q = xf / jnp.where(scale > 0, scale, 1.0)[..., None]
    q = jnp.clip(q, -spec.qmax, spec.qmax)
    if spec.kv_dtype == "int8":
        q = jnp.round(q)
    return q.astype(spec.store_dtype), scale


def dequantize_kv(payload, scale):
    """Inverse of ``quantize_kv``: f32 rows from payload + scales."""
    return payload.astype(jnp.float32) * scale[..., None]


def _pad_head_dim(x, hd_pool: int):
    """Zero-pad the last axis of K/V rows to the pool's physical width."""
    pad = hd_pool - x.shape[-1]
    if pad <= 0:
        return x
    widths = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
    return jnp.pad(x, widths)


def write_kv_rows(pool, phys, off, k, v, spec: PoolSpec = None,
                  layer=None):
    """Scatter new K/V rows at the decode/verify append frontier.

    pool: a pool dict ({"k","v"} plus ``k_scale``/``v_scale`` when
    quantized); phys/off: integer index arrays selecting (block, slot-
    in-block) per row; k/v: (..., Hkv, D) new rows, index arrays
    broadcasting over the leading dims. With ``layer`` (an int32
    scalar) every leaf is a stack over layers and the rows land at
    ``[layer, phys, off]``, in place in the stack. With a quantized spec
    the rows are quantized per (row, head) and the scales land at the
    same coordinates; with ``spec=None`` / bf16 this is exactly the
    historical two-scatter update."""
    idx = (phys, off) if layer is None else (layer, phys, off)
    if spec is not None:
        k = _pad_head_dim(k, spec.pool_head_dim)
        v = _pad_head_dim(v, spec.pool_head_dim)
    if spec is None or not spec.quantized:
        return dict(pool, k=pool["k"].at[idx].set(
                        k.astype(pool["k"].dtype)),
                    v=pool["v"].at[idx].set(
                        v.astype(pool["v"].dtype)))
    kq, ks = quantize_kv(k, spec)
    vq, vs = quantize_kv(v, spec)
    return dict(pool,
                k=pool["k"].at[idx].set(kq),
                v=pool["v"].at[idx].set(vq),
                k_scale=pool["k_scale"].at[idx].set(ks),
                v_scale=pool["v_scale"].at[idx].set(vs))


# ---------------------------------------------------------------------------
# Device-side pytree init / prefill packing
# ---------------------------------------------------------------------------


def init_layer_pool(cfg, layout: PagedLayout, dtype, *, window=None,
                    spec: PoolSpec = None):
    """Per-layer cache for the paged engine. Full-attention layers get a
    block pool; windowed layers keep a per-slot ring buffer (their state
    is bounded at ``window`` tokens — paging buys nothing); callers route
    SSM kinds to their existing per-slot state inits. ``spec`` selects
    the pool block format: a quantized ``PoolSpec`` stores low-precision
    payloads plus per-(row, head) f32 ``k_scale``/``v_scale`` leaves
    ``(NB, BS, Hkv)``; ``None`` (or a bf16 spec without padding) yields
    the identical tree to before the spec existed."""
    if window:
        return attn_lib.init_kv_cache(cfg, layout.num_slots, layout.max_len,
                                      dtype, window=window)
    hd = spec.pool_head_dim if spec is not None else cfg.head_dim
    shape = (layout.num_blocks, layout.block_size, cfg.n_kv_heads, hd)
    if spec is None or not spec.quantized:
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    sshape = shape[:-1]
    return {"k": jnp.zeros(shape, spec.store_dtype),
            "v": jnp.zeros(shape, spec.store_dtype),
            "k_scale": jnp.zeros(sshape, jnp.float32),
            "v_scale": jnp.zeros(sshape, jnp.float32)}


def init_cross_arena(cfg, layout: PagedLayout, dtype):
    """Cross-attention K/V arena for encoder-decoder serving.

    One row per resident request plus the reserved null row 0:
    ``{"k","v"}`` of ``(n_layers, num_slots + 1, Hkv, encoder_len, hd)``.
    Rows are written ONCE at admission (the encoder forward runs inside
    the prefill jit and scatters each layer's cross K/V, right-padded
    from the frame bucket to ``encoder_len``) and read every decode step
    by the cross-attention layers, masked to the request's true encoder
    length. Host bookkeeping lives in ``CrossArena``.
    """
    shape = (cfg.n_layers, layout.num_slots + 1, cfg.n_kv_heads,
             cfg.encoder_len, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def pack_cross_arena(arena, cross_kv, arena_ids):
    """Scatter freshly encoded cross-KV rows into the arena.

    arena: {"k","v"} of (L, A+1, Hkv, enc_len, hd); cross_kv: {"k","v"}
    of (L, N, Hkv, Fb, hd) with the frame bucket Fb <= enc_len
    (right-padded here with zeros — reads are masked to the true
    length); arena_ids: (N,) int32 destination rows. Prefill-batch
    filler rows point at the reserved null row 0, where their writes
    collide harmlessly (the ``pack_prefill_kv`` argument); duplicate
    REAL ids only occur for identity-shared features, whose rows are
    bit-identical (deterministic encoder), so collision order there is
    unobservable too."""
    # .at[:, ids] indexes axis 1 with (N,) ids and expects the update
    # shaped (L, N, Hkv, enc_len, hd) — cross_kv already matches.
    def put(a, c):
        pad = a.shape[3] - c.shape[3]
        if pad:
            widths = [(0, 0)] * c.ndim
            widths[3] = (0, pad)
            c = jnp.pad(c, widths)
        return a.at[:, arena_ids].set(c)

    return {"k": put(arena["k"], cross_kv["k"]),
            "v": put(arena["v"], cross_kv["v"])}


def init_slot_tables(layout: PagedLayout):
    """(block_table, lengths) device arrays, all slots empty/null."""
    table = jnp.full((layout.num_slots, layout.max_blocks_per_seq),
                     NULL_BLOCK, jnp.int32)
    lengths = jnp.zeros((layout.num_slots,), jnp.int32)
    return table, lengths


def pack_prefill_kv(pool, dense_kv, block_ids, block_size,
                    spec: PoolSpec = None):
    """Scatter a batch of prefilled dense caches into pool blocks.

    pool: {"k","v"} of (..., NB, BS, Hkv, D); dense_kv: {"k","v"} of
    (..., N, S, Hkv, D) with S == block_ids.shape[1] * BS (kernels/ops
    pads prefill caches with zeros past each row's true length);
    block_ids: (N, nbp) int32 physical destinations, one row per
    prefilled sequence. Leading dims (stacked layers) broadcast. With a
    quantized ``spec`` the dense rows are quantized per (token, head)
    and the scales scatter into the pool's ``k_scale``/``v_scale``
    leaves through the same flat block indices.

    Rows' REAL blocks are disjoint (the allocator hands each sequence its
    own); pad-tail and batch-filler entries all point at the reserved
    null block, so their writes collide there in unspecified order —
    harmless, because null-block contents are only ever read masked.
    """
    if block_ids.ndim == 1:               # single-sequence convenience
        block_ids = block_ids[None]       # dense rows already carry N=1
    n, nbp = block_ids.shape
    flat = block_ids.reshape(-1)

    def put(p, d):
        lead = p.shape[:-4]
        hkv, hd = p.shape[-2:]
        d = d.reshape(lead + (n * nbp, block_size, hkv, hd))
        return p.at[..., flat, :, :, :].set(d.astype(p.dtype))

    if spec is not None and spec.pool_head_dim != dense_kv["k"].shape[-1]:
        dense_kv = {"k": _pad_head_dim(dense_kv["k"], spec.pool_head_dim),
                    "v": _pad_head_dim(dense_kv["v"], spec.pool_head_dim)}
    if spec is None or not spec.quantized:
        return dict(pool, k=put(pool["k"], dense_kv["k"]),
                    v=put(pool["v"], dense_kv["v"]))

    def put_scale(p, s):
        lead = p.shape[:-3]
        hkv = p.shape[-1]
        s = s.reshape(lead + (n * nbp, block_size, hkv))
        return p.at[..., flat, :, :].set(s)

    kq, ks = quantize_kv(dense_kv["k"], spec)
    vq, vs = quantize_kv(dense_kv["v"], spec)
    return dict(pool, k=put(pool["k"], kq), v=put(pool["v"], vq),
                k_scale=put_scale(pool["k_scale"], ks),
                v_scale=put_scale(pool["v_scale"], vs))


def _select_slots(state, dense, row_of_slot, valid, batch_axis):
    """Gather-select install of per-slot decode state: slot s takes
    ``dense`` row ``row_of_slot[s]`` where ``valid[s]``, else keeps its
    current state. A gather + where instead of a scatter because scatter
    with duplicate indices applies updates in unspecified order, while
    this is exact for any (row_of_slot, valid)."""
    g = jnp.take(dense, row_of_slot, axis=batch_axis)
    shape = [1] * state.ndim
    shape[batch_axis] = -1
    return jnp.where(valid.reshape(shape), g, state)


def pack_prefill_ring(ring, dense_ring, row_of_slot, valid):
    """Install a batch of prefilled ring caches into per-slot storage.

    ring: (..., B, size_e, Hkv, D); dense_ring: (..., N, size_p, Hkv, D)
    with size_p <= size_e. When the prompt is shorter than the ring the
    prefill cache is zero-padded at the tail — those slots are masked by
    the position-validity predicate until decode overwrites them. When the
    prompt wrapped, size_p == size_e and ring order (slot = pos % size)
    already matches the decode discipline, so a direct copy is exact.
    """
    size_p = dense_ring.shape[-3]
    size_e = ring.shape[-3]
    pad = size_e - size_p
    if pad:
        widths = [(0, 0)] * dense_ring.ndim
        widths[-3] = (0, pad)
        dense_ring = jnp.pad(dense_ring, widths)
    return _select_slots(ring, dense_ring, row_of_slot, valid,
                         batch_axis=ring.ndim - 4)


def pack_prefill_state(state, dense_state, row_of_slot, valid):
    """Install a batch of SSM/conv decode states into per-slot storage.

    Both sides come from ``init_*_cache``-shaped stacked trees: a leading
    layer-count axis, then the batch axis — so the slot/batch axis is
    axis 1 on every leaf (rglru h (L, B, dr), conv (L, B, w-1, d),
    mlstm C (L, B, H, hd, hd), slstm c (L, B, H, hd), ...)."""
    return jax.tree.map(
        lambda s, d: _select_slots(s, d, row_of_slot, valid, batch_axis=1),
        state, dense_state)


def extract_blocks(pools, kinds, block_ids, slot, arena=NULL_ARENA):
    """Gather ONE slot's migratable cache out of a paged tree.

    ``kinds`` is a same-structure tree of kind strings (built by
    ``transformer.paged_pool_mask`` / the encdec equivalent — classified
    by LAYER KIND, never by shape): ``"pool"`` leaves
    ``(L, NB, BS, Hkv, D)`` gather the ``block_ids`` rows along the
    block axis (axis 1, after the stacked layer-count axis — the same
    convention ``pack_prefill_kv`` and the COW copy write through);
    ``"slot"`` leaves (rings, SSM carries, conv tails — slot axis also
    at axis 1) take the slot's own row; ``"cross"`` leaves (the cross-KV
    arena, arena-row axis at axis 1) take row ``arena`` instead — a
    slot's arena row is an indirection through the scheduler's
    ``arena_ids``, not the slot index. Single rows are kept at size 1 so
    every leaf preserves its rank (and therefore its PartitionSpec)
    across the migration. ``block_ids`` is padded to a fixed width with
    the null block so the jit traces ONCE per engine; pad rows carry
    null-block content and land back in the destination's null block on
    insert. Pure function of its inputs — the source pool is never
    mutated, so the caller may free the source blocks in any order
    relative to this gather."""
    def one(leaf, kind):
        if kind == "pool":
            return jnp.take(leaf, block_ids, axis=1)
        row = arena if kind == "cross" else slot
        return jax.lax.dynamic_slice_in_dim(leaf, row, 1, axis=1)

    return jax.tree.map(one, pools, kinds)


def insert_blocks(pools, kinds, packet, block_ids, slot, arena=NULL_ARENA):
    """Scatter an ``extract_blocks`` packet into a destination tree.

    The inverse of ``extract_blocks`` against a DIFFERENT pool: pool
    leaves scatter the packet's block rows into freshly allocated
    ``block_ids`` (pad entries point at the null block, where their
    null-content writes collide harmlessly — the ``pack_prefill_kv``
    argument); ``"slot"`` leaves overwrite the destination slot's row
    and ``"cross"`` leaves the destination's freshly allocated ``arena``
    row. Donatable: the caller's jit donates ``pools``."""
    def one(leaf, kind, pk):
        if kind == "pool":
            return leaf.at[:, block_ids].set(pk)
        row = arena if kind == "cross" else slot
        return jax.lax.dynamic_update_slice_in_dim(leaf, pk, row, axis=1)

    return jax.tree.map(one, pools, kinds, packet)


__all__ = [
    "KV_DTYPES", "NULL_ARENA", "NULL_BLOCK", "CrossArena", "PagedLayout",
    "BlockAllocator", "PoolSpec", "PrefixIndex", "blocks_for",
    "dequantize_kv", "extract_blocks", "head_shard_ok",
    "init_cross_arena", "init_layer_pool", "init_slot_tables",
    "insert_blocks", "make_pool_spec", "pack_cross_arena",
    "pack_prefill_kv", "pack_prefill_ring", "pack_prefill_state",
    "quantize_kv", "rollback_tail", "write_kv_rows",
]
