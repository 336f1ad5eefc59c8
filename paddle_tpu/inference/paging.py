"""Host-side bookkeeping for the block-paged KV cache (ISSUE 7).

The device side is dumb on purpose: per layer, one `[num_pages, kv_heads,
page_size, head_dim]` K/V arena plus per-slot page tables carried as traced
DATA through the compiled decode/prefill steps (engine.py).  Everything that
decides WHICH page holds WHICH tokens lives here, on the host, where it can
be mutated without recompiles:

- `PagePool` — refcounted free-list allocator over page ids.  Page 0 is a
  permanent scratch page: inactive slots' table rows are all-zero and every
  masked/out-of-range scatter is redirected to it, so garbage writes can
  never land in a page another sequence attends.
- `PrefixCache` — a token-chain index over COMMITTED prompt pages.  Full
  pages chain by `(parent_key, page_tokens)`; a partially filled last page
  is stored as a tail under its parent.  A new request walks the chain,
  maps every matched full page read-only (incref), and copy-on-writes the
  matched tail (the only shared page it would ever append into).  Entries
  are evicted LRU, leaves first, only when the allocator runs dry — the
  cache is a use for pages that would otherwise sit on the free list.

Sharing safety contract (relied on by the engine and the COW tests):

- readers of a cached page trust only rows < the entry's committed row
  count; everything beyond is masked by position, so the OWNER may keep
  appending into its own committed tail without invalidating readers;
- a reader never writes a shared page: full-page matches are read-only by
  construction (its own rows start after them) and the tail match is copied
  into a fresh page at admission, before any token lands.

Speculative verify (ISSUE 11) widens the decode write from one row to a
`[pos, pos+k]` window per slot.  The same scatter contract covers it: every
window row whose page-table entry is unmapped (table value 0) or beyond the
table redirects to scratch page 0, so REJECTED draft positions need no
rollback — their KV rows either landed in scratch or sit past the slot's
advanced `pos`, where the next verify window overwrites them before any
query can attend them (attention masks j <= pos+i).  `spec_write_pages`
below is the host-side mirror of that arithmetic, used by the engine's
debug-invariants check.
"""

from __future__ import annotations

import base64

import numpy as np


def spec_write_pages(pos, width, page_size, mapped_entries):
    """Page-table entries a verify window `[pos, pos+width)` writes through.

    Returns `(in_table, overrun)`: sorted entry indices that fall inside the
    slot's mapped table prefix (`entry < mapped_entries`) and those beyond it.
    Overrun entries MUST scatter to scratch page 0 on device — the engine's
    draft-budget clamp (`min(k, remaining-1)`) keeps every COMMITTED row in
    the mapped prefix, so a non-empty overrun set is only ever rejected-draft
    territory.  Pure host arithmetic; no device state."""
    pos, width, ps = int(pos), int(width), int(page_size)
    if width <= 0:
        return [], []
    entries = sorted({(pos + i) // ps for i in range(width)})
    in_table = [e for e in entries if e < mapped_entries]
    overrun = [e for e in entries if e >= mapped_entries]
    return in_table, overrun


# Quantized KV serving (ISSUE 18): 'int8' stores K/V pages as int8 with
# per-token-row, per-kv-head float32 scales kept in a parallel scale arena
# `[num_pages, kv_heads, 1, page_size]` (one per K and one per V per layer).
# Scale rows are written by the SAME scatters that write the quantized page
# rows and are addressed by the SAME page tables, so every piece of host
# bookkeeping in this module — refcounts, COW, prefix chains — covers them
# with zero extra state: holding a page holds its scale rows.
KV_QUANT_MODES = ("none", "int8")


class QuantConfigError(ValueError):
    """Raised at engine CONSTRUCTION time for an invalid KV-quantization
    configuration (an unknown mode), so the operator sees a typed,
    actionable error instead of a mid-traffic shape or dtype mismatch inside
    a compiled step — the same contract as distributed.sharding.ShardingError
    (ISSUE 14)."""


def validate_kv_quant(mode):
    """Typed validation of a kv_quant mode string (QuantConfigError on
    violation); returns the normalized mode."""
    mode = "none" if mode is None else str(mode).strip().lower()
    if mode not in KV_QUANT_MODES:
        raise QuantConfigError(
            f"kv_quant must be one of {'|'.join(KV_QUANT_MODES)}, got {mode!r}"
        )
    return mode


def kv_page_bytes(page_size, kv_heads, head_dim, dtype_bytes, quant="none"):
    """HBM bytes ONE layer's K+V storage spends per page.  Under 'int8'
    every K/V element costs 1 byte plus a 4-byte float32 scale per
    (token row, kv head) — one scale-arena element.  This is
    the byte math behind FLAGS_serve_kv_pool_pages auto-sizing: the int8
    pool gets `head_dim*dtype_bytes / (head_dim + 4)` times the pages the
    same budget buys at full precision (~1.94x at bf16 head_dim=128)."""
    if validate_kv_quant(quant) == "int8":
        return 2 * int(page_size) * int(kv_heads) * (int(head_dim) + 4)
    return 2 * int(page_size) * int(kv_heads) * int(head_dim) * int(dtype_bytes)


def check_scale_arenas(arenas, num_pages, page_size):
    """Debug-invariants audit of the scale arenas (ISSUE 18): every int8
    layer arena must carry k_scale/v_scale buffers congruent with the K/V
    arena — same leading page count (the tables index both), same kv
    heads, one [1, page_size] row of scales per (page, head), float32 — and a
    'none' arena must carry none.  The pool's refcounts need no separate
    scale accounting precisely BECAUSE of this congruence: page p's scale
    rows live and die with page p.  Raises AssertionError on violation."""
    for i, a in enumerate(arenas):
        quant = getattr(a, "quant", "none")
        ks, vs = getattr(a, "k_scale", None), getattr(a, "v_scale", None)
        if quant != "int8":
            if ks is not None or vs is not None:
                raise AssertionError(
                    f"scale invariant: layer {i} arena is quant={quant!r} "
                    "but carries scale buffers"
                )
            continue
        kvh = int(a.k.shape[1])
        want = (int(num_pages), kvh, 1, int(page_size))
        for name, t in (("k_scale", ks), ("v_scale", vs)):
            if t is None:
                raise AssertionError(
                    f"scale invariant: layer {i} int8 arena missing {name}"
                )
            if tuple(int(d) for d in t.shape) != want:
                raise AssertionError(
                    f"scale invariant: layer {i} {name} shape "
                    f"{tuple(t.shape)} != {want}"
                )
            if "float32" not in str(t.dtype):
                raise AssertionError(
                    f"scale invariant: layer {i} {name} dtype {t.dtype} "
                    "is not float32"
                )


# Canonical tensor-parallel layout of every KV cache buffer (ISSUE 14):
# paged arenas are [num_pages, kv_heads, page_size, head_dim] and split their
# KV HEADS axis over the 'mp' mesh axis, so each device stores and streams
# only its local heads' rows.  Page identity, table entries, and every piece of
# host-side bookkeeping in this module stay device-count-agnostic: a page
# is the SAME page on every shard, just narrower.
def shard_kv_for_tp(cache):
    """Place a paged arena's k/v buffers on the installed serving mesh: kv
    heads (dim 1) split over 'mp' and — under context parallelism
    (ISSUE 20) — the PAGE axis (dim 0) block-split over 'cp', so shard s physically holds
    pages [s*per_shard, (s+1)*per_shard) and the cp decode kernel streams
    only local pages.  No-op without a mesh, so the engine calls it
    unconditionally; returns the cache for chaining."""
    from jax.sharding import PartitionSpec as P

    from ..distributed import mesh as _mesh

    cp = _mesh.axis_size("cp")
    if _mesh.get_mesh() is None or (_mesh.axis_size("mp") <= 1 and cp <= 1):
        return cache
    mp_axis = "mp" if _mesh.axis_size("mp") > 1 else None
    spec = P("cp" if cp > 1 else None, mp_axis, None, None)
    _mesh.shard_tensor_(cache.k, spec)
    _mesh.shard_tensor_(cache.v, spec)
    # int8 arenas (ISSUE 18): scale buffers are [pages, kv_heads, 1,
    # page_size], so the same spec applies — each device holds exactly its
    # local heads' scale rows
    for name in ("k_scale", "v_scale"):
        t = getattr(cache, name, None)
        if t is not None:
            _mesh.shard_tensor_(t, spec)
    return cache


def check_table_bounds(table, num_pages):
    """Every page-table entry must name a real arena page: the fused paged
    Pallas kernel indexes the arena by the RAW table value inside its
    BlockSpec index maps (no clamp — a clamp would hide corruption as a
    silent wrong-page read), so an out-of-range entry is device-undefined
    behavior, not just a wrong answer.  Raises AssertionError on violation.
    Pure host arithmetic; `table` is the host mirror ([..., P] int array)."""
    t = np.asarray(table)
    if t.size == 0:
        return
    lo, hi = int(t.min()), int(t.max())
    if lo < 0 or hi >= int(num_pages):
        bad = np.argwhere((t < 0) | (t >= int(num_pages)))
        raise AssertionError(
            f"page table entries out of arena bounds [0, {int(num_pages)}): "
            f"min={lo}, max={hi}, first bad index={bad[0].tolist()}"
        )


# ---------------------------------------------------------------------------
# Disaggregated prefill/decode handoff wire format (ISSUE 19).  A prefill
# worker ships the COMMITTED prompt rows of every layer's K/V arena to a
# decode worker as ROW payloads — `[L, kv_heads, head_dim]` per layer, raw
# little-endian bytes, base64 for the JSON hop — deliberately page-size
# agnostic so the two sides may run different page geometries.  Under
# kv_quant='int8' the rows ship AS STORED (int8 elements + the float32
# per-row/per-head scale rows from the parallel scale arena), so handoff
# bytes get the same ~2x saving the arena gets and the decode side imports
# bit-identical quantized rows: no re-quantization, no drift.
# ---------------------------------------------------------------------------

HANDOFF_VERSION = 1


class HandoffFormatError(ValueError):
    """Raised when a handoff payload cannot be imported by the receiving
    decode engine — wrong version, mismatched quant mode / KV geometry /
    layer count, or corrupt row bytes.  Typed so the serving layer can map
    it to a 4xx instead of crashing a compiled step (same contract as
    QuantConfigError above)."""


def _np_dtype(name):
    """np.dtype for a cache dtype name, covering the ml_dtypes extension
    types (bfloat16 etc.) that plain numpy doesn't parse."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def _b64(arr):
    return base64.b64encode(np.ascontiguousarray(arr).tobytes()).decode("ascii")


def _unb64(s, dtype, shape, what):
    buf = base64.b64decode(s.encode("ascii"))
    want = int(np.prod(shape)) * dtype.itemsize
    if len(buf) != want:
        raise HandoffFormatError(
            f"handoff {what}: {len(buf)} bytes, expected {want} for "
            f"shape {tuple(shape)} dtype {dtype}"
        )
    return np.frombuffer(buf, dtype=dtype).reshape(shape).copy()


def serialize_kv_handoff(layers, prompt_len, quant, dtype_name):
    """Pack per-layer exported prompt rows into a JSON-safe handoff payload.

    `layers` is a list (one per model layer) of dicts with 'k'/'v' arrays of
    shape [L, kv_heads, head_dim] (int8 under quant='int8', else the cache
    dtype) plus 'k_scale'/'v_scale' [L, kv_heads, 1] float32 when quantized.
    Returns the payload dict; its 'payload_bytes' field counts the RAW row
    bytes (pre-base64) — the number the bench and paddle_disagg_* metrics
    report as handoff traffic."""
    if not layers:
        raise HandoffFormatError("handoff payload needs >= 1 layer")
    L = int(prompt_len)
    kvh, hd = int(layers[0]["k"].shape[1]), int(layers[0]["k"].shape[2])
    quant = validate_kv_quant(quant)
    raw = 0
    packed = []
    for ly in layers:
        rec = {"k": _b64(ly["k"]), "v": _b64(ly["v"])}
        raw += ly["k"].nbytes + ly["v"].nbytes
        if quant == "int8":
            rec["k_scale"] = _b64(ly["k_scale"])
            rec["v_scale"] = _b64(ly["v_scale"])
            raw += ly["k_scale"].nbytes + ly["v_scale"].nbytes
        packed.append(rec)
    return {
        "version": HANDOFF_VERSION,
        "prompt_len": L,
        "quant": quant,
        "kv_heads": kvh,
        "head_dim": hd,
        "n_layers": len(layers),
        "dtype": str(dtype_name),
        "payload_bytes": int(raw),
        "layers": packed,
    }


def deserialize_kv_handoff(payload, quant, kv_heads, head_dim, n_layers, dtype_name):
    """Unpack + validate a handoff payload against the RECEIVING engine's
    arena geometry.  Returns (layers, prompt_len) where `layers` mirrors the
    serialize_kv_handoff input layout.  Every mismatch is a typed
    HandoffFormatError — the decode engine must never feed foreign-geometry
    rows into its compiled import scatter."""
    if not isinstance(payload, dict):
        raise HandoffFormatError(f"handoff payload is {type(payload).__name__}, not a dict")
    if int(payload.get("version", -1)) != HANDOFF_VERSION:
        raise HandoffFormatError(
            f"handoff version {payload.get('version')!r} != {HANDOFF_VERSION}"
        )
    quant = validate_kv_quant(quant)
    for field, want in (
        ("quant", quant),
        ("kv_heads", int(kv_heads)),
        ("head_dim", int(head_dim)),
        ("n_layers", int(n_layers)),
        ("dtype", str(dtype_name)),
    ):
        got = payload.get(field)
        got = type(want)(got) if got is not None else got
        if got != want:
            raise HandoffFormatError(
                f"handoff {field} mismatch: payload has {got!r}, "
                f"this engine expects {want!r}"
            )
    L = int(payload.get("prompt_len", 0))
    if L <= 0:
        raise HandoffFormatError(f"handoff prompt_len {L} must be positive")
    rows = payload.get("layers")
    if not isinstance(rows, list) or len(rows) != int(n_layers):
        raise HandoffFormatError(
            f"handoff carries {len(rows) if isinstance(rows, list) else '?'} "
            f"layer records, expected {int(n_layers)}"
        )
    elem = np.dtype(np.int8) if quant == "int8" else _np_dtype(dtype_name)
    kvh, hd = int(kv_heads), int(head_dim)
    out = []
    for i, rec in enumerate(rows):
        ly = {
            "k": _unb64(rec["k"], elem, (L, kvh, hd), f"layer {i} k"),
            "v": _unb64(rec["v"], elem, (L, kvh, hd), f"layer {i} v"),
        }
        if quant == "int8":
            f32 = np.dtype(np.float32)
            ly["k_scale"] = _unb64(rec["k_scale"], f32, (L, kvh, 1), f"layer {i} k_scale")
            ly["v_scale"] = _unb64(rec["v_scale"], f32, (L, kvh, 1), f"layer {i} v_scale")
        out.append(ly)
    return out, L


class PagePool:
    """Refcounted page allocator.  Page 0 is scratch: pinned, never handed
    out, the target of every redirected garbage write.

    Context parallelism (ISSUE 20) block-shards the arena's page axis over
    the 'cp' mesh axis, so the pool optionally partitions its id space into
    `shards` equal contiguous ranges — shard s owns [s*per_shard,
    (s+1)*per_shard) and its FIRST page (s*per_shard) is that device's local
    scratch, pinned like page 0.  Sequence page k must be allocated from
    shard k % cp (the round-robin layout the cp decode kernel assumes), so
    `alloc` takes the owning shard.  shards=1 is the exact legacy pool."""

    def __init__(self, num_pages, shards=1):
        shards = int(shards) if shards else 1
        if shards < 1:
            raise ValueError(f"page pool shards must be >= 1, got {shards}")
        if num_pages % shards:
            raise ValueError(
                f"page pool size {num_pages} must divide evenly into "
                f"{shards} shards"
            )
        if num_pages < 2 * shards:
            raise ValueError(
                "page pool needs >= 2 pages per shard (1 scratch + 1 usable)"
            )
        self.num_pages = int(num_pages)
        self.shards = shards
        self.per_shard = self.num_pages // shards
        self.scratch_pages = tuple(s * self.per_shard for s in range(shards))
        self.refs = np.zeros(self.num_pages, np.int64)
        for p in self.scratch_pages:
            self.refs[p] = 1  # scratch, pinned forever
        self._free_by_shard = [
            list(range(s * self.per_shard + 1, (s + 1) * self.per_shard))
            for s in range(shards)
        ]

    @property
    def _free(self):
        """Flat read-only view of every free page id (audits and tests);
        allocation goes through the per-shard lists."""
        return [p for lst in self._free_by_shard for p in lst]

    @property
    def usable_pages(self):
        return self.num_pages - self.shards

    def shard_of(self, page):
        return int(page) // self.per_shard

    def is_scratch(self, page):
        return int(page) % self.per_shard == 0

    def free_count(self, shard=None):
        if shard is None:
            return sum(len(lst) for lst in self._free_by_shard)
        return len(self._free_by_shard[shard])

    def used_count(self):
        return self.usable_pages - self.free_count()

    def alloc(self, shard=0):
        """One page at refcount 1 from `shard`'s range; the caller must have
        checked free_count (the engine's admission math guarantees it never
        runs dry)."""
        if not self._free_by_shard[shard]:
            raise RuntimeError(
                f"page pool shard {shard} exhausted — admission reservations "
                "should have prevented this allocation (accounting bug)"
            )
        p = self._free_by_shard[shard].pop(0)
        assert self.refs[p] == 0, f"free-list page {p} had refcount {self.refs[p]}"
        self.refs[p] = 1
        return p

    def incref(self, page):
        assert not self.is_scratch(page), "scratch page is never mapped"
        assert self.refs[page] > 0, f"incref on dead page {page}"
        self.refs[page] += 1

    def decref(self, page):
        """Drop one reference; a page hitting 0 returns to its shard's free
        list."""
        assert not self.is_scratch(page), "scratch page is never released"
        assert self.refs[page] > 0, f"decref on dead page {page}"
        self.refs[page] -= 1
        if self.refs[page] == 0:
            self._free_by_shard[self.shard_of(page)].append(page)
            return True
        return False


class WindowPages:
    """The page group of layers whose rows are WINDOWED: a query at position
    p reads its layer's rows `p - reach + 1 .. p` and no earlier one, so a
    slot maps pages for the rows in reach only and gives a page back as soon
    as its last row falls behind the window.  Its own `PagePool` and page
    table beside the engine's (the same `[slots, pages_per_seq]` int32 of
    LOGICAL page columns: column c holds rows `c * page_size ..`, 0 = nothing
    mapped, which is the scratch page, so the kernels index it as they index
    the full group's), and arenas of `pool_pages` pages.

    A decoding slot holds at most `slot_pages` (`reach / page_size + 1` for
    whole pages), a prefilling one at most `chunk_pages` (the rows a chunk
    writes and the reach before them), and one prompt is prefilled at a time:
    `slots * slot_pages + chunk_pages` pages and the scratch page never run
    dry.  Nothing shares a windowed page (no prefix cache over windowed
    layers), so a page's refcount is 0 or 1.

    Host arithmetic only; the caller holds the engine's mutex.  A page given
    back may be mapped by another slot at once: the device runs its programs
    in the order of their dispatch, and each carries the table it was
    dispatched with."""

    def __init__(self, slots, pages_per_seq, page_size, reach, chunk_rows):
        self.page_size, self.reach = int(page_size), int(reach)
        if self.reach < 1:
            raise ValueError(f"a windowed layer reaches at least its own token, not {reach}")
        ps = self.page_size
        self.slot_pages = min(-(-(self.reach - 1) // ps) + 1, int(pages_per_seq))
        self.chunk_pages = min(-(-(self.reach - 1 + int(chunk_rows)) // ps) + 1, int(pages_per_seq))
        self.pool_pages = int(slots) * self.slot_pages + self.chunk_pages + 1
        self.pool = PagePool(self.pool_pages)
        self.table = np.zeros((int(slots), int(pages_per_seq)), np.int32)
        self.released_behind = 0  # since the engine last reported it

    def first_visible(self, pos):
        return max(int(pos) - self.reach + 1, 0)

    def held(self, s):
        return int(np.count_nonzero(self.table[s]))

    def pages_for(self, rows):
        """The most pages a prompt of `rows` rows needs at once while its
        chunks go in, and then in decode."""
        return min(-(-int(rows) // self.page_size), self.chunk_pages)

    def map_range(self, s, first, last):
        """Slot `s` is about to read rows from `first` and to write rows up
        to `last`: pages behind `first` go back to the pool, columns up to
        `last`'s that hold nothing get a page.  True if the table changed."""
        ps, row = self.page_size, self.table[s]
        lo, hi = int(first) // ps, int(last) // ps
        if row[hi] and not (lo and row[lo - 1]):
            return False  # the mapped columns are one run: nothing behind it, nothing missing
        behind = np.flatnonzero(row[:lo])
        for c in behind:
            self.pool.decref(int(row[c]))
            row[c] = 0
        self.released_behind += len(behind)
        fresh = [c for c in range(lo, hi + 1) if row[c] == 0]
        for c in fresh:
            row[c] = self.pool.alloc()
        return bool(len(behind) or fresh)

    def release(self, s):
        """Drop everything slot `s` maps (finish, evict, restart)."""
        for p in self.table[s][self.table[s] != 0]:
            self.pool.decref(int(p))
        self.table[s, :] = 0

    def check(self, seated_pos):
        """Debug invariants: `seated_pos[s]` is slot s's next position, None
        for a free slot.  A free slot maps nothing; a seated one maps one run
        of columns that covers every row in reach of its next step, none
        behind it, and no more than `slot_pages`; every mapped page is
        mapped once, and the free list is exactly the rest."""
        check_table_bounds(self.table, self.pool.num_pages)
        expected = np.zeros(self.pool.num_pages, np.int64)
        expected[0] = 1
        for s, pos in enumerate(seated_pos):
            cols = np.flatnonzero(self.table[s])
            if pos is None:
                if len(cols):
                    raise AssertionError(f"window invariant: free slot {s} maps columns {cols.tolist()}")
                continue
            # the rows its last step read and wrote (pos - 1) are still mapped,
            # and nothing behind what that step could see
            lo, hi = self.first_visible(pos - 1) // self.page_size, (pos - 1) // self.page_size
            if len(cols) == 0 or cols[0] < lo or cols[0] > self.first_visible(pos) // self.page_size \
                    or cols[-1] < hi or len(cols) != cols[-1] - cols[0] + 1:
                raise AssertionError(
                    f"window invariant: slot {s} at pos {pos} maps columns {cols.tolist()}, "
                    f"its reach is columns {lo}..{hi}")
            if len(cols) > self.slot_pages:
                raise AssertionError(
                    f"window invariant: slot {s} holds {len(cols)} pages, over the bound {self.slot_pages}")
            np.add.at(expected, self.table[s][cols], 1)
        if not np.array_equal(expected, self.pool.refs):
            raise AssertionError("window invariant: a page's refcount is not its one mapping")
        if sorted(self.pool._free) != [p for p in range(1, self.pool.num_pages) if expected[p] == 0]:
            raise AssertionError("window invariant: the free list is not the unmapped pages")


class _Entry:
    __slots__ = ("key", "parent_key", "page", "rows", "children", "last_used",
                 "tokens", "pinned")

    def __init__(self, key, parent_key, page, rows, tokens):
        self.key = key
        self.parent_key = parent_key
        self.page = int(page)
        self.rows = int(rows)  # committed rows; readers trust only j < rows
        self.children = 0
        self.last_used = 0
        self.tokens = tokens  # the page's committed token ids (tuple)
        self.pinned = 0  # session holds (ISSUE 20); > 0 => never evictable


class PrefixCache:
    """Token-chain index over committed prompt pages.

    Full pages are keyed `(parent_key, page_tokens)` so equal prefixes
    converge on the same chain regardless of which request committed them;
    partial last pages are stored as tails under their parent and matched by
    longest common prefix.  Eviction is LRU over childless entries only — a
    parent outlives its children, so no chain ever dangles.

    Chains are rooted per ADAPTER (ISSUE 12): a prompt prefilled under LoRA
    adapter A produced K/V that embed A's deltas, so a request under adapter
    B (or the base model) must never COW-reuse those pages even for an
    identical token chain.  `lookup`/`commit` take the request's STABLE
    registry adapter id (0 = base) and walk from a per-adapter root — equal
    prompts still share within an adapter, never across.
    """

    _ROOT = ()

    def _root(self, adapter):
        """Chain root for one adapter id.  The sentinel tuple can't collide
        with a full-page key (whose first element is itself a key, never the
        marker string) and is truthy, which `_remove`'s parent walk already
        handles (no full entry is keyed by it, so the parent lookup misses
        cleanly)."""
        return self._ROOT if not adapter else ("__lora__", int(adapter))

    def __init__(self, page_size):
        self.page_size = int(page_size)
        self._full = {}   # key -> _Entry (rows == page_size)
        self._tails = {}  # parent_key -> [ _Entry ] (rows < page_size)
        self._clock = 0

    def __len__(self):
        return len(self._full) + sum(len(v) for v in self._tails.values())

    def entries(self):
        for e in self._full.values():
            yield e
        for tails in self._tails.values():
            yield from tails

    def _tick(self, entry):
        self._clock += 1
        entry.last_used = self._clock

    def lookup(self, prompt, adapter=0):
        """Longest cached prefix of `prompt` (np.int32 [L]) committed under
        the same `adapter` id, capped at L-1 so at least one suffix token
        remains to prefill and sample from.  Returns (match_len, full_pages,
        tail_page, tail_rows): `full_pages` are read-only mappable as-is,
        the tail page (if any) must be copy-on-written before the reader
        appends.  Bumps LRU on the matched chain; refcounts are the
        caller's job (it holds the pool)."""
        ps = self.page_size
        L = int(prompt.size)
        toks = prompt.tolist()
        key = self._root(adapter)
        full_pages = []
        matched = []
        i = 0
        while i + ps <= L - 1:  # a full-page match must leave >= 1 suffix token
            child = self._full.get((key, tuple(toks[i : i + ps])))
            if child is None:
                break
            full_pages.append(child.page)
            matched.append(child)
            key = child.key
            i += ps
        tail_page, tail_rows = None, 0
        best = None
        for e in self._tails.get(key, ()):
            lcp = 0
            for a, b in zip(e.tokens, toks[i : L - 1]):  # cap total match at L-1
                if a != b:
                    break
                lcp += 1
            if lcp > tail_rows:
                tail_rows, tail_page, best = lcp, e.page, e
        if best is not None:
            matched.append(best)
        for e in matched:
            self._tick(e)
        return i + tail_rows, full_pages, tail_page, tail_rows

    def commit(self, prompt, pages, pool, adapter=0):
        """Insert-if-absent the prompt's pages after its prefill completed:
        one full-page entry per complete page, one tail for the remainder,
        chained under the committing request's `adapter` root.  New entries
        incref their page (the cache's own hold); pages whose chain position
        is already cached are left alone — the committer may have mapped
        that very entry's page at admission."""
        ps = self.page_size
        L = int(prompt.size)
        toks = prompt.tolist()
        key = self._root(adapter)
        inserted = 0
        for i in range(L // ps):
            ek = (key, tuple(toks[i * ps : (i + 1) * ps]))
            e = self._full.get(ek)
            if e is None:
                e = _Entry(ek, key, pages[i], ps, ek[1])
                self._full[ek] = e
                pool.incref(e.page)
                parent = self._full.get(key) if key is not self._ROOT else None
                if parent is not None:
                    parent.children += 1
                inserted += 1
            self._tick(e)
            key = e.key
        rows = L % ps
        if rows:
            tokens = tuple(toks[L - rows : L])
            tails = self._tails.setdefault(key, [])
            for e in tails:
                if e.tokens == tokens:
                    self._tick(e)
                    return inserted
            e = _Entry((key, tokens), key, pages[L // ps], rows, tokens)
            tails.append(e)
            pool.incref(e.page)
            parent = self._full.get(key) if key is not self._ROOT else None
            if parent is not None:
                parent.children += 1
            self._tick(e)
            inserted += 1
        return inserted

    def _remove(self, entry):
        if entry.rows == self.page_size:
            del self._full[entry.key]
            self._tails.pop(entry.key, None)  # only ever empty lists by now
        else:
            tails = self._tails.get(entry.parent_key, [])
            tails.remove(entry)
            if not tails:
                self._tails.pop(entry.parent_key, None)
        parent = self._full.get(entry.parent_key) if entry.parent_key else None
        if parent is not None:
            parent.children -= 1

    def evict_one(self, pool, shard=None):
        """Drop the LRU childless UNPINNED entry and release its page hold.
        Returns the evicted entry or None when nothing is evictable.  The
        freed page only reaches the free list if no live slot still maps
        it — eviction never invalidates a reader.

        Session-pinned entries (entry.pinned > 0, ISSUE 20) are never
        "childless-evictable": a session's committed chain must survive page
        pressure until the SESSION is evicted (SessionStore.evict_lru drops
        the pins first).  Under a sharded pool, `shard` restricts victims to
        entries whose page lives in that shard's range — evicting elsewhere
        cannot relieve that shard's pressure."""
        victim = None
        for e in self.entries():
            if e.pinned > 0:
                continue  # session hold — the session evicts first
            if shard is not None and pool.shard_of(e.page) != shard:
                continue
            if e.rows == self.page_size and (
                e.children > 0 or self._tails.get(e.key)
            ):
                continue  # a parent outlives its children
            if victim is None or e.last_used < victim.last_used:
                victim = e
        if victim is None:
            return None
        self._remove(victim)
        pool.decref(victim.page)
        return victim

    def chain(self, tokens, adapter=0):
        """The committed entry chain covering the longest cached prefix of
        `tokens` (np.int32 [L]) under `adapter` — full-page links plus an
        EXACT-match tail.  Unlike `lookup`, coverage may reach all L tokens
        (it walks what `commit` wrote, not what a new reader could reuse)
        and the LRU clock is NOT bumped.  Returns (entries, covered_tokens);
        the SessionStore pins exactly this chain."""
        ps = self.page_size
        toks = tokens.tolist() if hasattr(tokens, "tolist") else list(tokens)
        L = len(toks)
        key = self._root(adapter)
        out = []
        i = 0
        while i + ps <= L:
            e = self._full.get((key, tuple(toks[i : i + ps])))
            if e is None:
                break
            out.append(e)
            key = e.key
            i += ps
        covered = i
        rows = L - i
        if 0 < rows < ps:
            for e in self._tails.get(key, ()):
                if e.tokens == tuple(toks[i:L]):
                    out.append(e)
                    covered = L
                    break
        return out, covered

    def clear(self, pool):
        """Release every cache hold (engine shutdown / tests).  Session pins
        are dropped first — callers tearing down the cache tear down the
        sessions with it (SessionStore holds no page refs of its own)."""
        for e in self.entries():
            e.pinned = 0
        n = 0
        while self.evict_one(pool) is not None:
            n += 1
        return n


class SessionStore:
    """First-class multi-turn session KV (ISSUE 20).

    A session is a named, refcounted hold on the PrefixCache chain covering
    its committed conversation — prompt AND generated tokens of every turn
    so far.  `bind` walks the chain `PrefixCache.chain` returns for the
    committed sequence and bumps `entry.pinned` on each link (un-bumping the
    previous turn's chain), so under page pressure `evict_one` can never
    reclaim a live session's pages; the pool refcounts themselves stay the
    cache's — pinning adds no double accounting for the invariant audit to
    untangle.  Turn N+1's request then chunk-prefills ONLY the unshared
    suffix through the ordinary prefix-cache admission path, at true rope
    offsets, with zero new executables.

    Sessions are evicted LRU-whole (a half-pinned chain would be useless),
    either by capacity at bind time or explicitly by the engine's allocator
    when the prefix cache alone cannot relieve page pressure.  The store
    survives warm `restart()`/`fail_all()` for free: it references cache
    entries, and the warm paths keep pool + prefix cache intact."""

    def __init__(self, capacity=256):
        self.capacity = max(1, int(capacity))
        self._sessions = {}  # sid -> record dict
        self._clock = 0
        self.tokens_saved_total = 0  # prefill tokens served from pinned KV
        self.evictions = 0
        self.binds = 0

    def __len__(self):
        return len(self._sessions)

    def __contains__(self, sid):
        return sid in self._sessions

    def sessions(self):
        return list(self._sessions.values())

    def get(self, sid):
        return self._sessions.get(sid)

    def tokens(self, sid):
        s = self._sessions.get(sid)
        return None if s is None else s["tokens"]

    def touch(self, sid):
        s = self._sessions.get(sid)
        if s is not None:
            self._clock += 1
            s["last_used"] = self._clock
        return s

    def bind(self, sid, tokens, entries, adapter=0, tenant=""):
        """(Re)bind `sid` to the committed sequence `tokens` whose cache
        chain is `entries`: pin the new chain, then unpin the previous one
        (in that order, so shared links never transit refcount 0).  Returns
        the session ids evicted to stay within capacity."""
        self._clock += 1
        old = self._sessions.pop(sid, None)
        for e in entries:
            e.pinned += 1
        if old is not None:
            for e in old["entries"]:
                e.pinned -= 1
        self._sessions[sid] = {
            "sid": sid,
            "tokens": np.asarray(tokens, np.int32).copy(),
            "entries": list(entries),
            "adapter": int(adapter),
            "tenant": str(tenant or ""),
            "last_used": self._clock,
            "turns": (old["turns"] + 1) if old else 1,
        }
        self.binds += 1
        evicted = []
        while len(self._sessions) > self.capacity:
            v = self.evict_lru(exclude=sid)
            if v is None:
                break
            evicted.append(v)
        return evicted

    def release(self, sid):
        s = self._sessions.pop(sid, None)
        if s is None:
            return False
        for e in s["entries"]:
            e.pinned -= 1
        return True

    def evict_lru(self, exclude=None):
        """Unpin + drop the least-recently-used session (whole — a partially
        pinned chain serves nobody).  Returns its sid, or None."""
        victim = None
        for sid, s in self._sessions.items():
            if sid == exclude:
                continue
            if victim is None or s["last_used"] < victim["last_used"]:
                victim = s
        if victim is None:
            return None
        self.release(victim["sid"])
        self.evictions += 1
        return victim["sid"]

    def clear(self):
        for sid in list(self._sessions):
            self.release(sid)

    def pages_pinned(self):
        """Distinct cache entries (== pages) held by at least one session."""
        return len({id(e) for s in self._sessions.values() for e in s["entries"]})

    def stats(self):
        tenants = {s["tenant"] for s in self._sessions.values()}
        return {
            "sessions_resident": len(self._sessions),
            "session_tenants": len(tenants),
            "session_pages_pinned": self.pages_pinned(),
            "session_prefill_tokens_saved_total": int(self.tokens_saved_total),
            "session_evictions_total": int(self.evictions),
            "session_binds_total": int(self.binds),
        }

    def check(self, cache, pool):
        """FLAGS_serve_debug_invariants audit clause (ISSUE 20): every pin
        on a cache entry is explained by exactly the sessions holding it,
        every pinned entry is still IN the cache with a live page, and no
        session references an entry the cache no longer owns.  Raises
        AssertionError on violation."""
        want = {}
        for s in self._sessions.values():
            for e in s["entries"]:
                want[id(e)] = want.get(id(e), 0) + 1
        live = {id(e): e for e in cache.entries()}
        for s in self._sessions.values():
            for e in s["entries"]:
                if id(e) not in live:
                    raise AssertionError(
                        f"session invariant: session {s['sid']!r} pins page "
                        f"{e.page} whose cache entry was removed"
                    )
        for e in cache.entries():
            w = want.get(id(e), 0)
            if e.pinned != w:
                raise AssertionError(
                    f"session invariant: entry page {e.page} pinned="
                    f"{e.pinned} but {w} session hold(s) reference it"
                )
            if e.pinned > 0 and pool.refs[e.page] <= 0:
                raise AssertionError(
                    f"session invariant: pinned page {e.page} has refcount "
                    f"{int(pool.refs[e.page])}"
                )
