"""Device-resident bitmap arena: promote containers ONCE, query forever.

Every kernel path in this repo used to re-stage containers from host
numpy into a fresh padded slab on each call (``aggregate._dispatch``
pad/stack/transfer, ``pairwise`` per-class staging).  ``BitmapArena``
fixes that on the hot path: container rows are promoted once into a
device-resident slab, a host-side directory maps container objects to
slab rows, and warm queries move only row *ids*, segment offsets, and
results over PCIe -- never container payloads.

Layout and lifecycle (see docs/MEMORY.md for diagrams):

* **Host mirror** ``_host`` -- ``(capacity, 1024)`` uint64, the
  authoritative copy.  Row 0 is permanently reserved all-zero so kernel
  paths can pad ragged segments with id 0.
* **Device slab** ``_dev`` -- ``(capacity, 2048)`` uint32 ``jax`` array,
  uploaded lazily on the first :meth:`device_slab` call.  Edits batch
  into ONE scatter (``slab.at[ids].set(rows)``); the functional update
  allocates a fresh device buffer, so in-flight dispatches that captured
  the previous slab stay valid -- copy-on-write for free.
* **Directory** -- ``id(container) -> row``.  Correctness is structural,
  not generational: ``RoaringBitmap`` mutators replace container objects
  copy-on-write (the PR 6 ``_version`` audit), so a stale bitmap's new
  containers simply *miss* the lookup and are staged from host --
  bit-identical either way.  The per-bitmap ``_version`` snapshot only
  decides *when* :meth:`adopt` re-walks a bitmap; rows shared between
  bitmaps are refcounted.

Typical use::

    arena = BitmapArena()
    arena.adopt_many(bitmaps)                    # promote once
    or_many(bitmaps, arena=arena)                # warm: zero row uploads
    bitmaps[0].add(7)                            # host edit
    arena.adopt(bitmaps[0])                      # patches 1 row, 1 scatter

Complexity: :meth:`adopt` is O(changed containers) host work plus one
O(changed rows) device scatter; :meth:`lookup` is a dict hit; warm
dispatch gathers rows on-device (no PCIe).  ``docs/ARCHITECTURE.md`` §7
covers the data flow, ``docs/MEMORY.md`` the memory lifecycle.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core import containers as C
from repro.kernels.ref import WORDS


@dataclasses.dataclass
class ArenaStats:
    """Monotone transfer/patch counters -- the observability contract the
    zero-transfer tests assert against.

    ``rows_uploaded`` counts every container row that crossed host ->
    device (initial slab upload + incremental patches); a warm re-query
    must leave it unchanged.  ``host_rows_staged`` is bumped by
    ``aggregate._dispatch`` for each non-resident row it had to stage
    per-call (an arena *miss*).  ``device_gathers`` counts dispatches
    that gathered resident rows on-device (zero PCIe for those rows).
    """

    rows_promoted: int = 0      # container -> word-row promotions (host)
    rows_uploaded: int = 0      # rows that crossed host -> device
    rows_patched: int = 0       # scatter updates to already-device rows
    rows_freed: int = 0         # rows released back to the free list
    revalidations: int = 0      # adopt() calls that found a stale version
    device_gathers: int = 0     # on-device row gathers (no PCIe)
    host_rows_staged: int = 0   # per-call staged rows (arena misses)

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class _Entry:
    """Per-registered-bitmap directory entry (strong refs keep ``id``
    keys valid for the arena's lifetime)."""
    bm: object
    version: int
    conts: dict            # chunk key -> container object at last adopt


class BitmapArena:
    """Device-resident container slab with generation-tracked
    incremental maintenance.  See the module docstring for the layout;
    ``docs/MEMORY.md`` walks the full lifecycle.

    Args:
        capacity: initial row capacity (grows by doubling; device growth
            concatenates zero rows on-device, never re-uploads).
    """

    def __init__(self, capacity: int = 64):
        cap = max(int(capacity), 2)
        self._host = np.zeros((cap, 1024), np.uint64)
        self._n = 1                       # row 0 reserved all-zero
        self._free: list[int] = []
        self._dev = None                  # lazy (capacity, WORDS) uint32
        self._dirty: list[int] = []       # host rows not yet scattered
        self._entries: dict[int, _Entry] = {}   # id(bm) -> _Entry
        self._row_of: dict[int, int] = {}       # id(container) -> row
        self._ref: dict[int, int] = {}          # row -> refcount
        self._shards: ShardSlabs | None = None  # lazy per-shard slab mode
        self.stats = ArenaStats()

    # -- directory ----------------------------------------------------

    def lookup(self, cont) -> int | None:
        """Row id for a *container object*, or None if not resident.

        Container identity IS the generation check: mutators replace
        container objects, so edited-but-not-readopted containers miss.
        """
        return self._row_of.get(id(cont))

    def resident(self, bm) -> bool:
        """True iff ``bm`` is registered at its current ``_version``."""
        e = self._entries.get(id(bm))
        return e is not None and e.version == bm._version

    @property
    def n_rows(self) -> int:
        """Allocated rows (including reserved row 0)."""
        return self._n - len(self._free)

    @property
    def capacity(self) -> int:
        """Slab row capacity (doubles on growth; 8 KiB per row)."""
        return self._host.shape[0]

    # -- adoption / incremental maintenance ---------------------------

    def adopt(self, bm) -> int:
        """Register ``bm`` (or revalidate its generation), promoting only
        containers that changed since the last adopt.

        Returns the number of rows promoted/re-promoted (0 when the
        version snapshot matches -- the warm no-op).  Dirty rows are
        batched; the single device scatter happens lazily at the next
        :meth:`device_slab` / :meth:`sync`.
        """
        e = self._entries.get(id(bm))
        if e is not None and e.version == bm._version:
            return 0
        if e is None:
            e = _Entry(bm, -1, {})
            self._entries[id(bm)] = e
        else:
            self.stats.revalidations += 1
        cur = dict(zip(bm.keys, bm.containers))
        for k, old in list(e.conts.items()):
            if cur.get(k) is old:
                continue
            self._release_cont(old)
            del e.conts[k]
        changed = 0
        for k, c in cur.items():
            if e.conts.get(k) is c:
                continue
            self._register_cont(c)
            e.conts[k] = c
            changed += 1
        e.version = bm._version
        return changed

    def adopt_many(self, bitmaps) -> int:
        """:meth:`adopt` each bitmap; returns total rows promoted."""
        return sum(self.adopt(bm) for bm in bitmaps)

    def adopt_frozen(self, bitmaps) -> int:
        """Bulk-promote an entire (typically frozen / mmap-backed)
        snapshot into the slab: ONE vectorized host conversion and ONE
        host->device transfer, instead of per-container Python work.

        Args: ``bitmaps`` -- a single RoaringBitmap or an iterable of
        them (e.g. ``snapshot.bitmaps.values()`` from
        ``repro.core.serde.read_snapshot``); frozen view-backed and
        ordinary bitmaps both work, and results are bit-identical to
        per-bitmap :meth:`adopt`.

        Every container not yet resident is converted in one batched
        ``containers_to_word_rows`` sweep (bitset rows gathered
        vectorized, arrays/runs through one shared indicator +
        packbits pass) and lands in the device slab in a single
        scatter at the next :meth:`device_slab` / :meth:`sync` --
        ``ArenaStats.rows_uploaded`` grows by exactly the new row
        count.  Returns the number of rows promoted.  Complexity:
        O(total new payload bytes) host work + one device transfer;
        registered-and-current bitmaps cost O(1) each.
        """
        if hasattr(bitmaps, "containers"):      # a single RoaringBitmap
            bitmaps = [bitmaps]
        bitmaps = list(bitmaps)
        fresh, seen = [], set()
        for bm in bitmaps:
            e = self._entries.get(id(bm))
            if e is not None and e.version == bm._version:
                continue
            for c in bm.containers:
                ci = id(c)
                if ci not in self._row_of and ci not in seen:
                    seen.add(ci)
                    fresh.append(c)
        if fresh:
            rows = C.containers_to_word_rows(fresh)
            ids = [self._alloc() for _ in fresh]
            self._host[np.asarray(ids)] = rows
            for c, rid in zip(fresh, ids):
                self._row_of[id(c)] = rid
                self._ref[rid] = 0              # adopt() bumps it below
            self.stats.rows_promoted += len(fresh)
            self._note_dirty(ids)
        for bm in bitmaps:
            self.adopt(bm)
        return len(fresh)

    def revalidate(self) -> int:
        """Re-adopt every registered bitmap whose version moved (the
        query server's ``slab_mismatch`` rung).  Returns rows patched."""
        return sum(self.adopt(e.bm) for e in list(self._entries.values()))

    def release(self, bm) -> None:
        """Drop ``bm`` from the arena, freeing rows not shared with
        other registered bitmaps."""
        e = self._entries.pop(id(bm), None)
        if e is None:
            return
        for c in e.conts.values():
            self._release_cont(c)

    def _register_cont(self, c) -> int:
        rid = self._row_of.get(id(c))
        if rid is not None:
            self._ref[rid] += 1
            return rid
        rid = self._alloc()
        self._host[rid] = C.container_words64(c)
        self._row_of[id(c)] = rid
        self._ref[rid] = 1
        self.stats.rows_promoted += 1
        self._note_dirty([rid])
        return rid

    def _release_cont(self, c) -> None:
        rid = self._row_of.get(id(c))
        if rid is None:
            return
        self._ref[rid] -= 1
        if self._ref[rid] == 0:
            del self._ref[rid]
            del self._row_of[id(c)]
            self._free.append(rid)
            self.stats.rows_freed += 1

    def _note_dirty(self, ids) -> None:
        """Record host-mirror edits against every materialized device
        view: the single-device slab's dirty list AND (when the arena is
        in per-shard slab mode) the owning shard's pending set.  Views
        that were never uploaded skip the bookkeeping -- their first
        build reads the whole host mirror anyway."""
        if self._dev is not None:
            self._dirty.extend(ids)
        if self._shards is not None:
            self._shards.note_many(ids)

    def _alloc(self) -> int:
        if self._free:
            return self._free.pop()
        if self._n == self._host.shape[0]:
            self._grow()
        rid = self._n
        self._n += 1
        return rid

    def _grow(self) -> None:
        cap = self._host.shape[0] * 2
        host = np.zeros((cap, 1024), np.uint64)
        host[: self._n] = self._host[: self._n]
        self._host = host
        if self._dev is not None:
            # Grow on-device: existing rows never cross PCIe again.
            pad = jnp.zeros((cap - self._dev.shape[0], WORDS), jnp.uint32)
            self._dev = jnp.concatenate([self._dev, pad])

    # -- host/device views --------------------------------------------

    def host_row(self, rid: int) -> np.ndarray:
        """(1024,) uint64 view of one row in the host mirror."""
        return self._host[int(rid)]

    def host_rows(self, ids) -> np.ndarray:
        """Gather ``ids`` rows from the host mirror (copy).  Same bytes
        as re-promoting the containers, so host twins stay bit-identical
        without re-running promotion."""
        return self._host[np.asarray(ids, np.int64)]

    def device_slab(self):
        """The resident ``(capacity, 2048)`` uint32 slab, uploading lazily
        on first call and flushing pending edits in ONE scatter after.

        The scatter is a functional update (fresh buffer): dispatches
        already in flight keep their captured slab -- copy-on-write.
        """
        if self._dev is None:
            # copy=True: on CPU backends jnp.asarray may ALIAS numpy
            # memory zero-copy, and an aliased slab would mutate under
            # in-flight consumers whenever the host mirror is edited --
            # exactly the copy-on-write contract this class documents.
            self._dev = jnp.array(
                self._host.view(np.uint32).reshape(-1, WORDS), copy=True)
            self.stats.rows_uploaded += self._n
            self._dirty = []
        elif self._dirty:
            ids = np.array(sorted(set(self._dirty)), np.int32)
            rows = np.ascontiguousarray(self._host[ids])
            rows32 = rows.view(np.uint32).reshape(len(ids), WORDS)
            self._dev = self._dev.at[jnp.asarray(ids)].set(
                jnp.asarray(rows32))
            self.stats.rows_uploaded += len(ids)
            self.stats.rows_patched += len(ids)
            self._dirty = []
        return self._dev

    def sync(self) -> None:
        """Flush pending patches (uploading the slab if it never was)
        and block until the device copy is ready (benchmark fencing).
        When the arena is in per-shard slab mode the shard slabs are
        fenced too."""
        self.device_slab().block_until_ready()
        if self._shards is not None:
            self._shards.sync()

    # -- per-shard slab mode -------------------------------------------

    def shard_slabs(self, mesh=None) -> "ShardSlabs":
        """Per-shard slab mode: the arena's rows round-robined across the
        devices of a 1-D ``("wide",)`` mesh (row ``r`` lives on shard
        ``r % S`` at local index ``r // S``), host mirror still
        authoritative, CoW patching per shard.

        The first call stripes the host mirror into ``S`` device-local
        slabs (one upload per shard); later calls return the same
        :class:`ShardSlabs`, flushing host edits shard-by-shard (only
        shards owning dirty rows pay a scatter).  Passing a different
        mesh rebuilds.  ``mesh=None`` resolves through the installed
        wide mesh (``dist.ctx.resolve_wide``)."""
        from repro.dist import ctx
        mesh, size, axis = ctx.resolve_wide(mesh)
        if mesh is None:
            raise ValueError("shard_slabs needs a mesh (none installed)")
        if self._shards is None or self._shards.mesh != mesh:
            self._shards = ShardSlabs(self, mesh, size, axis)
        return self._shards


class ShardSlabs:
    """Round-robin per-shard device slabs over a 1-D mesh -- the arena
    scale-out mode behind the sharded ``SimilarityEngine`` path.

    Layout (docs/MEMORY.md "Per-shard slab layout"):

    * global row ``r`` -> shard ``r % S``, local index ``r // S`` (the
      wide-aggregate round-robin, so the mapping never changes when the
      arena grows -- growth only pads each shard with device-local
      zeros, existing rows never cross PCIe again);
    * each shard holds a ``(cap_s, 2048)`` uint32 slab committed to its
      mesh device, ``cap_s = ceil(capacity / S)``;
    * :meth:`assembled` presents the ``S`` slabs as ONE global
      ``(S * cap_s, 2048)`` jax array sharded over the mesh axis --
      metadata-only assembly (``make_array_from_single_device_arrays``),
      no copies -- that ``shard_map`` splits back into the per-shard
      slabs, each indexed by local row (:meth:`locate`);
    * host edits batch into per-shard CoW scatters: only shards owning
      dirty rows re-patch, each in ONE functional ``.at[].set`` (in-
      flight dispatches keep their captured slabs).

    ``stats[s]`` is a per-shard :class:`ArenaStats`: shard uploads and
    patches are accounted *here*, not in the arena's global stats (which
    keep tracking the single-device slab) -- the warm-query zero-PCIe
    assertions sum these counters.
    """

    def __init__(self, arena: BitmapArena, mesh, size: int, axis: str):
        self.arena = arena
        self.mesh = mesh
        self.size = int(size)
        self.axis = axis
        self.cap_s = 0
        self._devs: list | None = None       # per-shard (cap_s, WORDS) u32
        self._assembled = None               # cached global sharded view
        self._pending: set[int] = set()      # global rows dirty since flush
        self.stats = [ArenaStats() for _ in range(self.size)]

    def note_many(self, ids) -> None:
        """Mark global rows dirty (called by the arena on host edits)."""
        if self._devs is not None:
            self._pending.update(int(r) for r in ids)

    def _devices(self):
        return list(self.mesh.devices.reshape(-1))

    def _ensure(self) -> None:
        """Build the per-shard slabs on first use; afterwards grow
        (device-local zero padding) and flush pending rows (per-shard
        CoW scatters)."""
        import jax
        S = self.size
        host = self.arena._host
        need = -(-host.shape[0] // S)
        if self._devs is None:
            devs = self._devices()
            self._devs = []
            for s in range(S):
                block = np.zeros((need, 1024), np.uint64)
                rows_s = host[s::S]
                block[: rows_s.shape[0]] = rows_s
                self._devs.append(jax.device_put(
                    block.view(np.uint32).reshape(-1, WORDS), devs[s]))
                self.stats[s].rows_uploaded += max(
                    0, -(-(self.arena._n - s) // S))
            self.cap_s = need
            self._pending.clear()
            self._assembled = None
            return
        if need > self.cap_s:
            devs = self._devices()
            for s in range(S):
                pad = jax.device_put(
                    jnp.zeros((need - self.cap_s, WORDS), jnp.uint32),
                    devs[s])
                self._devs[s] = jnp.concatenate([self._devs[s], pad])
            self.cap_s = need
            self._assembled = None
        if self._pending:
            devs = self._devices()
            by_shard: dict[int, list[int]] = {}
            for r in self._pending:
                by_shard.setdefault(r % S, []).append(r)
            for s, rids in by_shard.items():
                rids = np.array(sorted(rids), np.int64)
                rows32 = np.ascontiguousarray(
                    host[rids]).view(np.uint32).reshape(len(rids), WORDS)
                self._devs[s] = self._devs[s].at[
                    jnp.asarray(rids // S, jnp.int32)].set(
                        jax.device_put(rows32, devs[s]))
                self.stats[s].rows_uploaded += len(rids)
                self.stats[s].rows_patched += len(rids)
            self._pending.clear()
            self._assembled = None

    def assembled(self):
        """The global ``(S * cap_s, 2048)`` uint32 slab, sharded over the
        mesh axis -- zero-copy metadata assembly of the per-shard slabs,
        flushed first.  Pass it to ``shard_map`` with ``P(axis)``: each
        shard then sees its own ``(cap_s, 2048)`` slab."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec
        self._ensure()
        if self._assembled is None:
            sharding = NamedSharding(self.mesh, PartitionSpec(self.axis))
            self._assembled = jax.make_array_from_single_device_arrays(
                (self.size * self.cap_s, WORDS), sharding, self._devs)
        return self._assembled

    def locate(self, ids):
        """``(shard, local index)`` of global rows ``ids`` (numpy), after
        building/flushing the slabs.  Sharded dispatches gather each
        shard's rows from its OWN slab by local index inside
        ``shard_map``; a gather from :meth:`assembled` outside it would
        make XLA all-gather every shard's slab onto every device."""
        self._ensure()
        ids = np.asarray(ids, np.int64)
        return ids % self.size, ids // self.size

    def shard_slab(self, s: int):
        """Shard ``s``'s ``(cap_s, 2048)`` slab (flushed)."""
        self._ensure()
        return self._devs[s]

    def sync(self) -> None:
        """Flush every shard and block (benchmark fencing)."""
        self._ensure()
        for d in self._devs:
            d.block_until_ready()
