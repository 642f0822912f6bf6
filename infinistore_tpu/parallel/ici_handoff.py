"""ICI intra-pod KV handoff: device-to-device page transfer over the mesh.

This is the third transport of SURVEY.md §2's TPU-native mapping (next to
the SHM/host-DMA path and the DCN/STREAM path): when prefill and decode
engines live in the SAME pod, KV pages should move chip-to-chip over ICI
with a collective, never bouncing through host DRAM or DCN. The
reference-side analogue being replaced is the GPUDirect path
(/root/reference/infinistore/lib.py:244-251,
/root/reference/src/libinfinistore.cpp:1166-1201) — RDMA directly between
device memories.

Design (store-keyed, SPMD):

- ``IciKVPool`` owns ONE jax.Array of KV pages sharded over a mesh axis:
  global shape [n_devices * slots_per_device, *page_shape], sharding
  ``P(axis)`` — each device holds ``slots_per_device`` local page slots
  (plus one hidden scratch slot that absorbs transfer padding).
- A host-side directory maps content keys → (device, slot), mirroring the
  store's kv index; ``match_last_index`` gives the same longest-prefix
  probe the store serves (infinistore.cpp:1092-1108) so an engine can ask
  "how much of this sequence is already resident in-pod".
- ``handoff(moves)`` relocates keyed pages between devices with
  ``shard_map`` + ``lax.ppermute``: every source concatenates its
  outgoing slots into a fixed-width buffer, one collective permute moves
  all (src → dst) routes of a round at once, receivers scatter into their
  free slots (padding lands in the scratch slot). ppermute requires each
  device to appear at most once as source and once as destination per
  collective, so moves are greedily scheduled into matching rounds — the
  steady disaggregation pairing (prefill chip i → decode chip j) is one
  round.
- Transfers are jitted per (n_xfer, perm) shape and cached — a steady
  prefill→decode pairing compiles once and reuses the executable.

The pool composes with the host store (``tpu.TpuKVStore``) as a faster
tier (the reference's tier layering: GPU memory over the DRAM pool,
infinistore.cpp:570-804): :meth:`IciKVPool.fetch_from_store` pulls
missing pages store → pool on a miss, and :meth:`evict_to_store` spills
resident pages pool → store and frees their slots. The handoff itself
never touches the host.

**Directory consistency (multi-process SPMD contract).** The directory
and free lists are HOST-side replicated state: in a multi-process SPMD
deployment (one process per host, jax.distributed) every process holds
its own copy and must execute the SAME sequence of directory-mutating
calls (``put`` / ``drop`` / ``handoff`` / ``fetch_from_store`` /
``evict_to_store``) with the same arguments — exactly the discipline
jax already imposes for the collectives these calls launch (a ppermute
only runs when every process enters it). All mutation is deterministic
given the call sequence (free lists are stacks; rounds are scheduled in
sorted order), so identical call sequences yield identical directories
with no cross-process protocol. The host store is the cross-process
rendezvous for page *bytes*: ``fetch_from_store`` has every process read
the same committed pages from the (shared) store, so the injected
content is globally consistent too; a store fetched from concurrently is
safe because committed pages are immutable (first-writer-wins).
"""

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_pool_mesh(n_devices, axis="pool", devices=None):
    """1-D mesh over the pod's chips; prefill and decode occupy disjoint
    ranges of the same axis so the handoff rides ICI."""
    if devices is None:
        devices = jax.devices()[:n_devices]
    return Mesh(np.asarray(devices), axis_names=(axis,))


class IciKVPool:
    """Store-keyed KV page pool resident across a mesh axis.

    Parameters:
        mesh: 1-D (or sliced) Mesh; the pool shards over ``axis``.
        page_shape / dtype: one KV page's shape and dtype (uniform, like
            the store's fixed block size).
        slots_per_device: page capacity per chip.
    """

    def __init__(self, mesh, page_shape, dtype, slots_per_device,
                 axis="pool"):
        self.mesh = mesh
        self.axis = axis
        self.n_dev = mesh.shape[axis]
        self.page_shape = tuple(page_shape)
        self.dtype = jnp.dtype(dtype)
        self.slots = int(slots_per_device)
        # +1 hidden scratch slot per device: transfer padding and
        # non-participating receivers scatter there instead of clobbering
        # live pages.
        self._local = self.slots + 1
        self._sharding = NamedSharding(mesh, P(axis))
        self.buffer = jax.device_put(
            jnp.zeros((self.n_dev * self._local, *self.page_shape),
                      dtype=self.dtype),
            self._sharding,
        )
        self.directory = {}  # key -> (device, slot)
        self._free = [list(range(self.slots)) for _ in range(self.n_dev)]
        self._xfer_cache = {}

    # -- directory (the store-keyed surface) ---------------------------

    def check_exist(self, key):
        return key in self.directory

    def match_last_index(self, keys):
        """Longest resident prefix — the in-pod twin of the store's
        get_match_last_index probe."""
        last = -1
        for i, k in enumerate(keys):
            if k not in self.directory:
                break
            last = i
        return last

    def device_of(self, key):
        return self.directory[key][0]

    def free_slots(self, device):
        return len(self._free[device])

    def _global_slot(self, device, slot):
        return device * self._local + slot

    # -- page injection / extraction -----------------------------------

    def put(self, keys, pages, device):
        """Host-injection path: place ``pages`` ([n, *page_shape]) under
        ``keys`` on ``device``. (The hot prefill path writes pages from
        on-device compute instead; this is the restore-from-host-store /
        test path.) First-writer-wins like the store: existing keys are
        skipped."""
        pages = jnp.asarray(pages, dtype=self.dtype)
        take = [i for i, k in enumerate(keys) if k not in self.directory]
        if not take:
            return
        if len(take) > len(self._free[device]):
            raise MemoryError(
                f"device {device}: {len(take)} pages > "
                f"{len(self._free[device])} free slots"
            )
        slots = [self._free[device].pop() for _ in take]
        gidx = jnp.asarray(
            [self._global_slot(device, s) for s in slots], dtype=jnp.int32
        )
        self.buffer = _scatter_pages(self.buffer, gidx, pages[jnp.asarray(take)])
        for i, s in zip(take, slots):
            self.directory[keys[i]] = (device, s)

    def get(self, keys):
        """Gather pages for ``keys`` (any placement) as one [n, *page]
        device array (cross-device gather compiles to XLA collectives)."""
        gidx = jnp.asarray(
            [self._global_slot(*self.directory[k]) for k in keys],
            dtype=jnp.int32,
        )
        return self.buffer[gidx]

    def drop(self, keys):
        """Release keys' slots (pages become garbage; directory is the
        source of truth, like BlockRef release in the host store)."""
        for k in keys:
            dev, slot = self.directory.pop(k)
            self._free[dev].append(slot)

    # -- host-store tiering (store <-> pool) ----------------------------

    def fetch_from_store(self, store, keys, device):
        """Pool-miss path: pull the pages of ``keys`` that are not
        resident from the host store (:class:`tpu.TpuKVStore`) into this
        pool on ``device``. Returns the number fetched. The engine's
        miss flow is ``match_last_index`` (pool) → ``cached_prefix_len``
        (store) → fetch → :meth:`handoff` to wherever decode runs —
        the reference's GPU-over-DRAM tier layering
        (infinistore.cpp:570-804) with ICI as the upper tier."""
        missing = [k for k in keys if k not in self.directory]
        if not missing:
            return 0
        if len(missing) > len(self._free[device]):
            raise MemoryError(
                f"device {device}: fetching {len(missing)} pages > "
                f"{len(self._free[device])} free slots"
            )
        # Fetch to HOST (one copy out of the pinned pool, no intermediate
        # device commit — a committed single-device array cannot feed the
        # sharded scatter) and inject; the scatter's compiled executable
        # owns the single host→device placement of the rows.
        pages = store.get_kv_pages_host(missing, self.page_shape, self.dtype)
        self.put(missing, pages, device)
        return len(missing)

    def evict_to_store(self, store, keys, sync=True):
        """Spill resident ``keys`` to the host store and release their
        pool slots (the pool's analogue of the server's DRAM→SSD spill).
        Store dedup is first-writer-wins, so re-evicting a key the store
        already holds is a no-op there but still frees the slot here.
        Returns the number spilled."""
        present = [k for k in keys if k in self.directory]
        if not present:
            return 0
        pages = self.get(present)
        if getattr(pages, "is_fully_addressable", True) is False:
            # Multi-process mesh: this process only holds its shards;
            # gather the full pages, then have ONE designated writer
            # commit them (N identical dedup'd writes would be wasted
            # rpc load) and barrier before anyone proceeds — without
            # the barrier a non-writer could drop its pool slots and
            # immediately fetch_from_store BEFORE the writer's commit
            # is visible, and the resulting one-sided miss would
            # desynchronize the SPMD replay at the next collective.
            # (sync=False is not honored here: the barrier needs the
            # committed state. Symmetric writes would NOT remove the
            # barrier: a process whose allocate dedups to FAKE writes
            # nothing, so its own sync says nothing about the winner's
            # commit.) The barrier doubles as the writer's status
            # broadcast: on a failed put EVERY process raises before any
            # directory mutation, so replicated directories never
            # diverge — instead of the non-writers hanging forever while
            # the writer unwinds.
            from jax.experimental import multihost_utils

            import jax as _jax

            pages = multihost_utils.process_allgather(pages, tiled=True)
            ok = 1
            if _jax.process_index() == 0:
                try:
                    store.put_kv_pages(present, pages, sync=True)
                except Exception:
                    ok = 0
            flags = multihost_utils.process_allgather(
                jnp.asarray([ok], dtype=jnp.int32), tiled=True
            )
            if int(jnp.min(flags)) == 0:
                raise RuntimeError(
                    "evict_to_store: designated writer failed to commit; "
                    "pool slots retained on every process"
                )
        else:
            store.put_kv_pages(present, pages, sync=sync)
        self.drop(present)
        return len(present)

    # -- the ICI handoff ------------------------------------------------

    def handoff(self, moves):
        """Relocate keyed pages device-to-device over ICI.

        ``moves``: {key: dst_device}. Pages move from their current
        device (directory lookup) to ``dst_device`` via one
        shard_map+ppermute per scheduling round. jax ppermute requires
        source AND destination to be unique within one collective, so
        routes are greedily scheduled into rounds that form a matching
        (the common disaggregation pairing — prefill chip i feeding
        decode chip j — is a single round). The directory and free lists
        are updated; data moves entirely on-device.
        """
        # Group by (src, dst) route.
        routes = {}
        for key, dst in moves.items():
            src, slot = self.directory[key]
            if src == dst:
                continue
            routes.setdefault((src, dst), []).append((key, slot))
        while routes:
            # One round: each device at most once as source and once as
            # destination (ppermute uniqueness on both sides).
            round_routes = {}
            used_src = set()
            for (src, dst), items in list(routes.items()):
                if dst not in round_routes and src not in used_src:
                    round_routes[dst] = (src, items)
                    used_src.add(src)
                    del routes[(src, dst)]
            self._handoff_round(round_routes)

    def _handoff_round(self, round_routes):
        """round_routes: {dst: (src, [(key, src_slot), ...])}."""
        # Within a round each source serves exactly one destination, so
        # the transfer width is the largest route's item count; shorter
        # routes pad with the scratch slot on both ends.
        n_xfer = max(len(items) for _src, items in round_routes.values())
        perm = tuple(
            sorted((src, dst) for dst, (src, _) in round_routes.items())
        )
        scratch = self.slots  # hidden slot index (local)
        send = np.full((self.n_dev, n_xfer), scratch, dtype=np.int32)
        recv = np.full((self.n_dev, n_xfer), scratch, dtype=np.int32)
        fills = {}  # src -> next free position in its send row
        placements = []  # (dst, key, position)
        for dst, (src, items) in sorted(round_routes.items()):
            for key, src_slot in items:
                pos = fills.get(src, 0)
                fills[src] = pos + 1
                send[src, pos] = src_slot
                placements.append((dst, key, pos))
        # Destination slot assignment.
        new_loc = {}
        for dst, key, pos in placements:
            if not self._free[dst]:
                raise MemoryError(f"device {dst} has no free slots")
            slot = self._free[dst].pop()
            recv[dst, pos] = slot
            new_loc[key] = (dst, slot)

        fn = self._xfer_fn(n_xfer, perm)
        send_d = jax.device_put(send, self._sharding)
        recv_d = jax.device_put(recv, self._sharding)
        self.buffer = fn(self.buffer, send_d, recv_d)

        # Commit directory updates; old slots become free.
        for key, (dst, slot) in new_loc.items():
            src, old_slot = self.directory[key]
            self.directory[key] = (dst, slot)
            self._free[src].append(old_slot)

    def _xfer_fn(self, n_xfer, perm):
        key = (n_xfer, perm)
        fn = self._xfer_cache.get(key)
        if fn is None:
            fn = _build_xfer(self.mesh, self.axis, perm, self._sharding)
            self._xfer_cache[key] = fn
        return fn


@partial(jax.jit, donate_argnums=(0,))
def _scatter_pages(buffer, gidx, pages):
    return buffer.at[gidx].set(pages)


def _build_xfer(mesh, axis, perm, sharding):
    """Jitted one-round transfer: gather send slots, ppermute, scatter
    into recv slots. Padding and non-receivers target the scratch slot,
    so live pages are never clobbered."""

    def local_xfer(local_pages, send_slots, recv_slots):
        # local_pages: [local_slots, *page]; send/recv_slots: [1, n_xfer]
        out = jax.lax.ppermute(
            local_pages[send_slots[0]], axis, perm
        )  # zeros on devices not a destination of `perm`
        return local_pages.at[recv_slots[0]].set(out)

    smapped = shard_map(
        local_xfer,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis)),
        out_specs=P(axis),
    )
    return jax.jit(smapped, donate_argnums=(0,))


__all__ = ["IciKVPool", "make_pool_mesh"]
