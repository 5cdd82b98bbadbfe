"""Device-resident session-slot arena — the serving runtime's hot state.

Every admitted session owns one *slot*: a fixed row of pre-allocated batched
KV-cache/position arrays (`cache`, every leaf stacked over a leading
capacity axis) and of the cut-activation staging buffer (`xbuf`). The slot
is assigned at admission and never moves while the session is resident, so
the serve loop's per-flush work is: scatter-decode the flush's payloads into
`xbuf[slots]` on device, run ONE jitted top step over the whole arena with
an active-slot mask, read the token rows back. Nothing per-session is
stacked, unstacked, or pulled to host — the O(sessions x cache bytes) of
per-flush `jnp.stack`/`a[i]` memcpy the pre-arena server paid per token is
gone, and with buffer donation the step updates the arena in place.

With a device `mesh`, the arena rows shard over every mesh axis (slot ->
shard mapping and the full layout story in docs/sharding.md): capacity is
padded up to a multiple of the device count so each shard holds the same
row count, `cache` leaves carry a `NamedSharding` over the flattened mesh
axes, and `xbuf` is allocated replicated (it is the small per-flush staging
buffer; the KV arena is the HBM term that must scale). `mesh=None` is
bit-identical to the pre-mesh single-device arena.

Aliasing/donation invariants (also in docs/performance.md):

  * `cache` and `xbuf` handles are CONSUMED by the donated jits
    (`steps.make_arena_top_step`, `protocol.server_decode_to_slots`); the
    owner must always rebind the returned arrays and never keep a stale
    reference across a flush.
  * `xbuf` has `capacity + 1` rows: row `capacity` is the scratch row that
    group padding scatters into (a cached zero row, NEVER an alias of a
    live session's data), keeping one compile per payload meta regardless
    of flush fill.
  * the top step writes each active slot's new K/V entries in place and
    drops an inactive slot's write, so inactive slots pass through it
    unchanged and stale `xbuf` rows from earlier flushes are never
    observable.

Slot lifecycle is owned by the server (admission, closed-slot reclaim, LRU
eviction of idle sessions to host, re-admission restore — see
`runtime.server`); every arena mutation (`reset_slot`, `restore_slot`,
`fetch_slot`) must only run from the thread that owns the donated step, so
row writes are serialized with the step, never raced against it from a
reader thread. The arena itself holds only the device state.
"""
from __future__ import annotations

import functools
import warnings
from typing import Any

import jax
import jax.numpy as jnp

# donation is a no-op on the CPU backend (jax warns once per compile);
# the arena is designed for TPU where it aliases in place
warnings.filterwarnings("ignore",
                        message="Some donated buffers were not usable")


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_slot(cache, row, slot):
    """Write one batch-1 cache pytree into one arena row (donated). Serves
    both the fresh-template reset and the eviction-restore write — same
    program, different `row` operand."""
    return jax.tree.map(lambda a, t: a.at[slot].set(t), cache, row)


class SlotArena:
    """Pre-allocated per-session serving state, resident on device.

    `make_cache() -> batch-1 cache pytree` defines one slot's state;
    `x_shape`/`x_dtype` the per-slot cut-activation row. Slot id assignment
    lives with the owning server (it is session bookkeeping); the arena
    holds the device arrays and the row-write primitives, which must only
    run from the thread that owns the donated step (see module docstring).

    `capacity` is the padded row count (requested capacity rounded up to a
    multiple of the mesh device count); the server admits at most
    `requested_capacity` sessions and the pad rows stay permanently
    inactive under the step's mask.
    """

    def __init__(self, make_cache, capacity: int, x_shape, x_dtype,
                 mesh=None):
        assert capacity >= 1
        self.mesh = mesh
        n_dev = int(mesh.devices.size) if mesh is not None else 1
        self._n_pod = (dict(mesh.shape).get("pod", 1)
                       if mesh is not None else 1)
        self.requested_capacity = capacity
        self.capacity = -(-capacity // n_dev) * n_dev
        self._template = make_cache()
        stacked = jax.tree.map(lambda a: jnp.stack([a] * self.capacity),
                               self._template)
        # +1: the scratch row that padded decode groups scatter into
        xbuf = jnp.zeros((self.capacity + 1,) + tuple(x_shape), x_dtype)
        if mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            axes = tuple(mesh.axis_names)
            rows = axes if len(axes) > 1 else axes[0]
            self.cache = jax.tree.map(
                lambda a: jax.device_put(
                    a, NamedSharding(mesh,
                                     P(rows, *([None] * (a.ndim - 1))))),
                stacked)
            # replicated: its +1 scratch row defeats row sharding, and the
            # step's shard_map reshards the `capacity` live rows anyway
            self.xbuf = jax.device_put(xbuf, NamedSharding(mesh, P()))
        else:
            self.cache = stacked
            self.xbuf = xbuf

    def wire_row(self, slot: int) -> int:
        """The `xbuf`/token row for a slot: identity without a pod axis;
        with one, the slot's ingestion-pod block — the sharded step's
        ppermute pair carries the activation row to the slot's (ring-next)
        label pod and the token row back (docs/sharding.md)."""
        if self._n_pod <= 1 or slot >= self.capacity:
            return slot
        block = self.capacity // self._n_pod
        pod, off = divmod(slot, block)
        return ((pod - 1) % self._n_pod) * block + off

    def reset_slot(self, slot: int) -> None:
        """Restore one row to the fresh-session template (slot reuse after
        a session closed). Must only run from the thread that owns the
        donated step — it consumes and rebinds `cache`."""
        self.cache = _write_slot(self.cache, self._template,
                                 jnp.asarray(slot, jnp.int32))

    def fetch_slot(self, slot: int) -> Any:
        """Host copy of one slot's cache row — the eviction path (the
        session's device state moves to `Session.host_state`). Same
        serialization rule as `reset_slot`: serve-loop thread only, the
        read must not race a donated step consuming `cache`."""
        return jax.tree.map(lambda a: jax.device_get(a[slot]), self.cache)

    def restore_slot(self, slot: int, state: Any) -> None:
        """Write an evicted session's host state back into a (possibly
        different) arena row — the re-admission path. Shares `_write_slot`
        with `reset_slot`, so no extra program compiles."""
        row = jax.tree.map(jnp.asarray, state)
        self.cache = _write_slot(self.cache, row,
                                 jnp.asarray(slot, jnp.int32))

    def release(self) -> None:
        """Drop the device arrays (`cache`, `xbuf`) once the serve loop
        has stopped; the arena is unusable afterwards."""
        self.cache = self.xbuf = None

    def slot_cache(self, slot: int) -> Any:
        """Host copy of one slot's cache row (tests/debug only — the serve
        path never unstacks a slot)."""
        return jax.tree.map(lambda a: jax.device_get(a[slot]), self.cache)
