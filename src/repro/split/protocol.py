"""Cut-layer transfer protocol — one generic encode/transfer/decode path.

Two entry points, one codec:

  * `cut_boundary` — the fused in-graph path (encode -> ppermute the payload
    leaves across the 'pod' mesh axis -> decode), used by `split.model`
    inside jit, with the payload-typed backward wire attached via custom VJP.
  * `client_encode` / `server_decode` — the same two halves exposed for
    out-of-process use: a feature owner that holds only the bottom model
    encodes its cut activation to a host-side `Payload` (ready for
    `core.wire.encode_payload_frame`), and a label owner decodes a received
    payload to the dense view without ever seeing the compressor object.
    `repro.runtime`'s streaming client/server is built on these halves.

Placement, the symmetrized-SPMD mapping of the two parties onto the two
pods, and the forward/backward wire-size rules (Table 2) are specified in
docs/protocol.md — the normative companion of this module.
"""
from __future__ import annotations

import contextlib
import functools
import threading

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import compressors, selection
from repro.core.payload import Payload, PayloadMeta
from repro.models.config import ArchConfig, Runtime, SplitConfig


def make_cut_compressor(sc: SplitConfig) -> compressors.Compressor:
    """Config -> codec object (factory; the protocol itself is generic)."""
    kw = {}
    if sc.compressor in ("topk", "randtopk", "randtopk_quant",
                         "randtopk_mask", "size_reduction"):
        kw["k"] = sc.k
    if sc.compressor in ("randtopk", "randtopk_quant", "randtopk_mask"):
        kw["alpha"] = sc.alpha
    if sc.compressor in ("quant", "randtopk_quant"):
        kw["bits"] = sc.quant_bits
    if sc.compressor == "l1":
        kw["lam"] = sc.l1_lam
    if sc.backend is not None:
        kw["backend"] = sc.backend
    return compressors.make_compressor(sc.compressor, **kw)


def pod_ring_perm(n_pod: int, *, inverse: bool = False):
    """The cut-boundary ring permutation along the 'pod' axis.

    Forward sends pod i's leaves to pod i+1 (mod n); inverse returns them.
    Shared by `_pod_permute` (the in-graph training transfer) and the
    sharded serving step (`runtime.steps.make_arena_top_step` with a
    pod-axis mesh), so both paths carry the identical collective schedule.
    """
    step = -1 if inverse else 1
    return [(i, (i + step) % n_pod) for i in range(n_pod)]


def _pod_permute(rt: Runtime, *leaves, inverse: bool = False):
    """ppermute every array along the pod axis (0 <-> 1).

    `inverse=True` applies the inverse permutation (used by the backward
    wire so cotangents return to the pod that produced the activation).
    """
    mesh = rt.mesh
    if mesh is None or "pod" not in mesh.axis_names or mesh.shape["pod"] < 2:
        return leaves
    perm = pod_ring_perm(mesh.shape["pod"], inverse=inverse)

    def spec_for(a):
        # batch axis is dim 0, sharded over (pod, data); rest replicated/model
        return P(("pod", "data"), *([None] * (a.ndim - 1)))

    def body(*xs):
        return tuple(jax.lax.ppermute(x, "pod", perm) for x in xs)

    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=tuple(spec_for(a) for a in leaves),
        out_specs=tuple(spec_for(a) for a in leaves),
    )(*leaves)
    return out


def _transfer_payload(rt: Runtime, p: Payload, inverse: bool = False) -> Payload:
    """Move every wire leaf of a payload across the pod boundary."""
    names = [n for n, _ in p.wire_leaves()]
    arrs = _pod_permute(rt, *[a for _, a in p.wire_leaves()], inverse=inverse)
    return p.with_leaves(**dict(zip(names, arrs)))


# ---------------------------------------------------------------------------
# Backward wire rules, dispatched on the payload kind (not the compressor).
# ---------------------------------------------------------------------------

def _grad_to_wire(kind: str, g, idx_far, k: int):
    """Label-owner side: the gradient leaves that cross back (Table 2 bwd)."""
    if kind in ("sparse", "sparse_quant"):
        return jnp.take_along_axis(g, idx_far.astype(jnp.int32), axis=-1)
    if kind == "mask":
        # idx_far = the packed support bitmask words; gather the k supported
        # gradient values in ascending-index order (the mask payload's value
        # order, so the feature owner can expand with the same mask)
        mask = selection.unpack_mask_words(idx_far, g.shape[-1])
        idx = jnp.argsort(~mask, axis=-1, stable=True)[..., :k]
        return jnp.take_along_axis(g, idx, axis=-1)
    if kind == "slice":
        return g[..., :k]
    return g  # dense / quant: full-precision dense gradient


def _grad_from_wire(kind: str, gw, idx_local, d: int):
    """Feature-owner side: route the wire gradient onto the activation.

    Sparse/slice/mask kinds scatter onto the forward support (the paper's
    same-mask backward); dense/quant kinds are the identity (STE)."""
    if kind in ("sparse", "sparse_quant"):
        out = jnp.zeros(gw.shape[:-1] + (d,), gw.dtype)
        return jnp.put_along_axis(out, idx_local.astype(jnp.int32), gw,
                                  axis=-1, inplace=False)
    if kind == "mask":
        # idx_local = packed support words; mask-driven expand, ascending
        return compressors.mask_expand_rows(gw, idx_local, d)
    if kind == "slice":
        pad = [(0, 0)] * (gw.ndim - 1) + [(0, d - gw.shape[-1])]
        return jnp.pad(gw, pad)
    return gw


def _transport(comp: compressors.Compressor, x, rt: Runtime, key,
               over_pod: bool):
    """encode -> ppermute payload leaves -> decode, with the payload-typed
    backward wire attached via custom VJP."""
    kind = comp.wire_kind
    d = x.shape[-1]
    k_eff = min(getattr(comp, "k", 0), d)

    def _encode_transfer(x):
        p = comp.encode(x, key=key, training=rt.training)
        pt = _transfer_payload(rt, p) if over_pod else p
        return p, pt

    @jax.custom_vjp
    def run(x):
        _, pt = _encode_transfer(x)
        return comp.decode(pt, shape=x.shape, dtype=x.dtype)

    def run_fwd(x):
        p, pt = _encode_transfer(x)
        y = comp.decode(pt, shape=x.shape, dtype=x.dtype)
        return y, (p.indices, pt.indices)

    def run_bwd(res, g):
        idx_local, idx_far = res
        gw = _grad_to_wire(kind, g, idx_far, k_eff)
        if over_pod:
            (gw,) = _pod_permute(rt, gw, inverse=True)
        return (_grad_from_wire(kind, gw, idx_local, d),)

    run.defvjp(run_fwd, run_bwd)
    return run(x)


# ---------------------------------------------------------------------------
# Out-of-process halves — the wire interface for parties that are NOT in the
# same jit program (streaming clients/servers, real sockets).
# ---------------------------------------------------------------------------

class HostDensifyCounter:
    """Registry-backed count of host-side dense materializations.

    Incremented by every `server_decode` call. The serving/training hot
    paths must keep it flat (they decode on device via
    `server_decode_device` / `server_decode_to_slots`), and it is read and
    written across server reader threads, the serve loop, and test threads
    — hence a locked counter, not a bare module global.

    The count itself lives in the process-wide metrics registry
    (`obs.registry.DEFAULT_REGISTRY`, metric `host_densify_total`) so it
    shows up in registry snapshots next to every other runtime metric;
    this class is the legacy surface over it. The registry metric stays
    monotonic (Prometheus counter semantics); `reset()` and `watch()` are
    implemented as baseline offsets on top of it.

    The registry binding happens at first use, not import: this module is
    imported by `runtime/server.py` while `repro.runtime.__init__` may be
    mid-execution, and pulling in `repro.obs` (which reaches
    `repro.testing` → `runtime.transport`) during *this* module's import
    would re-enter that cycle.

    Use `watch()` to pin a region flat (deprecated: new code should read
    `host_densify_total` from the registry snapshot instead; kept as a
    thin shim for existing callers)::

        with protocol.HOST_DENSIFY_COUNT.watch() as w:
            run_streaming(...)
        assert w.delta == 0

    `reset()` zeroes the counter and returns the prior value. `int(...)`
    and equality against ints keep one-off reads ergonomic.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._counter = None
        self._offset = 0

    def _bind(self):
        if self._counter is None:
            from repro.obs.registry import DEFAULT_REGISTRY
            self._counter = DEFAULT_REGISTRY.counter("host_densify_total")
        return self._counter

    @property
    def value(self) -> int:
        with self._lock:
            return int(self._bind().value) - self._offset

    def increment(self) -> None:
        self._bind().inc()

    def reset(self) -> int:
        with self._lock:
            total = int(self._bind().value)
            prior = total - self._offset
            self._offset = total
            return prior

    @contextlib.contextmanager
    def watch(self):
        # deprecated shim: prefer DEFAULT_REGISTRY.counter(
        # "host_densify_total").value deltas / registry snapshots
        outer = self

        class _Watch:
            start = outer.value

            @property
            def delta(self) -> int:
                return outer.value - self.start

        yield _Watch()

    def __int__(self) -> int:
        return self.value

    def __eq__(self, other) -> bool:
        # duck-typed: anything int()-able compares by count (this module
        # bans type-dispatch branches, pinned in tests/test_payload.py)
        try:
            return self.value == int(other)
        except (TypeError, ValueError):
            return NotImplemented

    def __repr__(self) -> str:
        return f"HostDensifyCounter({self.value})"


#: host-side dense materializations performed by `server_decode` — see
#: `HostDensifyCounter`; tests watch it around an engine run to pin "zero
#: host-side densification".
HOST_DENSIFY_COUNT = HostDensifyCounter()


def client_encode(comp: compressors.Compressor, x, *, key=None,
                  training: bool = False) -> Payload:
    """Feature-owner half: compress a cut activation to a host Payload.

    Returns the payload with numpy leaves, ready to be framed by
    `core.wire.encode_payload_frame` and put on a socket. The device-side
    `comp.encode` may be jitted by the caller; this helper just pulls the
    leaves to host afterwards.
    """
    import numpy as np

    p = comp.encode(x, key=key, training=training)
    return jax.tree.map(np.asarray, p)


def client_encode_device(comp: compressors.Compressor, x, *, key=None,
                         training: bool = False):
    """Device variant of `client_encode`: the wire bitstream is assembled
    on device (`kernels.encode.ops.pack_payload`), so the only host
    crossing is the final packed buffer(s) — no f32 dense pull, no numpy
    bit matrix.

    Returns `(payload, sections)`: `payload` keeps DEVICE leaves (the
    support leaf stays available for the training-direction grad decode
    without a dense pull), `sections` are the packed u32 buffers. Frame
    them with::

        body = enc_ops.sections_to_bytes(p.meta, p.batch_shape, sections)
        wire.encode_payload_frame_from_bytes(sid, seq, p.meta,
                                             p.batch_shape, body)

    When the backend resolves to Pallas (on-TPU default), the sparse /
    quant / mask kinds run the fused `kernels.encode` kernel (selection
    mask -> gather -> quantize -> pack in one pass); elsewhere the XLA
    `comp.encode` feeds the XLA bit-packer. Byte equality of the two
    paths with the host codec is pinned in tests/test_encode_kernels.py.
    """
    from repro.kernels.encode import ops as enc_ops

    kind = comp.wire_kind
    backend = selection._resolve_backend(comp.backend)
    if backend == "pallas" and kind in ("sparse", "sparse_quant", "mask",
                                        "quant", "slice"):
        d = x.shape[-1]
        k = min(getattr(comp, "k", 0) or 0, d)
        mask = (comp._mask(x, key, training)
                if kind in ("sparse", "sparse_quant", "mask") else None)
        p = enc_ops.encode_rows(x, kind, k=k,
                                bits=getattr(comp, "bits", 0), mask=mask)
    else:
        p = comp.encode(x, key=key, training=training)
    return p, enc_ops.pack_payload(p, backend=comp.backend)


def server_decode(p: Payload, *, dtype=None):
    """Label-owner half: dense (..., d) view of a received payload.

    Dispatches on `p.meta.kind` only (`compressors.payload_to_dense`) — the
    server needs no compressor object and no per-session codec state; the
    frame's subheader fully describes the payload.

    This is the *host-side* decode (counted in `HOST_DENSIFY_COUNT`): fine
    for warmup probes, tests, and one-off decodes. The serving/training hot
    loops use `server_decode_device` / `server_decode_to_slots` instead, so
    only the compressed wire leaves ever cross host->device.
    """
    HOST_DENSIFY_COUNT.increment()
    return compressors.payload_to_dense(p, dtype=dtype)


@functools.partial(jax.jit, static_argnames=("dtype", "backend"))
def _decode_device_jit(p: Payload, *, dtype: str, backend):
    return compressors.payload_to_dense(p, dtype=jnp.dtype(dtype),
                                        backend=backend)


def server_decode_device(p: Payload, *, dtype=None, backend=None):
    """`server_decode`, but the densification happens on device under jit.

    The host moves only the payload's wire leaves (k floats + packed
    indices, not the dense tensor) to the device; the scatter/dequant runs
    compiled (Pallas scatter kernel or XLA `put_along_axis` per `backend`).
    Jit caches by (meta, leaf shapes, dtype, backend) — one compile per
    distinct payload meta. Bit-identical to `server_decode`.
    """
    dt = jnp.dtype(dtype or jnp.float32).name
    return _decode_device_jit(p, dtype=dt, backend=backend)


def decode_to_slots_in_jit(xbuf, p: Payload, slots, *, dtype, backend,
                           mesh=None):
    """Trace-time body of the slot decode — shared by `_decode_to_slots_jit`
    and the serving runtime's fused decode+step program
    (`runtime.steps.make_fused_decode_step`), so both paths have identical
    numerics by construction. `backend="pallas"` runs the fused one-kernel
    path (dequant + scatter + slot placement in a single pass, xbuf aliased
    straight through the kernel); XLA decodes then scatters `xbuf[slots]`.

    With a device `mesh` (the sharded arena) xbuf and the flush payload
    are replicated over it, and the kernel runs once per device inside a
    `shard_map` on the replicated blocks: a Mosaic kernel is never
    partitioned by the compiler.
    """
    from repro.core import selection

    if selection._resolve_backend(backend) == "pallas":
        from repro.kernels.decode import ops as dec_ops

        if mesh is None:
            return dec_ops.decode_rows_to_slots(xbuf, p, slots)
        return jax.shard_map(dec_ops.decode_rows_to_slots, mesh=mesh,
                             in_specs=P(), out_specs=P(),
                             check_vma=False)(xbuf, p, jnp.asarray(slots))
    rows = compressors.payload_to_dense(p, dtype=jnp.dtype(dtype),
                                        backend=backend)
    return xbuf.at[slots].set(rows)


@functools.partial(jax.jit, static_argnames=("dtype", "backend", "mesh"),
                   donate_argnums=(0,))
def _decode_to_slots_jit(xbuf, p: Payload, slots, *, dtype: str, backend,
                         mesh):
    return decode_to_slots_in_jit(xbuf, p, slots, dtype=dtype,
                                  backend=backend, mesh=mesh)


def server_decode_to_slots(xbuf, p: Payload, slots, *, dtype=None,
                           backend=None, mesh=None):
    """Device/slot variant of `server_decode`: decode a *stacked* payload
    (leading batch axis = flush rows) and scatter the dense rows straight
    into `xbuf[slots]` — the serving arena's cut-activation buffer.

    `xbuf` is DONATED: the caller must treat its handle as consumed and keep
    the returned array (on TPU the update is in place; no (S, ..., d) dense
    staging array exists on the host at any point). `slots` maps flush row i
    -> arena slot; rows padded onto a scratch slot are how the server keeps
    one compile per payload meta. Jit caches by (meta, shapes, dtype,
    backend, mesh); `mesh` is the sharded arena's, over which xbuf is
    replicated.
    """
    dt = jnp.dtype(dtype or jnp.float32).name
    return _decode_to_slots_jit(xbuf, p, jnp.asarray(slots, jnp.int32),
                                dtype=dt, backend=backend, mesh=mesh)


def server_grad_encode(p: Payload, g) -> Payload:
    """Label-owner backward half: compress the dense cut gradient (..., d)
    to the wire payload the *forward* payload's kind dictates (Table 2 bwd).

    Sparse forward kinds send only the k gradient floats at the forward
    support (the feature owner already holds the indices), `slice` the first
    k, dense/quant kinds the full-precision dense gradient — the same rules
    `_grad_to_wire` applies inside the fused custom-VJP path. The returned
    payload has numpy leaves, ready for `core.wire.encode_grad_frame`.
    """
    import numpy as np

    kind = p.meta.kind
    d = p.meta.d
    k = min(p.meta.k or d, d)
    idx = None if p.indices is None else jnp.asarray(p.indices)
    gw = _grad_to_wire(kind, jnp.asarray(g), idx, k)
    sparse_bwd = kind in ("sparse", "sparse_quant", "slice", "mask")
    meta = (PayloadMeta("slice", d=d, k=k) if sparse_bwd
            else PayloadMeta("dense", d=d))
    return Payload(meta=meta, values=np.asarray(gw, np.float32))


def client_grad_decode(gp: Payload, *, fwd_kind: str, indices=None,
                       d: int):
    """Feature-owner backward half: dense (..., d) cut gradient from a
    received grad payload, routed onto the support of the forward payload
    the client sent (scatter for sparse kinds, pad for slice, identity for
    dense/quant — the paper's same-mask backward / STE rules)."""
    idx = None if indices is None else jnp.asarray(indices)
    return _grad_from_wire(fwd_kind, jnp.asarray(gp.values), idx, d)


def cut_boundary(x, cfg: ArchConfig, rt: Runtime, key) -> tuple:
    """Compress the cut activation (B, S, d), move the packed payload across
    the pod boundary, decode on the far side. Returns (x_top, l1_penalty).

    One generic path for every compressor — the payload object is the whole
    interface between the compressor, the wire, and the far side."""
    sc = cfg.split
    comp = make_cut_compressor(sc)
    d = x.shape[-1]
    pen = comp.loss_penalty(x.reshape(-1, d))
    y = _transport(comp, x, rt, key, over_pod=sc.transfer_over_pod)
    return rt.shard(y, "batch", None, None), pen


def wire_bytes_per_step(cfg: ArchConfig, batch: int, seq: int,
                        *, training: bool) -> float:
    """Paper-exact cut-layer wire bytes for one step (Table 2)."""
    from repro.core import wire

    sc = cfg.split
    if sc is None:
        return 0.0
    method = sc.compressor
    return wire.bytes_per_step(method, cfg.d_model, batch * seq, k=sc.k,
                               bits=sc.quant_bits, training=training)


def measured_payload_bytes(cfg: ArchConfig, batch: int, seq: int,
                           *, training: bool = False, key=None) -> int:
    """Byte-exact forward payload size for one (batch, seq) step, measured by
    actually encoding a probe activation and serializing it with
    `wire.encode_payload` — the codec-side cross-check of
    `wire_bytes_per_step`'s analytic formula."""
    from repro.core import wire

    sc = cfg.split
    if sc is None:
        return 0
    comp = make_cut_compressor(sc)
    probe = jax.random.normal(jax.random.key(0), (batch, seq, cfg.d_model))
    return wire.payload_nbytes(client_encode(comp, probe, key=key,
                                             training=training))
