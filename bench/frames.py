"""The feature owners' frames, made once in set-up and replayed.

Each pool script runs through the program's own feature-owner code: its
embedding and bottom layers (`transformer.apply_layers` over layers
[0, cut), the whole script at once), its compressor (`Compressor.encode`,
inference mode) and its wire codec (`core.wire.encode_payload`). The
payload body of every step stays on the host; a session frames it with
its own session id and step (`wire.encode_payload_frame_from_bytes`)
when it sends. The label owner's chip pays nothing per token for the
feature owners, as in a deployment where each has its own hardware.
"""
from __future__ import annotations

import dataclasses
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

#: scripts per bottom-pass program (one compile for every chunk)
CHUNK = 8


@dataclasses.dataclass
class Pool:
    metas: list                 # (S,) PayloadMeta of each script's frames
    bodies: List[List[bytes]]   # (S, steps) payload bitstream per step

    def frame(self, session: int, script: int, step: int) -> bytes:
        from repro.core import wire

        return wire.encode_payload_frame_from_bytes(
            session, step, self.metas[script], (1, 1),
            self.bodies[script][step])


def _row_sections(kind: str, n: int, meta) -> List[int]:
    """Byte length per row of each section of `encode_payload` for n rows
    of one instance each; every section must end on a byte per row."""
    from repro.core import wire

    d, k, bits, r = meta.d, meta.k, meta.bits, wire.index_bits(meta.d)
    per_row = {"dense": [(4 * d, 8)], "sparse": [(4 * k, 8), (k * r, 1)],
               "mask": [(4 * k, 8), (8 * wire.mask_row_nbytes(d), 1)],
               "quant": [(8, 8), (d * bits, 1)]}[kind]
    out = []
    for size, unit in per_row:
        bits_row = size * unit
        if bits_row % 8:
            raise ValueError(f"{kind} section of {bits_row} bits per row "
                             f"does not end on a byte")
        out.append(bits_row // 8)
    return out


def split_rows(p) -> List[bytes]:
    """Per-row payload bodies of a host Payload of n single-instance rows,
    cut out of one `encode_payload` call over all n."""
    from repro.core import wire

    n = int(np.prod(p.batch_shape))
    bulk = wire.encode_payload(p)
    sizes = _row_sections(p.meta.kind, n, p.meta)
    starts = np.cumsum([0] + [n * s for s in sizes])
    rows = [b"".join(bulk[starts[j] + i * s: starts[j] + (i + 1) * s]
                     for j, s in enumerate(sizes)) for i in range(n)]
    one = wire.payload_expected_nbytes(p.meta, (1, 1))
    if any(len(r) != one for r in rows):
        raise ValueError("row bodies differ from the single-row size")
    return rows


def make_pool(cfg, params, plan) -> Pool:
    from repro.core import compressors, wire
    from repro.core.payload import Payload
    from repro.models import transformer
    from repro.models.config import Runtime

    rt = Runtime(mesh=None, training=False)
    cut = cfg.split.cut_layer
    s, max_len = plan.tokens.shape

    @jax.jit
    def bottom(params, tokens):
        x = transformer.embed(params, cfg, rt, tokens)
        x, _ = transformer.apply_layers(params, cfg, rt, x, {}, 0, cut)
        return x

    # the XLA selection path: frames are byte-identical to the Pallas
    # path's (tests/test_encode_kernels.py), and the Pallas top-k kernel
    # does not lower for a bulk of rows at d = 3072 (PERF.md)
    comps = [dataclasses.replace(compressors.make_compressor(spec),
                                 backend="xla") for spec in plan.specs]
    encoders = [jax.jit(lambda x, c=c: c.encode(x, training=False))
                for c in comps]
    metas: list = [None] * s
    bodies: List[List[bytes]] = [[] for _ in range(s)]
    pad = -s % CHUNK
    tokens = np.concatenate([plan.tokens,
                             np.zeros((pad, max_len), np.int32)])
    for c0 in range(0, s + pad, CHUNK):
        x = bottom(params, jnp.asarray(tokens[c0:c0 + CHUNK]))
        x = x.reshape(CHUNK * max_len, 1, 1, x.shape[-1])
        for ci in range(len(comps)):
            idx = [i for i in range(c0, min(c0 + CHUNK, s))
                   if plan.comp[i] == ci]
            if not idx:
                continue
            steps = [plan.steps(i) for i in idx]
            # every position of the chunk is encoded (one program shape);
            # the host keeps the rows the scripts send
            take = np.concatenate([(i - c0) * max_len + np.arange(n)
                                   for i, n in zip(idx, steps)])
            full = encoders[ci](x)
            p = Payload(meta=full.meta, **{
                f: np.asarray(a)[take] for f, a in full.wire_leaves()})
            one = split_rows(p)
            # spot check: the first row alone encodes to the same bytes
            first = Payload(meta=p.meta, **{f: a[:1] for f, a in
                                            p.wire_leaves()})
            if wire.encode_payload(first) != one[0]:
                raise ValueError("bulk and single-row encodes differ")
            off = 0
            for i, n in zip(idx, steps):
                metas[i], bodies[i] = p.meta, one[off:off + n]
                off += n
    return Pool(metas=metas, bodies=bodies)
