"""The plain reference: the configuration's split forward pass in float32.

It imports nothing of the program. It reads the configuration file, the
benchmark's own weights (bench/model.py, cast up exactly from the dtype
they are served in), the script tokens, and the frame bytes the sessions
sent, which it parses itself. It computes, at `highest` matmul precision:

  1. the bottom layers [0, cut) over the whole script (causal attention,
     RoPE, qk-norm where the configuration has it);
  2. the cut compressor on its own activation. A compressor makes discrete
     choices (the top-k support, a quantization bin). Where the frame's
     choice lies within `TAU` of the reference's own decision boundary
     (a near tie, decided by rounding), the reference takes the frame's
     choice; otherwise its own. Values are always the reference's own;
  3. the top layers [cut, L) and the head over that compressed input.

`top_gaps` returns, at every served position, how far the served token's
reference logit lies below the reference's best logit, in units of the
standard deviation of that position's reference logits.

`Reference(..., quant=control_quant(conf))` is the lower-precision
control, the same computation one precision step below the configuration's
dtype. For bfloat16 or float16 it is "w8a8": every weight matrix and every
activation entering a matmul rounded to int8 (symmetric; per output column
for weights, per row for activations), as an int8 serving path would
compute it. For float32 it is "bf16": those operands rounded to bfloat16,
the products accumulated in float32.
"""
from __future__ import annotations

import struct
import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

#: near-tie band of a compressor decision, as a share of the row's RMS: a
#: bf16 bottom pass moves cut activations by about 2^-8 of their size per
#: rounding, and 16 layers deep by a few percent of the top-k threshold
#: (about 2.4 RMS); 2^-3 RMS holds that, and is far below the distance of
#: a wrong choice (a typical element sits 1.5-2 RMS below the threshold)
TAU = 2.0 ** -3

_HEAD = struct.Struct("<IBBII")
#: the wire's frame kind of a token reply (`repro.core.wire`)
FRAME_TOKENS = 2


# -- wire parsing -------------------------------------------------------------

def parse_frame(buf: bytes):
    """(kind, session, seq, body) of one whole frame; checks the CRC."""
    n, _version, kind, session, seq = _HEAD.unpack_from(buf, 0)
    if len(buf) != n + 4:
        raise ValueError(f"frame of {len(buf)} B, length prefix {n}")
    (crc,) = struct.unpack_from("<I", buf, len(buf) - 4)
    if zlib.crc32(buf[4:-4]) != crc:
        raise ValueError("frame crc mismatch")
    return kind, session, seq, buf[_HEAD.size:-4]


def parse_tokens(body: bytes) -> np.ndarray:
    (count,) = struct.unpack_from("<I", body, 0)
    return np.frombuffer(body, "<i4", count=count, offset=4)


def _unpack(buf: bytes, width: int, count: int) -> np.ndarray:
    """Little-endian bit stream: value i is bits [i*width, (i+1)*width)."""
    bits = np.unpackbits(np.frombuffer(buf, np.uint8), bitorder="little")
    bits = bits[: count * width].reshape(count, width).astype(np.int64)
    return bits @ (1 << np.arange(width, dtype=np.int64))


def parse_payload(kind: str, d: int, k: int, bits: int, body: bytes) -> dict:
    """One instance's payload body, as its values and discrete choices."""
    r = max(1, int(np.ceil(np.log2(d))))
    if kind == "dense":
        return {"values": np.frombuffer(body, "<f4", count=d)}
    if kind == "sparse":
        return {"values": np.frombuffer(body, "<f4", count=k),
                "support": _unpack(body[4 * k:], r, k)}
    if kind == "mask":
        m = np.unpackbits(np.frombuffer(body[4 * k:], np.uint8),
                          bitorder="little")[:d]
        return {"values": np.frombuffer(body, "<f4", count=k),
                "support": np.flatnonzero(m)}
    if kind == "quant":
        lo, step = np.frombuffer(body, "<f4", count=2)
        return {"lo": float(lo), "step": float(step),
                "codes": _unpack(body[8:], bits, d)}
    raise ValueError(f"payload kind {kind!r}")


# -- the compressor, with near ties taken from the frame -----------------------

def compress(x: np.ndarray, kind: str, k: int, bits: int, frames: list):
    """Reference top input (n, d) from the reference cut activation x
    (n, d) and the n parsed frames; also the count of rows where a frame's
    choice lay outside the near-tie band (the reference's own choice was
    used there), and the widest distance of a frame's choice beyond the
    reference's decision boundary, in row RMS."""
    n, d = x.shape
    rms = np.sqrt(np.mean(x * x, axis=1, keepdims=True))
    tau = TAU * rms
    if kind == "dense":
        return x.copy(), 0, 0.0
    if kind in ("sparse", "mask"):
        a = np.abs(x)
        sel = np.zeros((n, d), bool)
        for i, f in enumerate(frames):
            sel[i, f["support"]] = True
        ok = sel.sum(1) == k
        lo_sel = np.where(sel, a, np.inf).min(1)
        hi_out = np.where(sel, -np.inf, a).max(1)
        ok &= lo_sel >= hi_out - tau[:, 0]
        own = np.zeros((n, d), bool)
        np.put_along_axis(own, np.argpartition(-a, k - 1, axis=1)[:, :k],
                          True, axis=1)
        use = np.where(ok[:, None], sel, own)
        slack = float(np.max((hi_out - lo_sel) / rms[:, 0]))
        return (np.where(use, x, 0.0).astype(np.float32), int((~ok).sum()),
                slack)
    if kind == "quant":
        lo = x.min(1, keepdims=True)
        step = (x.max(1, keepdims=True) - lo) / 2 ** bits
        step = np.where(step <= 0, 1.0, step)
        top = 2 ** bits - 1
        own = np.clip(np.floor((x - lo) / step), 0, top)
        got = np.stack([f["codes"] for f in frames]).astype(np.float64)
        below = np.where(got == 0, -np.inf, lo + got * step - tau)
        above = np.where(got == top, np.inf, lo + (got + 1) * step + tau)
        inside = (x >= below) & (x <= above)
        codes = np.where(inside, got, own)
        out = np.maximum(below + tau - x, x - above - tau)
        slack = float(np.max(np.where(np.isfinite(out), out, -np.inf) / rms))
        return ((lo + (codes + 0.5) * step).astype(np.float32),
                int((~inside.all(1)).sum()), slack)
    raise ValueError(f"payload kind {kind!r}")


# -- the model ------------------------------------------------------------------

def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """HF rotate-half RoPE over (B, S, H, hd) at positions 0..S-1."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _int8(a, axis):
    """Symmetric int8 rounding along `axis`, back in float32."""
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(a / s), -127, 127) * s


def control_quant(conf: dict) -> str:
    """The control's precision: one step below the configuration's."""
    return {"bfloat16": "w8a8", "float16": "w8a8",
            "float32": "bf16"}[conf["torch_dtype"]]


def _mm(quant, a, w):
    if quant == "w8a8":
        return _int8(a, -1) @ _int8(w, 0)
    if quant == "bf16":
        return jnp.matmul(a.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return a @ w


def _layer(conf, quant, w, x):
    f = lambda a: a.astype(jnp.float32)
    mm = partial(_mm, quant)
    eps, hq, hkv, hd = (conf["rms_norm_eps"], conf["num_attention_heads"],
                        conf["num_key_value_heads"], conf["head_dim"])
    B, S, _ = x.shape
    a = w["attn"]
    h = _rms(x, f(a["norm"]["scale"]), eps)
    q = mm(h, f(a["wq"])).reshape(B, S, hq, hd)
    k = mm(h, f(a["wk"])).reshape(B, S, hkv, hd)
    v = mm(h, f(a["wv"])).reshape(B, S, hkv, hd)
    if conf["qk_norm"]:
        q = _rms(q, f(a["q_norm"]["scale"]), eps)
        k = _rms(k, f(a["k_norm"]["scale"]), eps)
    q, k = _rope(q, conf["rope_theta"]), _rope(k, conf["rope_theta"])
    k = jnp.repeat(k, hq // hkv, axis=2)
    v = jnp.repeat(v, hq // hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / hd ** 0.5
    i = jnp.arange(S)
    mask = i[None, :] <= i[:, None]
    if conf.get("sliding_window"):
        mask &= i[None, :] > i[:, None] - conf["sliding_window"]
    s = jnp.where(mask, s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    x = x + mm(o.reshape(B, S, hq * hd), f(a["wo"]))
    m = w["mlp"]
    h = _rms(x, f(m["norm"]["scale"]), eps)
    g = jax.nn.silu(mm(h, f(m["w_gate"]))) * mm(h, f(m["w_up"]))
    return x + mm(g, f(m["w_down"]))


class Reference:
    def __init__(self, conf: dict, weights, quant=None):
        self.conf, self.w = conf, weights
        self._layer = jax.jit(partial(_layer, conf, quant))
        self._embed = jax.jit(lambda e, t: e.astype(jnp.float32)[t])
        self._head = jax.jit(partial(_head_stats, conf))
        self._argmax = jax.jit(partial(_head_argmax, conf, quant))

    def _layers(self, x, lo, hi):
        for i in range(lo, hi):
            wl = jax.tree.map(lambda a: a[i], self.w["layers"])
            with jax.default_matmul_precision("highest"):
                x = self._layer(wl, x)
        return x

    def bottom(self, tokens: np.ndarray) -> np.ndarray:
        """(B, S) token ids -> (B, S, d) cut activations on the host."""
        with jax.default_matmul_precision("highest"):
            x = self._embed(self.w["embed"], jnp.asarray(tokens))
        return np.asarray(self._layers(x, 0, self.conf["cut_layer"]))

    def top_gaps(self, x: np.ndarray, served: np.ndarray) -> np.ndarray:
        """(B, S, d) top inputs and (B, S) served tokens -> (B, S) gaps of
        the served token below the best, in reference-logit std units."""
        c = self.conf
        h = self._layers(jnp.asarray(x), c["cut_layer"],
                         c["num_hidden_layers"])
        with jax.default_matmul_precision("highest"):
            return np.asarray(self._head(self.w["final_norm"]["scale"],
                                         self.w["unembed"], h,
                                         jnp.asarray(served)))


    def top_argmax(self, x: np.ndarray) -> np.ndarray:
        """(B, S, d) top inputs -> (B, S) greedy tokens of this reference."""
        c = self.conf
        h = self._layers(jnp.asarray(x), c["cut_layer"],
                         c["num_hidden_layers"])
        with jax.default_matmul_precision("highest"):
            return np.asarray(self._argmax(self.w["final_norm"]["scale"],
                                           self.w["unembed"], h))


def _head_argmax(conf, quant, g, unembed, h):
    w = unembed[:, :conf["vocab_size"]].astype(jnp.float32)
    hn = _rms(h, g.astype(jnp.float32), conf["rms_norm_eps"])
    return jax.lax.map(lambda hb: jnp.argmax(_mm(quant, hb, w), -1), hn)


def _head_stats(conf, g, unembed, h, served):
    V = conf["vocab_size"]
    w = unembed[:, :V].astype(jnp.float32)
    hn = _rms(h, g.astype(jnp.float32), conf["rms_norm_eps"])

    def one(args):
        hb, sb = args
        logits = hb @ w                                     # (S, V)
        best = logits.max(-1)
        got = jnp.take_along_axis(logits, jnp.clip(sb, 0, V - 1)[:, None],
                                  -1)[:, 0]
        got = jnp.where((sb >= 0) & (sb < V), got, -jnp.inf)
        return (best - got) / logits.std(-1)

    return jax.lax.map(one, (hn, served))
