"""Three-term roofline model from a compiled dry-run artifact.

    compute   = HLO_FLOPs / (chips * peak_FLOPs)
    memory    = HLO_bytes / (chips * HBM_bw)
    collective= collective_link_bytes / (chips * link_bw)

Hardware constants: TPU v5e — 197 TFLOP/s bf16 per chip, 819 GB/s HBM,
~50 GB/s/link ICI.

cost_analysis() FLOPs/bytes on the host backend are whole-program (all
partitions) for the replicated program: we detect per-device vs global by
dividing by chips. Collective bytes come from the HLO parser (per-device link
bytes already).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

from repro.models import attention
from repro.roofline import hlo as hlo_mod

PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
LINK_BW = 50e9               # bytes/s / link (ICI)


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float          # whole-program FLOPs (all chips)
    hlo_bytes: float          # whole-program bytes accessed
    coll_bytes: float         # per-chip link bytes
    coll_detail: Dict[str, float]
    model_flops: float = 0.0  # 6*N*D (or 6*N_active*D)
    peak_memory: float = 0.0  # per-device bytes (from memory_analysis)

    @property
    def t_compute(self) -> float:
        return self.hlo_flops / (self.chips * PEAK_FLOPS)

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / (self.chips * HBM_BW)

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / LINK_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    def row(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "model_flops": self.model_flops,
            "hlo_flops": self.hlo_flops,
            "useful_ratio": self.useful_flops_ratio,
            "peak_mem_gb": self.peak_memory / 1e9,
            "coll_detail": self.coll_detail,
        }


def from_compiled(compiled, *, arch: str, shape: str, mesh_desc: str,
                  chips: int, model_flops: float = 0.0,
                  hlo_text: Optional[str] = None,
                  bf16_target: bool = True) -> Roofline:
    text = hlo_text if hlo_text is not None else compiled.as_text()
    # xla's cost_analysis() counts while bodies ONCE; our HLO walker applies
    # loop trip counts (scan over layers/chunks), so it is the source of truth.
    # The parsed numbers are per-partition (post-SPMD shapes); scale to the
    # whole program by multiplying with the chip count.
    flops_pp, bytes_pp = hlo_mod.program_costs(text, f32_deflate=bf16_target)
    flops = flops_pp * chips
    byts = bytes_pp * chips
    stats = hlo_mod.collective_bytes(text, f32_deflate=bf16_target)
    mem = compiled.memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes +
            mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    return Roofline(
        arch=arch, shape=shape, mesh=mesh_desc, chips=chips,
        hlo_flops=flops, hlo_bytes=byts,
        coll_bytes=stats.total_link_bytes, coll_detail=stats.raw_bytes,
        model_flops=model_flops, peak_memory=peak)


# --------------------------------------------------------------------------
# MODEL_FLOPS = 6 * N_active * D  (D = tokens processed in the step)
# --------------------------------------------------------------------------

def active_param_count(cfg) -> int:
    """Active params per token (MoE counts topk experts, not all)."""
    d, ff, L, V = cfg.d_model, cfg.d_ff, cfg.n_layers, cfg.vocab
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    attn = d * hq * hd + 2 * d * hkv * hd + hq * hd * d

    if cfg.family in ("dense",):
        per_layer = attn + 3 * d * ff
        total = L * per_layer
    elif cfg.family == "moe":
        expert = 3 * d * ff
        per_layer = attn + cfg.topk_experts * expert + d * cfg.n_experts
        total = L * per_layer
    elif cfg.family == "hybrid":
        di, N, H = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
        mamba = d * 2 * di + d * (2 * N + H) + di * d
        n_attn = sum((i + 1) % cfg.attn_every == 0 for i in range(L))
        total = L * mamba + n_attn * (attn + 3 * d * ff)
    elif cfg.family == "ssm":
        total = L * (4 * d * d + d * d) + L * (2 * d * ff + d * d)
    elif cfg.family == "vlm":
        n_cross = L // cfg.cross_attn_every
        n_self = L - n_cross
        total = n_self * (attn + 3 * d * ff) + n_cross * (attn + 3 * d * ff)
    elif cfg.family == "audio":
        enc = cfg.n_enc_layers * (attn + 3 * d * ff)
        dec = L * (2 * attn + 3 * d * ff)
        total = enc + dec
    else:
        total = 0
    total += 2 * V * d  # embed + unembed
    return int(total)


def model_flops(cfg, *, tokens: int, training: bool) -> float:
    mult = 6.0 if training else 2.0
    return mult * active_param_count(cfg) * tokens


# --------------------------------------------------------------------------
# Serving-kernel audit: predicted (flops, bytes) for the streaming server's
# compiled programs, under the SAME conventions as `hlo.program_costs`
# (flops = dots only, loop-amplified; bytes = 2x every materialized
# instruction output, fusion internals excluded). Tolerances are calibrated
# against the XLA:CPU smoke programs and documented in docs/performance.md.
# --------------------------------------------------------------------------

#: measured decode bytes / predicted floor — XLA materializes scatter
#: staging (zeros + one-hot accumulate) on top of the decoded update slice;
#: dense decode sits at ~1.0x, sparse kinds at ~2.7x, and the mask kind at
#: ~4.2x (bitmask unpack + the prefix-sum position map are both staged).
DECODE_BYTES_BAND = (1.0, 5.0)
#: measured fused-step bytes / predicted floor — per-layer activation
#: intermediates (attention scores, FFN hidden states, residual copies,
#: all materialized per arena row) land on top of the state-update floor
#: (cache + xbuf); the XLA:CPU smoke programs calibrate at ~10x.
FUSED_BYTES_BAND = (1.0, 16.0)
#: fused-step dot flops are fully predictable: matmul params + attention
#: score/mix dots; everything else in the program is elementwise.
FUSED_FLOPS_RTOL = 0.05
#: measured encode bytes / predicted floor — the fused device encode
#: (`split.protocol.client_encode_device`: selection -> gather -> quantize
#: -> bit-pack) materializes the selection machinery on top of the
#: activation-in / packed-words-out floor: dense sits at 1.0x exactly,
#: full-row quant at ~2.5x (code staging before the pack), and the top-k
#: kinds at ~6.6-6.8x (sort/threshold selection staging) on the XLA:CPU
#: smoke programs.
ENCODE_BYTES_BAND = (1.0, 10.0)


def top_matmul_params(cfg, cut: int) -> int:
    """Matmul (dot-contributing) params of the label owner's top model:
    attention + FFN projections of layers [cut, n_layers) plus the unembed
    over the padded vocab. Embedding gathers and norms contribute no dots,
    so this matches `hlo.program_costs` flops, not the byte-count param
    total. Dense-family only (the serving bench's arch)."""
    d, ff = cfg.d_model, cfg.d_ff
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    attn = d * hq * hd + 2 * d * hkv * hd + hq * hd * d
    return (cfg.n_layers - cut) * (attn + 3 * d * ff) + d * cfg.padded_vocab


def serving_decode_costs(rows: int, d: int, *, dtype_bytes: int = 4):
    """Predicted (flops, bytes floor) of the slot-decode program.

    No dots -> 0 flops exactly. The byte floor is the decoded update slice
    written + read (2 * rows * d); measured lands within
    `DECODE_BYTES_BAND` of it depending on how much scatter staging the
    payload kind makes XLA materialize."""
    return 0.0, 2.0 * rows * d * dtype_bytes


def serving_encode_costs(rows: int, d: int, *, dtype_bytes: int = 4):
    """Predicted (flops, bytes floor) of the client's fused device-encode
    program (`protocol.client_encode_device`: selection mask -> gather ->
    quantize -> bit-pack into wire words).

    No dots -> 0 flops exactly (selection, gather, quantization, and the
    bit-pack are all elementwise/compare/shift work — the kernels'
    zero-dot-flops budget, see `kernels.encode`). The byte floor is the
    activation read + an output write of the same order (2 * rows * d);
    measured lands within `ENCODE_BYTES_BAND` of it depending on how much
    selection/pack staging the payload kind makes XLA materialize."""
    return 0.0, 2.0 * rows * d * dtype_bytes


def serving_step_costs(cfg, cut: int, capacity: int, max_len: int,
                       state_nbytes: int):
    """Predicted (flops, bytes floor) of the fused decode+step program.

    flops: every arena row computes (inactive rows drop their writes),
    each paying the top matmul params plus the two decode-attention dots
    against a `max_len` KV cache of lane-padded heads
    (`attention.kv_width`) — exact to `FUSED_FLOPS_RTOL`.
    bytes floor: the arena state written + read (`state_nbytes` = cache
    leaves + xbuf, measured off the live arrays so an int8 KV arena
    predicts its smaller traffic automatically); measured lands within
    `FUSED_BYTES_BAND` of it."""
    score_dots = 2 * cfg.n_heads * attention.kv_width(cfg) * max_len
    flops = 2.0 * capacity * (top_matmul_params(cfg, cut) + score_dots)
    return flops, 2.0 * state_nbytes


def serving_collective_costs(cfg, capacity: int, mesh_axes,
                             *, dtype_bytes: int = 4):
    """Predicted per-device collective bytes of the SHARDED arena step
    (`runtime.steps._make_sharded_arena_step`), per HLO op, under the same
    conventions as `hlo.collective_bytes`: raw bytes are each collective
    instruction's per-device output size, and the returned total applies
    the per-op ring factors (`hlo.RING_FACTOR`).

    The sharded step's collectives are fully enumerable from its
    decomposition (docs/sharding.md):

      * 'model' axis: the Megatron-SP row gather (`tp.gather_seq_local`,
        one all-gather of the rank's hidden row block) plus the exact
        vocab-parallel argmax (one f32 pmax + one s32 pmin, each an
        all-reduce over a scalar per gathered row).
      * 'pod' axis: the cut-boundary ring crossing — one collective-permute
        of the local activation row block forward and one of the gathered
        token rows back (`protocol.pod_ring_perm` and its inverse).

    `mesh_axes` is the mesh's `{axis: size}` mapping; `capacity` the padded
    arena row count. Rows shard over all axes flattened, so the per-device
    row block is `capacity / n_devices` and the model-group gathered block
    is that times the model-axis size."""
    sizes = dict(mesh_axes)
    n_model = sizes.get("model", 1)
    n_pod = sizes.get("pod", 1)
    n_dev = 1
    for s in sizes.values():
        n_dev *= s
    rows_local = capacity // n_dev          # per-device row shard
    rows_group = rows_local * n_model       # rows a model group reassembles
    d = cfg.d_model
    per_op: Dict[str, float] = {}
    if n_model > 1:
        per_op["all-gather"] = float(rows_group * d * dtype_bytes)
        # pmax f32[rows, 1] + pmin s32[rows, 1]: 4 bytes each per row
        per_op["all-reduce"] = float(2 * rows_group * 4)
    if n_pod > 1:
        per_op["collective-permute"] = float(
            rows_local * d * dtype_bytes     # activation block forward
            + rows_group * 4)                # s32 token rows back
    total = sum(hlo_mod.RING_FACTOR.get(op, 1.0) * b
                for op, b in per_op.items())
    return per_op, total


def serving_collective_slack(cfg, capacity: int, mesh_axes,
                             *, dtype_bytes: int = 4):
    """Per-op byte SLACK the sharded-step collective audit allows on top of
    `serving_collective_costs` — non-intrinsic traffic XLA's partitioner
    adds, each with a closed-form bound (calibrated exact on the XLA:CPU
    smoke programs):

      * collective-permute: the replicated `xbuf`'s live-row slice enters
        shard_map row-sharded, and the partitioner stages that reshard as a
        permute chain instead of a local slice — bounded by ONE full copy
        of the live xbuf rows (`capacity * d_model * dtype_bytes`).
      * all-reduce (model axis == 1 only): the vocab-parallel argmax's
        pmax/pmin legalize to degenerate single-device-group all-reduces —
        two 4-byte scalars per local row, zero actual link traffic. With a
        real model axis the all-reduce bytes are intrinsic and must match
        the prediction exactly, so no slack.

    The audit gate is `predicted <= measured <= predicted + slack` per op.
    """
    sizes = dict(mesh_axes)
    n_dev = 1
    for s in sizes.values():
        n_dev *= s
    rows_group = (capacity // n_dev) * sizes.get("model", 1)
    slack = {"collective-permute":
             float(capacity * cfg.d_model * dtype_bytes)}
    if sizes.get("model", 1) == 1:
        slack["all-reduce"] = float(2 * 4 * rows_group)
    return slack
