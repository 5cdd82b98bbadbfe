"""Roofline HLO walker on pinned fixture programs: dot-flops counting,
while-loop trip amplification (known_trip_count and compare-constant
fallback), the dynamic-update-slice byte convention, collective ring
factors — plus the closed-form serving-kernel cost predictions in
`roofline.analysis`."""
import numpy as np

import repro.configs as configs
from repro.models import attention
from repro.roofline import analysis, hlo


def _mod(body: str) -> str:
    return "HloModule fixture\n\n" + body.strip() + "\n"


# ---------------------------------------------------------------------------
# program_costs: dots and bytes
# ---------------------------------------------------------------------------

DOT_HLO = _mod("""
ENTRY %main.1 (p0: f32[4,8], p1: f32[8,16]) -> f32[4,16] {
  %p0 = f32[4,8]{1,0} parameter(0)
  %p1 = f32[8,16]{1,0} parameter(1)
  ROOT %d.1 = f32[4,16]{1,0} dot(%p0, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
""")


def test_dot_flops_and_bytes():
    flops, byts = hlo.program_costs(DOT_HLO)
    # 2 * out_elems * contracted = 2 * (4*16) * 8
    assert flops == 2 * 4 * 16 * 8
    # parameters are skipped; only the dot output materializes: write+read
    assert byts == 2 * (4 * 16 * 4)


def test_f32_deflate_halves_bytes_not_flops():
    flops, byts = hlo.program_costs(DOT_HLO, f32_deflate=True)
    assert flops == 2 * 4 * 16 * 8
    assert byts == (4 * 16 * 4)          # counted at bf16 width


WHILE_KNOWN_TRIP = _mod("""
%body.1 (bp: (s32[], f32[4,8])) -> (s32[], f32[4,8]) {
  %bp = (s32[], f32[4,8]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%bp), index=0
  %x = f32[4,8]{1,0} get-tuple-element(%bp), index=1
  %w = f32[8,8]{1,0} constant({...})
  %y = f32[4,8]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %out = (s32[], f32[4,8]{1,0}) tuple(%i, %y)
}

%cond.1 (cp: (s32[], f32[4,8])) -> pred[] {
  %cp = (s32[], f32[4,8]{1,0}) parameter(0)
  %it = s32[] get-tuple-element(%cp), index=0
  %lim = s32[] constant(99)
  ROOT %lt = pred[] compare(%it, %lim), direction=LT
}

ENTRY %main.2 (p0: (s32[], f32[4,8])) -> (s32[], f32[4,8]) {
  %p0 = (s32[], f32[4,8]{1,0}) parameter(0)
  ROOT %w.1 = (s32[], f32[4,8]{1,0}) while(%p0), condition=%cond.1, body=%body.1, backend_config={"known_trip_count":{"n":"3"}}
}
""")


def test_while_known_trip_count_beats_compare_constant():
    """XLA's known_trip_count annotation (3) must win over the condition's
    compare constant (99)."""
    flops, byts = hlo.program_costs(WHILE_KNOWN_TRIP)
    body_flops = 2 * (4 * 8) * 8
    assert flops == 3 * body_flops
    # body bytes: only the dot output (GTEs/tuple/params/constants skipped)
    assert byts == 3 * 2 * (4 * 8 * 4)


WHILE_COMPARE_FALLBACK = WHILE_KNOWN_TRIP.replace(
    ', backend_config={"known_trip_count":{"n":"3"}}', "").replace(
    "constant(99)", "constant(5)")


def test_while_compare_constant_fallback():
    flops, _ = hlo.program_costs(WHILE_COMPARE_FALLBACK)
    assert flops == 5 * 2 * (4 * 8) * 8


DUS_FUSION = _mod("""
%fused_dus (fb: f32[8,16], fu: f32[1,16], fi: s32[], fz: s32[]) -> f32[8,16] {
  %fb = f32[8,16]{1,0} parameter(0)
  %fu = f32[1,16]{1,0} parameter(1)
  %fi = s32[] parameter(2)
  %fz = s32[] parameter(3)
  ROOT %dus.1 = f32[8,16]{1,0} dynamic-update-slice(%fb, %fu, %fi, %fz)
}

ENTRY %main.3 (p0: f32[8,16], p1: f32[1,16], p2: s32[]) -> f32[8,16] {
  %p0 = f32[8,16]{1,0} parameter(0)
  %p1 = f32[1,16]{1,0} parameter(1)
  %p2 = s32[] parameter(2)
  %z = s32[] constant(0)
  ROOT %f.1 = f32[8,16]{1,0} fusion(%p0, %p1, %p2, %z), kind=kLoop, calls=%fused_dus
}
""")


def test_dus_fusion_counts_update_not_buffer():
    """A kLoop fusion rooted at dynamic-update-slice aliases the big buffer
    in place — only the update slice moves, not the full output."""
    _, byts = hlo.program_costs(DUS_FUSION)
    assert byts == 2 * (1 * 16 * 4)      # not 2 * 8*16*4


BARE_DUS = _mod("""
ENTRY %main.4 (p0: f32[8,16], p2: s32[]) -> f32[8,16] {
  %p0 = f32[8,16]{1,0} parameter(0)
  %p2 = s32[] parameter(2)
  %z = s32[] constant(0)
  %u = f32[2,16]{1,0} add(%p0, %p0)
  ROOT %dus.2 = f32[8,16]{1,0} dynamic-update-slice(%p0, %u, %p2, %z)
}
""")


def test_bare_dus_counts_update_operand():
    _, byts = hlo.program_costs(BARE_DUS)
    # add output (2x 2*16*4) + DUS counted at its update operand's shape
    assert byts == 2 * (2 * 16 * 4) + 2 * (2 * 16 * 4)


# ---------------------------------------------------------------------------
# collective_bytes: ring factors, tuple -start forms, loop amplification
# ---------------------------------------------------------------------------

AR_HLO = _mod("""
%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

ENTRY %main.5 (p0: f32[128]) -> f32[128] {
  %p0 = f32[128]{0} parameter(0)
  ROOT %ar.1 = f32[128]{0} all-reduce(%p0), replica_groups={}, to_apply=%add
}
""")


def test_all_reduce_ring_factor():
    stats = hlo.collective_bytes(AR_HLO)
    assert stats.raw_bytes == {"all-reduce": 128 * 4}
    # ring all-reduce = reduce-scatter + all-gather phases -> 2x local bytes
    assert stats.total_link_bytes == 2.0 * 128 * 4
    deflated = hlo.collective_bytes(AR_HLO, f32_deflate=True)
    assert deflated.raw_bytes == {"all-reduce": 128 * 2}


TUPLE_AG_HLO = _mod("""
ENTRY %main.6 (p0: f32[4]) -> f32[8] {
  %p0 = f32[4]{0} parameter(0)
  %ag.1 = (f32[4]{0}, f32[8]{0}) all-gather-start(%p0), dimensions={0}
  ROOT %agd = f32[8]{0} all-gather-done(%ag.1)
}
""")


def test_tuple_collective_start_counts_operand():
    """-start ops return (operand, result) tuples; the walker counts the
    first (operand) shape — the local contribution each device puts on the
    link — not the gathered result."""
    stats = hlo.collective_bytes(TUPLE_AG_HLO)
    assert stats.raw_bytes["all-gather"] == 4 * 4


WHILE_COLL = _mod("""
%wbody (bp: (s32[], f32[64])) -> (s32[], f32[64]) {
  %bp = (s32[], f32[64]{0}) parameter(0)
  %i = s32[] get-tuple-element(%bp), index=0
  %x = f32[64]{0} get-tuple-element(%bp), index=1
  %ar.2 = f32[64]{0} all-reduce(%x), replica_groups={}, to_apply=%add
  ROOT %out = (s32[], f32[64]{0}) tuple(%i, %ar.2)
}

%wcond (cp: (s32[], f32[64])) -> pred[] {
  %cp = (s32[], f32[64]{0}) parameter(0)
  %it = s32[] get-tuple-element(%cp), index=0
  %lim = s32[] constant(4)
  ROOT %lt = pred[] compare(%it, %lim), direction=LT
}

ENTRY %main.7 (p0: (s32[], f32[64])) -> (s32[], f32[64]) {
  %p0 = (s32[], f32[64]{0}) parameter(0)
  ROOT %w.2 = (s32[], f32[64]{0}) while(%p0), condition=%wcond, body=%wbody
}
""")


def test_collective_inside_while_amplified():
    stats = hlo.collective_bytes(WHILE_COLL)
    assert stats.raw_bytes == {"all-reduce": 4 * 64 * 4}


def test_empty_and_collective_free_programs():
    assert hlo.program_costs("") == (0.0, 0.0)
    assert hlo.collective_bytes(DOT_HLO).raw_bytes == {}
    assert hlo.collective_bytes("").total_link_bytes == 0.0


# ---------------------------------------------------------------------------
# analysis: closed-form serving-kernel predictions
# ---------------------------------------------------------------------------

def test_serving_decode_costs_no_dots():
    flops, floor = analysis.serving_decode_costs(8, 256)
    assert flops == 0.0
    assert floor == 2.0 * 8 * 256 * 4
    lo, hi = analysis.DECODE_BYTES_BAND
    assert lo <= 1.0 < hi


def test_top_matmul_params_matches_hand_count():
    cfg = configs.get("qwen3-8b", smoke=True)
    d, ff = cfg.d_model, cfg.d_ff
    attn = (d * cfg.n_heads * cfg.hd + 2 * d * cfg.n_kv_heads * cfg.hd
            + cfg.n_heads * cfg.hd * d)
    for cut in (0, 1, cfg.n_layers):
        want = (cfg.n_layers - cut) * (attn + 3 * d * ff) \
            + d * cfg.padded_vocab
        assert analysis.top_matmul_params(cfg, cut) == want
    # deeper cut -> strictly fewer top-model params
    assert analysis.top_matmul_params(cfg, 1) < \
        analysis.top_matmul_params(cfg, 0)


def test_serving_step_costs_scaling():
    cfg = configs.get("qwen3-8b", smoke=True)
    state = 12_345
    flops, floor = analysis.serving_step_costs(cfg, 1, 8, 20, state)
    assert floor == 2.0 * state
    # the attention dots run over the cache's lane-padded head dim
    score = 2 * cfg.n_heads * attention.kv_width(cfg) * 20
    assert flops == 2.0 * 8 * (analysis.top_matmul_params(cfg, 1) + score)
    # flops scale linearly in arena capacity; byte floor does not move
    flops2, floor2 = analysis.serving_step_costs(cfg, 1, 16, 20, state)
    assert flops2 == 2 * flops and floor2 == floor


def test_band_constants_sane():
    for lo, hi in (analysis.DECODE_BYTES_BAND, analysis.FUSED_BYTES_BAND):
        assert 0 < lo < hi
    assert 0 < analysis.FUSED_FLOPS_RTOL < 1
