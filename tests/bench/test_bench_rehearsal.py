"""The harness end to end on the CPU, at a small size, through the real
StreamingServer."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench_small import ROOT, run as _run, small_cell
from bench import spec  # noqa: E402


@pytest.mark.parametrize("mix", ["randtopk-chat", "mixed-chat"])
def test_rehearsal(mix):
    """The randtopk mix runs the fused decode+step program; the mixed
    fleet's flushes mix payload metas and take the per-meta decodes and
    the plain arena step."""
    cell = small_cell("qwen3-8b-l8.randtopk-chat", traffic=mix)
    out = _run(cell)
    assert out["correct"], out
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in cell["end_to_end"]}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"
    if mix == "randtopk-chat":
        # randtopk k=64 at d=256: 64 f32 + 64 8-bit indices + 37 B framing
        assert out["metrics"]["wire_bytes_per_tok"]["value"] == 357.0


def test_traced_rehearsal():
    cell = small_cell("phi3-mini-3.8b.randtopk-longgen")
    cell["conf"]["num_key_value_heads"] = 4
    out = _run(cell, trace=True)
    assert out["correct"], out
    # the CPU has no device trace: only the host's readers find numbers
    assert {"host_us_per_tok.longgen", "flush_fill.longgen",
            "queue_wait_ms_p50.longgen"} <= set(out["metrics"])
    assert "idle_share.longgen" not in out["metrics"]
    assert out["device"]["window_s"] > 0
    assert "breakdown" in out


def test_no_chip_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload",
         "qwen3-8b-l8.randtopk-chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_benchmark_names_its_files():
    """Every name in BENCHMARK.json finds its file."""
    bm = spec.benchmark()
    for c in bm["configs"]:
        conf = spec.load_json(os.path.join(ROOT, c["file"]))
        assert conf["name"] == c["name"] and conf["source"] == c["source"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
    for w in bm["workloads"]:
        cell = spec.cell(bm, w["name"])
        assert cell["limits"]["logit_gap"]["limit"] > 0
        for m in cell["end_to_end"] + cell["per_layer"]:
            assert callable(spec.reader(m["name"]).read)
    assert not np.isnan(spec.peaks("TPU v5 lite")["hbm_bytes_per_s"])
