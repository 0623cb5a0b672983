"""The byte and operation counts the metrics divide by, at the published
sizes, and the configurations' layouts against the program's."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.families import dense, ssm  # noqa: E402


def _cfg(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def test_mamba2_370m_bytes():
    c = _cfg("mamba2-370m")
    # 50,277 tokens padded to 50,288 embedding rows, tied to the head
    assert ssm.sizes(c)["vocab"] == 50_288 and ssm.sizes(c)["tokens"] == 50_277
    assert ssm.weight_bytes(c) == 736_702_464
    assert ssm.slot_state_bytes(c) == 50_995_204
    # 64 slots, every one active: weights once, each slot's state read and
    # written
    assert ssm.step_bytes(c, [100] * 64) == 736_702_464 + 2 * 64 * 50_995_204


def test_starcoder2_3b_bytes():
    c = _cfg("starcoder2-3b")
    # the head tied to the embedding; biases on the query, key and value
    # projections (30 layers of 3072 + 2 * 256 in bfloat16)
    assert dense.weight_bytes(c) == 6_060_742_656
    assert "lm_head/w" not in dense.layout(c)
    assert dense.slot_state_bytes(c, 4096) == 125_829_120 + 4
    entry = 125_829_120 // 4096
    # a slot at position p reads keys and values 0..p and writes entry p
    assert dense.step_bytes(c, [0, 999]) == 6_060_742_656 + (2 + 1001) * entry


def test_prefill_operations():
    c = _cfg("starcoder2-3b")
    per_token = 2 * (3072 * (24 + 4) * 128 + 24 * 128 * 3072
                     + 2 * 3072 * 12288) * 30
    attn = 30 * 4 * 24 * 128 * (1024 * 1025 // 2)
    assert dense.prefill_flops(c, 1024) == 1024 * per_token + attn + 2 * 3072 * 49152
    m = _cfg("mamba2-370m")
    assert ssm.prefill_flops(m, 64) > 64 * 2 * (
        ssm.weight_bytes(m) // 2 - 50288 * 1024) * 0.99


@pytest.mark.parametrize("name,fam", [("mamba2-370m", ssm),
                                      ("starcoder2-3b", dense)])
def test_layout_matches_the_program_at_published_widths(name, fam):
    from repro import models
    from repro.configs import get_config

    c = _cfg(name)
    program = get_config(c["program_arch"])
    run = program.replace(**fam.program_overrides(c))
    # the file states the published values; the program's own defaults
    # for these models may differ, but never in a width
    for width in ("num_layers", "d_model", "num_heads", "num_kv_heads",
                  "d_ff", "ssm_state", "ssm_head_dim", "ssm_expand"):
        assert getattr(run, width) == getattr(program, width), width
    specs = {n: (tuple(s.shape), s.dtype)
             for n, s in models.param_specs(run).items()}
    assert specs == {n: (tuple(s[0]), s[1]) for n, s in fam.layout(c).items()}


def test_benchmark_names_resolve_to_files():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfgs = {c["name"]: c for c in spec["configs"]}
    for c in spec["configs"]:
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["name"] == c["name"]
        assert set(c["reduced"]) == set(data["reduced"])
        assert (ROOT / "bench" / "families" / f"{data['family']}.py").is_file()
    for w in spec["workloads"]:
        assert w["config"] in cfgs
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        assert (ROOT / "bench" / "limits" / f"{w['name']}.json").is_file()
    for m in spec["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
    cells = {w["name"] for w in spec["workloads"]}
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
