"""The harness end to end at a small size on the CPU: it refuses a CPU
device, a sound run is correct, and each fault planted under the timed path
turns ``correct`` false, as does the float8 control."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
DATA = Path(__file__).resolve().parent / "data"
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
LIMIT = 0.01     # logit gap of a float32 program at this size; sound runs read 0


def test_run_refuses_a_cpu_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "mamba2-370m.chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "refused" in p.stderr


def _cell(tmp_path, family, mix, compared=("logit_gap", LIMIT)):
    from bench import run as R

    root = tmp_path / "root"
    (root / "bench" / "traffic").mkdir(parents=True)
    (root / "bench" / "limits").mkdir(parents=True)
    shutil.copy(DATA / f"tiny-{family}.json", root / "cfg.json")
    shutil.copy(DATA / f"tiny-{mix}.json", root / "bench" / "traffic" / f"{mix}.json")
    (root / "bench" / "limits" / "cell.json").write_text(json.dumps({
        "sample": {"min_tokens": 300, "max_requests": 12},
        "compare": {"param_leaves_differing": 0,
                    "requests_unanswered_or_malformed": 0,
                    compared[0]: compared[1]}}))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "cfg", "file": "cfg.json"}]
    spec["workloads"] = [{"name": "cell", "config": "cfg", "traffic": mix,
                          "chips": 1}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    return R.Cell(spec, "cell", root=root)


def _run(tmp_path, family="ssm", mix="chat", trace=False, control=False,
         compared=("logit_gap", LIMIT)):
    from bench import run as R

    R.setup_jax()
    cell = _cell(tmp_path, family, mix, compared)
    return R.run_cell(cell, 2**31 + 17, 2.0, trace, peaks=PEAKS,
                      work_dir=tmp_path / "work", control=control)


@pytest.fixture(autouse=True)
def _private_compile_cache(tmp_path, monkeypatch):
    from bench import run as R

    monkeypatch.setattr(R, "CACHE_DIR", tmp_path / "jax-cache")


def test_sound_run_is_correct_and_the_control_is_not(tmp_path):
    out = _run(tmp_path, control=True)
    res = out["result"]
    assert res["correct"], res["checks"]
    assert res["attempted"] == 12 and res["failed"] == 0
    assert set(res["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert list(res)[-1] == "checks"
    assert "window: 0 programs lowered and 0 compiled" in out["lines"][0] \
        or any("window: 0 programs lowered and 0 compiled" in l
               for l in out["lines"])
    assert out["extra"]["served_tokens_checked"] > 0
    # the control, judged in the program's place by the same limits
    ctl = out["control"]
    assert not ctl["correct"], ctl["checks"]
    assert ctl["checks"]["logit_gap"]["value"] > 3 * LIMIT
    assert ctl["checks"]["logit_gap"]["limit"] == LIMIT
    assert any("not counted in setup_s" in l for l in out["lines"])


def test_traced_dense_run_reads_the_host_layers(tmp_path):
    res = _run(tmp_path, family="dense", mix="code", trace=True)["result"]
    assert res["correct"], res["checks"]
    m = res["metrics"]
    assert {"epoch_load_s", "lift_s"} <= set(m)
    assert m["epoch_load_s"]["value"] > 0
    # no device plane in a CPU trace: device metrics are left out, not 0
    assert "decode_hbm_roofline" not in m and "device_idle_share" not in m
    assert res["device"]["window_s"] > 0


def _roll(logits):
    import jax.numpy as jnp

    return jnp.roll(logits, 1, axis=-1)


def test_a_token_altered_where_it_is_produced_fails(tmp_path, monkeypatch):
    from repro import models

    real = models.decode_step

    def altered(cfg, params, cache, tokens):
        logits, cache = real(cfg, params, cache, tokens)
        return _roll(logits), cache

    monkeypatch.setattr(models, "decode_step", altered)
    res = _run(tmp_path)["result"]
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > LIMIT


def test_a_step_that_returns_its_state_unchanged_fails(tmp_path, monkeypatch):
    from repro import models

    real = models.decode_step

    def stuck(cfg, params, cache, tokens):
        logits, new = real(cfg, params, cache, tokens)
        return logits, dict(cache, pos=new["pos"])

    monkeypatch.setattr(models, "decode_step", stuck)
    res = _run(tmp_path)["result"]
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > LIMIT


def test_params_that_differ_from_the_published_bytes_fail(tmp_path, monkeypatch):
    from repro.serve import ServeEngine

    real = ServeEngine._lift_params

    def damaged(image, param_builder=None):
        params = real(image, param_builder)
        params["final_norm/scale"] = params["final_norm/scale"] * 2
        return params

    monkeypatch.setattr(ServeEngine, "_lift_params", staticmethod(damaged))
    res = _run(tmp_path)["result"]
    assert not res["correct"]
    assert res["checks"]["param_leaves_differing"]["value"] == 1


def test_a_cell_that_compares_the_mean_gap_fails_an_altered_token(
        tmp_path, monkeypatch):
    """Where a cell compares the mean gap over the served tokens, the
    float8 control and a token altered in the decode step both fail it."""
    from repro import models

    mean = ("mean_logit_gap", LIMIT / 10)
    out = _run(tmp_path, family="dense", mix="code", control=True,
               compared=mean)
    assert out["result"]["correct"], out["result"]["checks"]
    assert "logit_gap" not in out["result"]["checks"]
    assert not out["control"]["correct"], out["control"]["checks"]
    real = models.decode_step

    def altered(cfg, params, cache, tokens):
        logits, cache = real(cfg, params, cache, tokens)
        return _roll(logits), cache

    monkeypatch.setattr(models, "decode_step", altered)
    res = _run(tmp_path / "altered", family="dense", mix="code",
               compared=mean)["result"]
    assert not res["correct"]
    assert res["checks"]["mean_logit_gap"]["value"] > mean[1]
