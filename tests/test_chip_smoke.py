"""The chip smoke script and the one-chip-per-process plumbing, on the CPU.

``chip_smoke.py`` itself only passes on a TPU; here it must refuse the CPU
loudly, and its phases run at a reduced size so their checks stay honest.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
SMOKE = REPO / "chip_smoke.py"


def _reduced_bf16():
    from repro.configs import get_config

    return get_config("mamba2-370m", smoke=True).replace(dtype="bfloat16")


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_chip_smoke_refuses_the_cpu(tmp_path, where):
    """No CPU branch: on the CPU, and in a directory holding nothing of the
    repo but the script, it exits non-zero and prints no result line."""
    script = SMOKE
    if where == "alone":
        script = tmp_path / "chip_smoke.py"
        shutil.copy(SMOKE, script)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True,
        env=env, cwd=script.parent, timeout=300,
    )
    assert out.returncode != 0
    last = (out.stdout.strip().splitlines() or [""])[-1]
    assert '"ok": true' not in last
    if where == "repo":
        assert "platform is 'cpu'" in out.stderr


def test_serve_phase_checks_hold_at_reduced_size():
    """(a)-(d) on a 2-layer bf16 mamba2: weights round-trip to the device
    before and after the flip, cached logits agree with the forward,
    every request completes, and the flip compiles nothing."""
    sys.path.insert(0, str(REPO))
    import chip_smoke

    assert chip_smoke.serve_phase(_reduced_bf16(), seed=0) == []


def test_fleet_phase_checks_hold_at_reduced_size(tmp_path):
    """The fleet phase in a fresh process: the dispatcher stays off JAX's
    backends, one worker and two workers answer every request, and each
    request's tokens agree between the two runs."""
    code = textwrap.dedent(f"""
        import json, sys
        sys.path.insert(0, {str(REPO)!r})
        import chip_smoke
        from repro.configs import get_config

        if __name__ == "__main__":
            cfg = get_config("mamba2-370m", smoke=True).replace(dtype="bfloat16")
            runs, failures = chip_smoke.fleet_phase(cfg, 0, 2)
            print(json.dumps({{"failures": failures,
                              "devices": runs[2].devices}}))
    """)
    script = tmp_path / "fleet.py"
    script.write_text(code)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True,
        env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["failures"] == []
    assert [d["platform"] for d in res["devices"]] == ["cpu", "cpu"]


@pytest.mark.parametrize("given", [None, "outside"])
def test_compile_cache_placed_from_outside(tmp_path, given):
    """A cache directory given in the environment is used as it is;
    otherwise the fixed ``<repo>/.jax_cache``, exported for workers."""
    code = textwrap.dedent("""
        import json, os
        import jax
        from repro.core.chips import compile_cache_dir

        path = compile_cache_dir()
        print(json.dumps([path, os.environ["JAX_COMPILATION_CACHE_DIR"],
                          jax.config.jax_compilation_cache_dir]))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = str(REPO / ".jax_cache")
    if given:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / given)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) == [want] * 3


def test_pinned_to_chip_scopes_the_worker_environment(monkeypatch):
    from repro.core.chips import pinned_to_chip

    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    with pinned_to_chip(3) as env:
        assert os.environ["TPU_VISIBLE_CHIPS"] == "3"
        assert os.environ["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert env["TPU_PROCESS_ADDRESSES"] == (
            f"localhost:{env['TPU_PROCESS_PORT']}"
        )
    assert "TPU_VISIBLE_CHIPS" not in os.environ
