"""The routed-expert family: its plain reference against the program at a
small size on the CPU, the shares of the experts against the uncut layer,
its byte counts at the published size, the harness end to end on a tiny
cell, and the grouped-matmul roofline's reader."""

import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench.families import moe  # noqa: E402
from bench.harness import check, quant, weights  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


def _tiny(**over):
    from repro.configs import get_config

    c = dict(json.loads((DATA / "tiny-moe.json").read_text()), **over)
    prog = get_config(c["program_arch"]).replace(**moe.program_overrides(c))
    return c, moe.layout(c), prog


def test_reference_matches_models_forward():
    from repro import models

    c, layout, prog = _tiny()
    params = weights.generate(layout, c["weight_seed"])
    T = 32
    tokens = np.random.default_rng(0).integers(0, 256, (2, T), dtype=np.int32)
    want = np.asarray(models.forward(prog, params,
                                     {"tokens": jnp.asarray(tokens)})[0])
    ref = check.Reference(moe, c, layout, c["weight_seed"], batch=2, length=T,
                          n_pos=T, vocab=256)
    idx = np.broadcast_to(np.arange(T, dtype=np.int32), (2, T))
    got = np.asarray(ref.logits(jnp.asarray(tokens), jnp.asarray(idx), "f32"))
    err = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert err < 1e-4, err
    low = np.asarray(ref.logits(jnp.asarray(tokens), jnp.asarray(idx), "fp8"))
    err_low = np.max(np.abs(low - want)) / np.max(np.abs(want))
    assert 30 * err < err_low < 1.0, (err, err_low)


def test_prefill_then_decode_through_the_slot_pool_matches_the_reference():
    """Rows prefilled alone at different lengths, pooled, then stepped
    together: every step's logits against the reference's full forward
    over the prompt and the tokens fed so far. One slot stays free."""
    from repro import models

    c, layout, prog = _tiny()
    params = weights.generate(layout, c["weight_seed"])
    rng = np.random.default_rng(3)
    lens, n_steps, S = (5, 11, 8), 4, 32
    prompts = [rng.integers(0, 256, n, dtype=np.int32) for n in lens]
    rows, seqs = [], []
    for p in prompts:
        lg, cache, counts = models.prefill(
            prog, params, {"tokens": jnp.asarray(p)[None]}, impl="naive",
            cache_len=S, counters=True)
        assert 0 < int(counts[0]) <= len(p) * 2 * 2   # rows: T k L at most
        rows.append(cache)
        seqs.append([*p.tolist(), int(jnp.argmax(lg[0, -1]))])
    rows.append(rows[0])                               # the free slot
    pool = jax.tree_util.tree_map(
        lambda *r: jnp.stack(r) if r[0].ndim == 0 else jnp.concatenate(r, 1),
        *rows)
    active = jnp.asarray([True, True, True, False])
    got = []
    for _ in range(n_steps):
        toks = jnp.asarray([[s[-1]] for s in seqs] + [[0]], jnp.int32)
        lg, pool, counts = models.decode_step(prog, params, pool, toks,
                                              active=active, counters=True)
        assert 0 < int(counts[0]) <= 3 * 2 * 2         # free slot routes none
        got.append(np.asarray(lg[:3, 0]))
        for b, s in enumerate(seqs):
            s.append(int(jnp.argmax(lg[b, 0])))
    ref = check.Reference(moe, c, layout, c["weight_seed"], batch=3, length=S,
                          n_pos=n_steps, vocab=256)
    tokens = np.zeros((3, S), np.int32)
    idx = np.zeros((3, n_steps), np.int32)
    for b, s in enumerate(seqs):
        tokens[b, :len(s)] = s
        idx[b] = lens[b] + np.arange(n_steps)
    want = np.asarray(ref.logits(jnp.asarray(tokens), jnp.asarray(idx), "f32"))
    got = np.stack(got, 1)
    err = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert err < 1e-4, err


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips' shares of eight experts, two each: their MoE outputs,
    in the program and in the reference, add up to the reference's layer
    with every expert held."""
    from repro.models.moe import moe_block

    c, layout, _ = _tiny(num_experts=8, first_held_expert=0)
    p = weights.layer_fn(layout, c["weight_seed"])(0)
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64), jnp.float32)
    mm = quant.matmul("f32")
    with jax.default_matmul_precision("highest"):
        w = moe.routing(c, h, p["router/w"], mm)
        whole = moe.experts(c, p, h, w, mm)
        ref_parts, prog_parts = [], []
        for chip in range(4):
            ids = np.arange(2 * chip, 2 * chip + 2)
            share = {n: p[n][ids] for n in
                     ("experts/w_gate", "experts/w_up", "experts/w_down")}
            ref_parts.append(moe.experts(c, share, h, w, mm, held=ids))
            out, _, counts = moe_block(
                h, p["router/w"], *share.values(), k=2, first=2 * chip,
                norm_topk=False)
            prog_parts.append(out)
            assert int(counts[1]) <= 2
    np.testing.assert_allclose(np.asarray(sum(ref_parts)), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(sum(prog_parts)), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(whole).max()) > 0.1


def _cfg():
    return json.loads((ROOT / "bench" / "configs" / "olmoe-1b-7b.json").read_text())


def test_olmoe_1b_7b_layout_and_bytes():
    from repro import models
    from repro.configs import get_config

    c = _cfg()
    program = get_config(c["program_arch"])
    run = program.replace(**moe.program_overrides(c))
    for width in ("num_layers", "d_model", "num_heads", "num_kv_heads",
                  "d_ff", "num_experts", "experts_per_token"):
        assert getattr(run, width) == getattr(program, width), width
    assert (run.held_experts, run.num_experts) == (16, 64)
    specs = {n: (tuple(s.shape), s.dtype)
             for n, s in models.param_specs(run).items()}
    assert specs == {n: (tuple(s[0]), s[1]) for n, s in moe.layout(c).items()}
    # 16 layers of 117,579,776 held parameters, embedding, norm and head
    assert moe.weight_bytes(c) == 2 * (16 * 117_579_776 + 206_047_232)
    assert moe.slot_state_bytes(c, 1536) == 1536 * 131_072 + 4
    assert 48 * 1536 * 131_072 == 9_663_676_416
    # 48 rows each choosing 8 of 64 leave a held expert unhit 0.2 % of steps
    assert moe.experts_hit(c, 48) == pytest.approx(16 * (1 - (7 / 8) ** 48))
    dense = moe._dense_weight_bytes(c)
    assert moe.step_bytes(c, []) == dense
    assert moe.step_bytes(c, [0] * 48) == pytest.approx(
        dense + 16 * moe.experts_hit(c, 48) * moe.expert_bytes(c)
        + 48 * 2 * 131_072)
    assert moe.gmm_flops(c, 10) == 6 * 10 * 2048 * 1024
    assert moe.gmm_bytes(c, 10, 3) == 3 * 6 * 2048 * 1024 + 10 * 6 * 3072


PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
LIMIT = 0.01


def test_sound_tiny_moe_run_is_correct_and_the_control_is_not(tmp_path,
                                                              monkeypatch):
    from bench import run as R
    from repro.core import spans

    monkeypatch.setattr(R, "CACHE_DIR", tmp_path / "jax-cache")
    root = tmp_path / "root"
    (root / "bench" / "traffic").mkdir(parents=True)
    (root / "bench" / "limits").mkdir(parents=True)
    shutil.copy(DATA / "tiny-moe.json", root / "cfg.json")
    shutil.copy(DATA / "tiny-chat.json", root / "bench" / "traffic" / "chat.json")
    (root / "bench" / "limits" / "cell.json").write_text(json.dumps({
        "sample": {"min_tokens": 300, "max_requests": 12},
        "compare": {"param_leaves_differing": 0,
                    "requests_unanswered_or_malformed": 0,
                    "logit_gap": LIMIT}}))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "cfg", "file": "cfg.json"}]
    spec["workloads"] = [{"name": "cell", "config": "cfg", "traffic": "chat",
                          "chips": 1}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    R.setup_jax()
    cell = R.Cell(spec, "cell", root=root)
    out = R.run_cell(cell, 2**31 + 29, 2.0, True, peaks=PEAKS,
                     work_dir=tmp_path / "work", control=True)
    res = out["result"]
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert not out["control"]["correct"], out["control"]["checks"]
    # no device plane in a CPU trace: the kernel's share is left out
    assert "moe_gmm_roofline" not in res["metrics"]
    steps = [r for r in spans.records() if r.name == "serve.step"
             and r.attrs and "moe_rows" in r.attrs]
    # rows: each active row's 2 choices in each of 2 layers, at most;
    # (layer, held expert) pairs: 2 x 4 at most
    assert steps and all(
        r.attrs["moe_rows"] <= r.attrs["n_active"] * 2 * 2
        and r.attrs["moe_experts_hit"] <= min(2 * 4, r.attrs["moe_rows"])
        for r in steps)
    assert sum(r.attrs["moe_rows"] for r in steps) > 0
    prefills = [r for r in spans.records() if r.name == "serve.admit.prefill"
                and "moe_rows" in (r.attrs or {})]
    assert prefills


def test_gmm_roofline_reader_on_a_hand_built_trace():
    """The reader's need over the kernel's device time: 2 ms of
    ``%moe_gmm`` operations and 1.5 ms more of staging one layer's held
    experts' weights (one copy overlapping a kernel call by 0.5 ms) inside
    the slice, one step's counters."""
    import importlib.util

    from bench.harness.trace import Trace
    from repro.core import spans

    spec = importlib.util.spec_from_file_location(
        "moe_gmm_roofline", ROOT / "bench" / "metrics" / "moe_gmm_roofline.py")
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    with spans.span("serve.step", n_active=3) as sp:
        sp.annotate(moe_rows=40, moe_experts_hit=20)
    t0 = [r for r in spans.records() if r.name == "serve.step"][-1].t0_ns
    lo, hi = t0 - 1_000_000, t0 + 10_000_000
    ms = 1_000_000
    c = _cfg()
    s = moe.sizes(c)
    w_up = f"bf16[{s['held']},{s['d']},{s['ff']}]"
    w_down = f"bf16[{s['held']},{s['ff']},{s['d']}]"
    tr = Trace(ops=[(t0, t0 + ms, "%moe_gmm.3 = bf16[384,1024] custom-call()"),
                    (t0 + ms, t0 + 2 * ms, "%moe_gmm = bf16[384,2048] custom-call()"),
                    (t0 + 3 * ms, t0 + 4 * ms,
                     f"%dynamic-slice_bitcast_fusion.7 = {w_up}{{2,1,0}} fusion()"),
                    (t0 + 3 * ms // 2, t0 + 5 * ms // 2,
                     f"%dynamic-slice_bitcast_fusion.8 = {w_down} fusion()"),
                    (t0, t0 + 5 * ms, "%fusion.1 = f32[4] fusion()"),
                    (t0, t0 + 5 * ms, f"%fusion.2 = bf16[2,{s['d']},{s['ff']}] fusion()")],
               devices=1, lo=lo, hi=hi, mono_offset_ns=0)

    class Run:
        trace, family, peaks = tr, moe, {"bf16_flops_per_s": 197e12,
                                         "hbm_bytes_per_s": 819e9}
    Run.c = c
    need = max(moe.gmm_flops(c, 40) / 197e12, moe.gmm_bytes(c, 40, 20) / 819e9)
    assert reader.read(Run) == pytest.approx(100 * need / 3.5e-3)
    Run.family = sys.modules["bench.families.dense"]
    assert reader.read(Run) is None
