"""The mixed-length AFMoE cell end to end on the CPU at a tiny size,
through the harness's own functions (``run_cell``), an altered token and
a planted dropped pair failing ``check``, the fp8 control failing the
limit there, and the parent-fails-fast contract of the family. CPU
numbers prove the control flow and the contract's shape, never a speed.

``test_run_cpu.py``'s fixture renames the accepted cells by a fixed
table, so it cannot hold another cell; this file builds its own tiny
benchmark for the new one, as ``test_mimo_cpu.py`` does.
"""
import json
import os
import sys

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

CELL = "serve-trinity-mixedlen-batch"
TRINITY = {
    "family": "afmoe_serving", "reference": "trinity",
    "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 6,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"] * 3 + ["full_attention"],
    "layers_run": [1, 2, 3, 4, 5, 7], "num_dense_layers": 2,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "rope_theta": 100, "sliding_window": 48, "num_experts": 4,
    "router_experts": 16, "expert_offset": 8, "num_experts_per_tok": 4,
    "route_scale": 2.826, "num_shared_experts": 1, "mup_enabled": True,
    "vocab_size": 256, "rms_norm_eps": 1e-5, "torch_dtype": "bfloat16",
    "initializer_range": 0.3,      # wide logits at a tiny width
    # the derived keys the accepted reducers read
    "hybrid_layer_pattern": [1, 1, 0, 1, 1, 0],
    "swa_num_key_value_heads": 2, "v_head_dim": 16,
    "serving": {"page_size": 16, "max_length": 192, "pool_pages": None,
                "decode_chunk": 1, "prefill_chunk": None,
                "prefix_cache": False},
    "limits": {"served_logit_gap": 1.0, "served_logit_mean_gap": 0.1}}
TRAFFIC = {"kind": "closed_loop", "max_batch": 2, "clients": 4,
           "ramp_s": 0.5, "cycle": 4, "trace_s": 1.0,
           "prompt": {"dist": "lognormal", "median": 60, "sigma": 1.0,
                      "min": 20, "max": 128},
           "output": {"dist": "uniform", "min": 6, "max": 12},
           "check_requests": 2}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A benchmark of the one tiny cell: the real BENCHMARK.json's
    entries for it, the real metric files, tiny configuration and
    traffic."""
    root = tmp_path_factory.mktemp("bench_trinity")
    data = root / "b"
    for d in ("configs", "traffic", "metrics"):
        (data / d).mkdir(parents=True)
    (data / "configs" / "trinity-tiny.json").write_text(
        json.dumps(TRINITY))
    (data / "traffic" / "mixedlen-tiny.json").write_text(
        json.dumps(TRAFFIC))
    real = json.load(open(os.path.join(REPO, "BENCHMARK.json")))

    def mine(items):
        return [dict(m, workloads=[CELL]) if "workloads" in m else dict(m)
                for m in items
                if "workloads" not in m or CELL in m["workloads"]]

    per_layer = mine(real["per_layer"])
    for m in per_layer:
        spec = open(os.path.join(REPO, "benchmarks", "metrics",
                                 m["name"] + ".json")).read()
        (data / "metrics" / f"{m['name']}.json").write_text(spec)
    bench = {"command": real["command"], "paths": ["b"], "run_seconds": 2,
             "configs": [{"name": "trinity-tiny", "source": "test",
                          "file": "b/configs/trinity-tiny.json",
                          "reduced": [], "why": "tiny"}],
             "workloads": [{"name": CELL, "config": "trinity-tiny",
                            "traffic": "mixedlen-tiny", "chips": 1,
                            "why": "tiny"}],
             "end_to_end": mine(real["end_to_end"]),
             "per_layer": per_layer}
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_on_cpu(root, trace):
    from benchmarks.harness.runner import run_cell

    lines = []
    out = run_cell(root, CELL, seed=2 ** 31 + 3535 + trace, seconds=2.0,
                   trace=bool(trace), require_tpu=False, say=lines.append)
    text = "\n".join(lines)
    line = json.loads(json.dumps(out))
    assert line["correct"] is True, text
    assert line["attempted"] > 0 and line["failed"] == 0, text
    assert any("routed pairs the expert layers dropped: 0 " in l
               for l in lines), text
    if trace:
        got = set(line["metrics"])
        # host counters read on any backend; the device-trace shares of
        # the kernels have nothing to read on the CPU and are left out
        assert {"moe_pairs_per_expert.served",
                "moe_load_max_over_mean.served", "batch_occupancy.served",
                "kv_bytes_per_context_token.served",
                "window_ring_fill.served",
                "prefill_padding_share.served"} <= got, text
        assert "window_decode_attention_roofline.served" not in got
        assert "global_decode_attention_roofline.served" not in got
        # a ring of 4 pages of 16: contexts of 26..140 fill 2 to 4
        fill = line["metrics"]["window_ring_fill.served"]["value"]
        assert 25.0 <= fill <= 100.0
        # prompts of 20..128 on the buckets 64 and 128
        pad = line["metrics"]["prefill_padding_share.served"]["value"]
        assert 0.0 < pad < 70.0
    else:
        assert set(line["metrics"]) == {"served_tokens_per_s", "setup_s"}


def test_altered_token_is_not_correct(root, monkeypatch):
    from benchmarks.harness.families import afmoe_serving
    from benchmarks.harness.runner import run_cell

    real_pop = afmoe_serving.System.pop_finished

    def altered(self):
        out = real_pop(self)
        for _, _, _, tokens in out:
            tokens[len(tokens) // 2] = (tokens[len(tokens) // 2] + 7) % 256
        return out

    monkeypatch.setattr(afmoe_serving.System, "pop_finished", altered)
    lines = []
    out = run_cell(root, CELL, seed=79, seconds=1.5, trace=False,
                   require_tpu=False, say=lines.append)
    assert out["correct"] is False, "\n".join(lines)
    assert any("widest gap" in l and "FAIL" in l for l in lines)
    assert any("mean gap" in l and "FAIL" in l for l in lines)


def test_planted_dropped_pair_is_not_correct(root, monkeypatch):
    from benchmarks.harness.families import afmoe_serving
    from benchmarks.harness.runner import run_cell

    real = afmoe_serving.System.moe_host

    def dropped(self):
        return dict(real(self), moe_dropped_pairs=1)

    monkeypatch.setattr(afmoe_serving.System, "moe_host", dropped)
    lines = []
    out = run_cell(root, CELL, seed=80, seconds=1.5, trace=False,
                   require_tpu=False, say=lines.append)
    assert out["correct"] is False, "\n".join(lines)
    assert any("routed pairs the expert layers dropped: 1 " in l
               and "FAIL" in l for l in lines)


def test_fp8_control_fails_the_limit():
    """The reference in fp8 in the program's place: the gap of its best
    token below the float32 reference's best passes a limit that a
    sound bf16-sized error stays under (tiny size, same arithmetic as
    ``tools/control.py`` reads on the chip)."""
    from benchmarks.references import trinity as ref

    cfg = dict(TRINITY)
    rng = np.random.default_rng(5)
    reqs = [(rng.integers(0, 256, 48), rng.integers(0, 256, 24))
            for _ in range(3)]
    want = ref.ServeReference(cfg, 11).logits(reqs)
    low = ref.ServeReference(cfg, 11, "fp8").logits(reqs)
    same = ref.ServeReference(cfg, 11).logits(reqs)
    ctl = max(ref.served_gap(w, l.argmax(-1)).max()
              for w, l in zip(want, low))
    assert ctl > 0.05
    assert max(ref.served_gap(w, s.argmax(-1)).max()
               for w, s in zip(want, same)) == 0.0


def test_reference_imports_nothing_of_the_program():
    import subprocess

    code = ("import sys; import benchmarks.references.trinity; "
            "assert not any(m.startswith('paddle_tpu') "
            "for m in sys.modules)")
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr


def test_parent_fails_at_import(monkeypatch):
    """A tree whose ``HybridMoEConfig`` lacks the switches fails where
    the family is imported, before anything is allocated."""
    import dataclasses
    import importlib

    import benchmarks.harness.families.afmoe_serving as fam
    from paddle_tpu.models import hybrid_moe

    @dataclasses.dataclass
    class Parent:                   # the fields the parent commit had
        vocab_size: int = 32000
        sliding_window: int = 128

    monkeypatch.setattr(hybrid_moe, "HybridMoEConfig", Parent)
    try:
        with pytest.raises(ImportError, match="lacks .*qk_norm"):
            importlib.reload(fam)
    finally:
        monkeypatch.undo()
        importlib.reload(fam)


def test_config_file_keeps_every_published_number():
    cfg = json.load(open(os.path.join(REPO, "benchmarks", "configs",
                                      "trinity-mini.json")))
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = [c for c in bench["configs"] if c["name"] == "trinity-mini"][0]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_experts"]
    assert entry["source"] == cfg["source"]
    for k, v in cfg["published"].items():
        if k in cfg["reduced"]:
            assert cfg[k] < v
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["sliding_window"],
            cfg["router_experts"], cfg["num_experts_per_tok"],
            cfg["moe_intermediate_size"], cfg["intermediate_size"],
            cfg["vocab_size"], cfg["route_scale"], cfg["num_dense_layers"],
            cfg["num_shared_experts"]) == (
        2048, 128, 32, 4, 2048, 128, 8, 1024, 6144, 200192, 2.826, 2, 1)
    assert len(cfg["layer_types"]) == 32
    assert cfg["layers_run"] == list(range(1, 10))
    kinds = [cfg["layer_types"][l] for l in cfg["layers_run"]]
    assert kinds.count("full_attention") == 2 and len(kinds) == 9
    # the derived keys say what the published ones say
    assert cfg["hybrid_layer_pattern"] == [
        int(k == "sliding_attention") for k in kinds]
    assert cfg["swa_num_key_value_heads"] == cfg["num_key_value_heads"]
    assert cfg["v_head_dim"] == cfg["head_dim"]
    for key in ("deployment", "assumed", "limits", "serving", "derived"):
        assert key in cfg
    for key in ("qk_norm", "rotary", "attention_gate", "sandwich_norm",
                "mup_enabled", "router", "weights"):
        assert key in cfg["assumed"]
    cell = [w for w in bench["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "trinity-mini", "mixedlen-batch", 1)
