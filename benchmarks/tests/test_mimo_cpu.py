"""The window/full expert cell end to end on the CPU at a tiny size,
through the harness's own functions (``run_cell``), the fp8 control
failing the limit there, and the parent-fails-fast contract of the
family. CPU numbers prove the control flow and the contract's shape,
never a speed.

``test_run_cpu.py``'s fixture renames the accepted cells by a fixed
table, so it cannot hold another cell; this file builds its own tiny
benchmark for the new one, as ``test_sarvam_cpu.py`` does.
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

CELL = "serve-mimo-longctx-batch"
MIMO = {
    "family": "hybrid_moe_serving", "reference": "mimo",
    "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 4,
    "hybrid_layer_pattern": [0, 1, 1, 0, 1, 1],
    "moe_layer_freq": [0, 1, 1, 1, 1, 1],
    "num_attention_heads": 8, "num_key_value_heads": 2,
    "swa_num_key_value_heads": 4, "swa_num_attention_heads": 8,
    "swa_head_dim": 24, "swa_v_head_dim": 16, "head_dim": 24,
    "v_head_dim": 16, "partial_rotary_factor": 0.334,
    "rope_theta": 50000, "swa_rope_theta": 100, "sliding_window": 24,
    "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False,
    "attention_value_scale": 0.707, "n_routed_experts": 4,
    "router_experts": 16, "expert_offset": 4, "num_experts_per_tok": 4,
    "routed_scaling_factor": None, "vocab_size": 256,
    "layernorm_epsilon": 1e-5, "torch_dtype": "bfloat16",
    "initializer_range": 0.3,      # wide logits at a tiny width
    "serving": {"page_size": 16, "max_length": 160, "pool_pages": None,
                "decode_chunk": 1, "prefill_chunk": None,
                "prefix_cache": False},
    "limits": {"served_logit_gap": 1.0}}
TRAFFIC = {"kind": "closed_loop", "max_batch": 2, "clients": 4,
           "ramp_s": 0.5, "cycle": 4, "trace_s": 1.0,
           "prompt": {"dist": "uniform", "min": 40, "max": 100},
           "output": {"dist": "uniform", "min": 6, "max": 12},
           "check_requests": 2}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A benchmark of the one tiny cell: the real BENCHMARK.json's
    entries for it, the real metric files, tiny configuration and
    traffic."""
    root = tmp_path_factory.mktemp("bench_mimo")
    data = root / "b"
    for d in ("configs", "traffic", "metrics"):
        (data / d).mkdir(parents=True)
    (data / "configs" / "mimo-tiny.json").write_text(json.dumps(MIMO))
    (data / "traffic" / "longctx-tiny.json").write_text(
        json.dumps(TRAFFIC))
    real = json.load(open(os.path.join(REPO, "BENCHMARK.json")))

    def mine(items):
        out = []
        for m in items:
            if "workloads" in m and CELL not in m["workloads"]:
                continue
            out.append(dict(m, workloads=[CELL]) if "workloads" in m
                       else dict(m))
        return out

    per_layer = mine(real["per_layer"])
    for m in per_layer:
        spec = open(os.path.join(REPO, "benchmarks", "metrics",
                                 m["name"] + ".json")).read()
        (data / "metrics" / f"{m['name']}.json").write_text(spec)
    bench = {"command": real["command"], "paths": ["b"], "run_seconds": 2,
             "configs": [{"name": "mimo-tiny", "source": "test",
                          "file": "b/configs/mimo-tiny.json",
                          "reduced": [], "why": "tiny"}],
             "workloads": [{"name": CELL, "config": "mimo-tiny",
                            "traffic": "longctx-tiny", "chips": 1,
                            "why": "tiny"}],
             "end_to_end": mine(real["end_to_end"]),
             "per_layer": per_layer}
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_on_cpu(root, trace):
    from benchmarks.harness.runner import run_cell

    lines = []
    out = run_cell(root, CELL, seed=2 ** 31 + 4321 + trace, seconds=2.0,
                   trace=bool(trace), require_tpu=False, say=lines.append)
    text = "\n".join(lines)
    line = json.loads(json.dumps(out))
    assert line["correct"] is True, text
    assert line["attempted"] > 0 and line["failed"] == 0, text
    assert any("routed pairs the expert layers dropped: 0 " in l
               for l in lines), text
    assert any("expert choices of an untimed full forward" in l
               for l in lines), text
    if trace:
        got = set(line["metrics"])
        # host counters read on any backend; the device-trace shares of
        # the kernel have nothing to read on the CPU and are left out
        assert {"moe_pairs_per_expert.served",
                "moe_load_max_over_mean.served",
                "batch_occupancy.served",
                "kv_bytes_per_context_token.served"} <= got, text
        assert "window_decode_attention_roofline.served" not in got
        assert "global_decode_attention_roofline.served" not in got
        # 2 full layers x 2 heads and 2 window layers x 4 heads of
        # (128 + 16) x 2 B a position as allocated, pages held whole
        kv = line["metrics"]["kv_bytes_per_context_token.served"]["value"]
        assert 1152 < kv < 20000
        # 2 rows x 4 chosen of 16 experts, 4 held: a pair per two
        # experts a step, give or take the router's taste
        v = line["metrics"]["moe_pairs_per_expert.served"]["value"]
        assert 0.0 < v < 2.0
        assert line["metrics"]["moe_load_max_over_mean.served"][
            "value"] >= 1.0
    else:
        assert set(line["metrics"]) == {"served_tokens_per_s", "setup_s"}


def test_altered_token_is_not_correct(root, monkeypatch):
    from benchmarks.harness.families import hybrid_moe_serving
    from benchmarks.harness.runner import run_cell

    real_pop = hybrid_moe_serving.System.pop_finished

    def altered(self):
        out = real_pop(self)
        for _, _, _, tokens in out:
            tokens[len(tokens) // 2] = (tokens[len(tokens) // 2] + 7) % 256
        return out

    monkeypatch.setattr(hybrid_moe_serving.System, "pop_finished", altered)
    lines = []
    out = run_cell(root, CELL, seed=79, seconds=1.5, trace=False,
                   require_tpu=False, say=lines.append)
    assert out["correct"] is False, "\n".join(lines)
    assert any("widest gap" in l and "FAIL" in l for l in lines)


def test_fp8_control_fails_the_limit():
    """The reference in fp8 in the program's place: the gap of its best
    token below the float32 reference's best passes a limit that a
    sound bf16-sized error stays under (tiny size, same arithmetic as
    ``tools/control.py`` reads on the chip)."""
    from benchmarks.references import mimo as ref

    cfg = dict(MIMO)
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

    code = ("import sys; import benchmarks.references.mimo; "
            "assert not any(m.startswith('paddle_tpu') "
            "for m in sys.modules)")
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr


def test_parent_fails_at_import():
    """A tree without the model fails where the family is imported,
    before anything is allocated: the module imports it at the top."""
    import benchmarks.harness.families.hybrid_moe_serving as fam

    src = open(fam.__file__).read()
    head = src[:src.index("class System")]
    assert "from paddle_tpu.models.hybrid_moe import" in head


def test_config_file_keeps_every_published_number():
    cfg = json.load(open(os.path.join(REPO, "benchmarks", "configs",
                                      "mimo-v2-flash.json")))
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = [c for c in bench["configs"] if c["name"] == "mimo-v2-flash"][0]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] == cfg["source"]
    for k, v in cfg["published"].items():
        if k in cfg["reduced"]:
            assert cfg[k] < v
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["v_head_dim"],
            cfg["sliding_window"], cfg["router_experts"],
            cfg["num_experts_per_tok"], cfg["moe_intermediate_size"],
            cfg["intermediate_size"]) == (4096, 192, 128, 128, 256, 8,
                                          2048, 16384)
    assert len(cfg["hybrid_layer_pattern"]) == 48 \
        and cfg["hybrid_layer_pattern"][:7] == [0, 1, 1, 1, 1, 0, 1]
    for key in ("deployment", "assumed", "limits", "serving"):
        assert key in cfg
