"""The shortcut-connected expert cell end to end on the CPU at a tiny
size, through the harness's own functions (``run_cell``, the family's
``control``), the fp8 control failing the limit there, the
parent-fails-fast contract of the family, the configuration file's
contract, the new scope reducer on a hand-made trace and the accepted
latent roofline reading EIGHT kernel calls a step as eight calls' cost.
CPU numbers prove the control flow and the contract's shape, never a
speed.
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

CELL = "serve-longcat-dialoggen-batch"
NAME = "longcat-flash-omni"
LONGCAT = {
    "family": "shortcut_moe_serving", "reference": "longcat",
    "hidden_size": 64, "ffn_hidden_size": 128, "expert_ffn_hidden_size": 32,
    "num_layers": 2, "num_hidden_layers": 4, "num_attention_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 4,
    "router_experts": 8, "expert_offset": 4, "zero_expert_num": 4,
    "zero_expert_type": "identity", "moe_topk": 3,
    "routed_scaling_factor": 6, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "attention_bias": False,
    "attention_method": "MLA", "vocab_size": 256, "rope_theta": 10000,
    "rms_norm_eps": 1e-5, "torch_dtype": "bfloat16",
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
    root = tmp_path_factory.mktemp("bench_longcat")
    data = root / "b"
    for d in ("configs", "traffic", "metrics"):
        (data / d).mkdir(parents=True)
    (data / "configs" / "longcat-tiny.json").write_text(json.dumps(LONGCAT))
    (data / "traffic" / "dialoggen-tiny.json").write_text(
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
             "configs": [{"name": "longcat-tiny", "source": "test",
                          "file": "b/configs/longcat-tiny.json",
                          "reduced": [], "why": "tiny"}],
             "workloads": [{"name": CELL, "config": "longcat-tiny",
                            "traffic": "dialoggen-tiny", "chips": 1,
                            "why": "tiny"}],
             "end_to_end": mine(real["end_to_end"]),
             "per_layer": per_layer}
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_on_cpu(root, trace, capsys):
    from benchmarks.harness.runner import run_cell

    lines = []
    out = run_cell(root, CELL, seed=2 ** 31 + 4545 + trace, seconds=2.0,
                   trace=bool(trace), require_tpu=False, say=lines.append)
    text = "\n".join(lines)
    line = json.loads(json.dumps(out))
    assert line["correct"] is True, text
    assert line["attempted"] > 0 and line["failed"] == 0, text
    assert any("routed pairs the expert layers dropped: 0 " in l
               for l in lines), text
    assert any("router choices of an untimed full forward" in l
               for l in lines), text
    if trace:
        got = set(line["metrics"])
        # host counters read on any backend; the device-trace shares
        # have nothing to read on the CPU and are left out
        assert {"moe_pairs_per_expert.served",
                "moe_load_max_over_mean.served",
                "moe_zero_pick_share.served", "batch_occupancy.served",
                "prefill_padding_share.served"} <= got, text
        assert "mla_decode_attention_roofline.served" not in got
        assert "shortcut_moe_time_share.served" not in got
        # 2 rows x 3 chosen of 12 outputs, 4 real experts held: half a
        # pair an expert a step, a third of the picks identity, give or
        # take the router's taste
        v = line["metrics"]["moe_pairs_per_expert.served"]["value"]
        assert 0.0 < v < 1.5
        z = line["metrics"]["moe_zero_pick_share.served"]["value"]
        assert 5.0 < z < 70.0
        # two pooled tuples a layer: 2 layers x 2 x (32 + 128) x 2 B a
        # position, and the unwritten tails of held pages. A host
        # reading only: the accepted metric's list is closed by
        # test_dsv32_cpu.py, which asserts its own cell LAST on it
        host = json.loads([l for l in capsys.readouterr().out.splitlines()
                           if l.startswith("host: ")][0][6:])
        assert host["kv_bytes_per_context_token"] >= 2 * 2 * (32 + 128) * 2
    else:
        assert set(line["metrics"]) == {"served_tokens_per_s", "setup_s"}


def test_altered_token_is_not_correct(root, monkeypatch):
    from benchmarks.harness.families import shortcut_moe_serving
    from benchmarks.harness.runner import run_cell

    real_pop = shortcut_moe_serving.System.pop_finished

    def altered(self):
        out = real_pop(self)
        for _, _, _, tokens in out:
            tokens[len(tokens) // 2] = (tokens[len(tokens) // 2] + 7) % 256
        return out

    monkeypatch.setattr(shortcut_moe_serving.System, "pop_finished",
                        altered)
    lines = []
    out = run_cell(root, CELL, seed=79, seconds=1.5, trace=False,
                   require_tpu=False, say=lines.append)
    assert out["correct"] is False, "\n".join(lines)
    assert any("widest gap" in l and "FAIL" in l for l in lines)


def test_control_runs_and_fp8_fails_the_limit(root):
    """``family.control`` as ``tools/control.py`` drives it (build, warm,
    a short window, the two readings): the program's gap passes the
    tiny cell's limit, the fp8 reference's best token lies further
    below the float32 reference's best than a bf16-sized error."""
    from benchmarks.harness import runner

    c = runner.Cell(root, CELL)
    seed = 2 ** 31 + 45
    plan = c.kind.plan(c.traffic, seed, 1.5, c.cfg["vocab_size"])
    import jax

    system = c.family.build(c.cfg, c.traffic, plan, seed,
                            jax.devices()[:1])
    system.warm()
    phases = runner.Phases(False, 0.0, "", runner.CompileCounter(),
                           lambda: 0)
    result = c.kind.run(system, plan, 1.5, phases)
    out = c.family.control(system, result)
    assert out["program"][0]["value"] <= out["program"][0]["limit"]
    assert out["control"][0]["value"] > 0.05
    assert out["control"][0]["value"] > out["program"][0]["value"]
    assert 0.0 <= out["program_choice_flips"] <= 1.0


def test_reference_imports_nothing_of_the_program():
    import subprocess

    code = ("import sys; import benchmarks.references.longcat; "
            "assert not any(m.startswith('paddle_tpu') "
            "for m in sys.modules)")
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr


def test_parent_fails_at_import(monkeypatch):
    """A tree whose ``MLAMoEConfig`` lacks the new fields fails where
    the family is imported, before anything is allocated."""
    import dataclasses
    import importlib

    import benchmarks.harness.families.shortcut_moe_serving as fam
    from paddle_tpu.models import mla_moe

    @dataclasses.dataclass
    class Parent:                   # fields the parent commit had
        vocab_size: int = 32000
        q_lora_rank: int = 0
        kv_lora_rank: int = 512
        head_on_last_row: bool = False

    monkeypatch.setattr(mla_moe, "MLAMoEConfig", Parent)
    try:
        with pytest.raises(ImportError, match="lacks mla_scale_kv_lora, "
                                              "mla_scale_q_lora, "
                                              "norm_topk_prob"):
            importlib.reload(fam)
    finally:
        monkeypatch.undo()
        importlib.reload(fam)


def test_config_file_keeps_every_published_number():
    cfg = json.load(open(os.path.join(
        REPO, "benchmarks", "configs", NAME + ".json")))
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = [c for c in bench["configs"] if c["name"] == NAME][0]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] == cfg["source"]
    for k in cfg["reduced"]:
        assert cfg[k] < cfg["published"][k]
    # every key of the catalog's config, unchanged unless reduced
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = [json.loads(l) for l in open(catalog)
               if json.loads(l)["source_url"] == cfg["source"]][0]
        assert row["name"] == "LongCat-Flash-Omni"
        for k, v in row["config"].items():
            assert k in cfg, k
            if k in cfg["reduced"]:
                assert cfg["published"][k] == v, k
            else:
                assert cfg[k] == v, k
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["q_lora_rank"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["ffn_hidden_size"],
            cfg["expert_ffn_hidden_size"], cfg["moe_topk"],
            cfg["zero_expert_num"], cfg["router_experts"],
            cfg["routed_scaling_factor"], cfg["rope_theta"],
            cfg["rms_norm_eps"], cfg["max_position_embeddings"]) == (
        6144, 64, 1536, 512, 128, 64, 128, 12288, 2048, 12, 256, 512, 6,
        1e7, 1e-5, 131072)
    assert cfg["mla_scale_q_lora"] is cfg["mla_scale_kv_lora"] is True
    assert "rope_scaling" not in cfg
    assert (cfg["num_layers"], cfg["n_routed_experts"],
            cfg["expert_offset"], cfg["vocab_size"]) == (4, 16, 0, 16384)
    # the derived key the accepted reducers multiply a call's cost by
    assert cfg["num_hidden_layers"] == 2 * cfg["num_layers"]
    assert cfg["published"]["chips_sharing_a_layer"] == 32
    assert cfg["published"]["n_routed_experts"] \
        == 32 * cfg["n_routed_experts"]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    for key in ("deployment", "assumed", "limits", "serving"):
        assert key in cfg
    for key in ("num_layers", "num_hidden_layers", "n_routed_experts",
                "vocab_size", "block", "router", "router_bias",
                "mla_scale", "rope", "weights", "max_length", "pool",
                "modes"):
        assert key in cfg["assumed"], key
    assert set(cfg["limits"]) == {"served_logit_gap"}
    cell = [w for w in bench["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "dialoggen-batch", 1)
    traffic = json.load(open(os.path.join(
        REPO, "benchmarks", "traffic", "dialoggen-batch.json")))
    assert (traffic["kind"], traffic["max_batch"], traffic["clients"],
            traffic["cycle"], traffic["check_requests"], traffic["ramp_s"],
            traffic["trace_s"]) == ("closed_loop", 128, 192, 64, 4, 30.0,
                                    5.0)
    # the issue's stated remedy (its outputs 384-768 spread too widely to
    # be admitted): the longest request ends two pages short of max_length
    assert (traffic["output"]["min"], traffic["output"]["max"]) == (256, 512)
    assert traffic["prompt"]["max"] + traffic["output"]["max"] \
        <= cfg["serving"]["max_length"]
    assert traffic["prompt"]["max"] == 512          # three buckets
    srv = cfg["serving"]
    pages = -(-srv["max_length"] // srv["page_size"])
    assert traffic["max_batch"] * pages + 1 <= srv["pool_pages"]
    # the mean pairs a held expert a step the cell's why states
    width = cfg["router_experts"] + cfg["zero_expert_num"]
    assert traffic["max_batch"] * cfg["moe_topk"] / width == 2.0
    # the new cell is on every accepted metric's list it reports
    lists = {m["name"]: m.get("workloads", []) for m in bench["per_layer"]}
    for name in ("moe_zero_pick_share.served",
                 "shortcut_moe_time_share.served"):
        assert lists[name] == [CELL]
    for name in ("batch_occupancy.served", "prefill_device_share.served",
                 "pallas_time_share.served", "device_idle_share.served",
                 "peak_hbm_gib.served",
                 "mla_decode_attention_roofline.served",
                 "mla_decode_time_share.served",
                 "moe_pairs_per_expert.served",
                 "moe_load_max_over_mean.served",
                 "idle_in_prefill_share.served",
                 "idle_in_decode_round_share.served",
                 "idle_in_bookkeeping_share.served",
                 "prefill_dispatch_ms_p50.served",
                 "decode_fetch_wait_share.served",
                 "prefill_padding_share.served"):
        assert CELL in lists[name], name
    for name in ("sparse_mla_decode_attention_roofline.served",
                 "window_decode_attention_roofline.served",
                 "sparse_selected_share.served",
                 "kv_bytes_per_context_token.served"):
        assert CELL not in lists[name]
    e2e = {m["name"]: m.get("workloads") for m in bench["end_to_end"]}
    assert CELL in e2e["served_tokens_per_s"]


def test_named_scope_reducer_by_hand():
    from benchmarks.harness import reduce as R
    from benchmarks.harness.reducers import inner_scope_share as iss

    spec = json.load(open(os.path.join(
        REPO, "benchmarks", "metrics",
        "shortcut_moe_time_share.served.json")))
    assert spec["reducer"] == "named_scope_share"
    known = tuple(spec["args"]["known"])
    base = "jit(step)/mla_moe/layer3"
    assert iss.innermost(base + "/layer3.moe.shortcut/dot_general:",
                         known) == "layer3.moe.shortcut"
    assert iss.innermost(
        base + "/layer3.attn1/mla_paged_decode_attention/pallas_call:",
        known) == "layer3.attn1"
    assert iss.innermost(base + "/add:", known) == ""
    # the accepted reducer's own scopes do not hold the new endings
    assert iss.innermost(base + "/layer3.moe.shortcut/dot_general:") == ""
    # window 0..10 s, one device busy 8 s: 2 s of the shortcut branch,
    # 1 + 1 s of the attentions, 1.5 + 1.5 s of the dense parts, 1 else
    ops = [R.Op("a", 0.0, 2.0, 0, "layer0.moe.shortcut"),
           R.Op("b", 2.0, 1.0, 0, "layer0.attn0"),
           R.Op("c", 3.0, 1.0, 0, "layer1.attn1"),
           R.Op("d", 4.0, 1.5, 0, "layer0.mlp0"),
           R.Op("e", 5.5, 1.5, 0, "layer2.mlp1"),
           R.Op("f", 7.0, 1.0, 0, "")]
    tr = R.Trace(ops, [], (0.0, 10.0))
    assert iss.share(tr, spec["args"]["endings"]) \
        == pytest.approx(100 * 2.0 / 8.0)
    assert iss.share(tr, ["attn0", "attn1"]) == pytest.approx(25.0)
    assert iss.share(tr, known) == pytest.approx(100 * 7.0 / 8.0)
    # XLA's grouped matmul reaches a TPU trace with no scope: the new
    # reducer counts it by name where the metric's file says so, and
    # never an op that has a scope of its own
    from benchmarks.harness.reducers import named_scope_share as nss

    assert spec["args"]["names"] == ["ragged-dot"]
    ops += [R.Op("ragged-dot-none.3", 8.0, 0.5, 0, "", "f32[256,2048]"),
            R.Op("ragged-dot-none.9", 8.5, 0.5, 0, "layer0.mlp0", "")]
    tr = R.Trace(ops, [], (0.0, 10.0))
    assert nss.share(tr, ["moe.shortcut"]) == pytest.approx(100 * 2.0 / 9.0)
    assert nss.share(tr, **{k: spec["args"][k] for k in (
        "endings", "names")}) == pytest.approx(100 * 2.5 / 9.0)
    assert nss.share(R.Trace([], [], (0.0, 1.0)), ["moe.shortcut"]) is None


def test_named_scope_reducer_reads_nothing_without_a_trace(monkeypatch):
    from benchmarks.harness import program_spans as PS
    from benchmarks.harness import reduce as R
    from benchmarks.harness.reducers import named_scope_share

    monkeypatch.setattr(PS, "last_trace", lambda: None)
    ctx = {"trace": R.Trace([], [], (0.0, 1.0))}
    assert named_scope_share.read(ctx, ["moe.shortcut"],
                                  ["moe.shortcut"]) is None


def test_mla_roofline_reads_eight_calls_a_step_as_eight_calls_cost():
    """One traced decode step of one row at 128 positions through the
    file's 8 attention sublayers: a synthetic kernel that takes exactly
    the roofline's time a call reads 100%, never more; counted as the 4
    layers of the source's ``num_layers`` it would read 50%."""
    from benchmarks.harness import reduce as R
    from benchmarks.harness.kernel_cost import least_seconds
    from benchmarks.harness.mla_cost import latent_decode
    from benchmarks.harness.peaks import peaks_for
    from benchmarks.harness.reducers import mla_roofline

    cfg = json.load(open(os.path.join(
        REPO, "benchmarks", "configs", NAME + ".json")))
    pk = peaks_for("TPU v5 lite")
    one = least_seconds(*latent_decode(
        [(1, 128)], cfg["num_attention_heads"], cfg["kv_lora_rank"],
        cfg["qk_rope_head_dim"], cfg["serving"]["page_size"]), pk)
    calls = 2 * cfg["num_layers"]
    ops = [R.Op(f"custom-call.{i}", i * 2 * one, one, 0, "jit_step",
                "mla_paged_decode_attention mosaic") for i in range(calls)]
    tr = R.Trace(ops, [], (0.0, calls * 2 * one))
    ctx = {"trace": tr, "cfg": cfg, "host": {"decode_rows": [(1, 128)]},
           "peaks": pk}
    v = mla_roofline.read(ctx, program="jit_step", rows="decode_rows")
    assert v == pytest.approx(100.0) and v <= 100.0 + 1e-9
    as_layers = dict(cfg, num_hidden_layers=cfg["num_layers"])
    assert mla_roofline.read(dict(ctx, cfg=as_layers), "jit_step",
                             "decode_rows") == pytest.approx(50.0)
