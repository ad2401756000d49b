"""The state-space + latent-expert cell end to end on the CPU at a tiny
size, through the harness's own functions (``run_cell``, the family's
``control``), an altered token failing the check, the
parent-fails-fast contract of the family, the configuration file's
contract, the four metric files on a hand-made trace and the
``BENCHMARK.json`` lists. CPU numbers prove the control flow and the
contract's shape, never a speed.
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

CELL = "serve-nemotron3-reasongen-batch"
NAME = "nemotron-3-super-120b-a12b"
NEW = ("ssm_time_share.served", "ssm_scan_time_share.served",
       "latent_moe_time_share.served", "ssm_state_bytes_per_row.served")
TINY = {
    "family": "ssm_moe_serving", "reference": "nemotron_h",
    "hidden_size": 64, "expand": 2, "mamba_num_heads": 8,
    "mamba_head_dim": 16, "n_groups": 2, "ssm_state_size": 16,
    "conv_kernel": 4, "use_conv_bias": True, "chunk_size": 16,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "hybrid_override_pattern": "MEM*E", "num_hidden_layers": 5,
    "layers_run": [0, 1, 2, 3, 4], "n_routed_experts": 4,
    "router_experts": 8, "expert_offset": 4, "num_experts_per_tok": 3,
    "routed_scaling_factor": 5, "norm_topk_prob": True,
    "moe_intermediate_size": 48, "moe_latent_size": 32,
    "moe_shared_expert_intermediate_size": 96, "n_shared_experts": 1,
    "mlp_hidden_act": "relu2", "norm_eps": 1e-5, "vocab_size": 256,
    "time_step_min": 0.001, "time_step_max": 0.1,
    "time_step_floor": 1e-4, "torch_dtype": "bfloat16",
    "initializer_range": 0.3,      # wide logits at a tiny width
    "ssm_state_dtype": "float32",
    "serving": {"page_size": 16, "max_length": 160, "pool_pages": None,
                "decode_chunk": 1, "prefill_chunk": None,
                "prefix_cache": False},
    "limits": {"served_logit_gap": 1.0, "served_logit_mean_gap": 0.2}}
TRAFFIC = {"kind": "closed_loop", "max_batch": 2, "clients": 4,
           "ramp_s": 0.5, "cycle": 4, "trace_s": 1.0,
           "prompt": {"dist": "uniform", "min": 20, "max": 100},
           "output": {"dist": "uniform", "min": 6, "max": 12},
           "check_requests": 2}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A benchmark of the one tiny cell: the real BENCHMARK.json's
    entries for it, the real metric files, tiny configuration and
    traffic."""
    root = tmp_path_factory.mktemp("bench_nemotron")
    data = root / "b"
    for d in ("configs", "traffic", "metrics"):
        (data / d).mkdir(parents=True)
    (data / "configs" / "nemotron-tiny.json").write_text(json.dumps(TINY))
    (data / "traffic" / "reasongen-tiny.json").write_text(
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
             "configs": [{"name": "nemotron-tiny", "source": "test",
                          "file": "b/configs/nemotron-tiny.json",
                          "reduced": [], "why": "tiny"}],
             "workloads": [{"name": CELL, "config": "nemotron-tiny",
                            "traffic": "reasongen-tiny", "chips": 1,
                            "why": "tiny"}],
             "end_to_end": mine(real["end_to_end"]),
             "per_layer": per_layer}
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_on_cpu(root, trace, capsys):
    from benchmarks.harness.runner import run_cell

    lines = []
    out = run_cell(root, CELL, seed=2 ** 31 + 4848 + trace, seconds=2.0,
                   trace=bool(trace), require_tpu=False, say=lines.append)
    text = "\n".join(lines)
    line = json.loads(json.dumps(out))
    assert line["correct"] is True, text
    assert line["attempted"] > 0 and line["failed"] == 0, text
    assert any("routed pairs the expert layers dropped: 0 " in l
               for l in lines), text
    assert any("recurrent state is not kept in float32 (kept: float32): 0 "
               in l for l in lines), text
    assert any("mean gap of a served token's logit" in l for l in lines)
    if trace:
        got = set(line["metrics"])
        # host readings on any backend; the device-trace shares have
        # nothing to read on the CPU and are left out
        assert {"moe_pairs_per_expert.served",
                "moe_load_max_over_mean.served", "batch_occupancy.served",
                "prefill_padding_share.served",
                "ssm_state_bytes_per_row.served"} <= got, text
        assert not got & set(NEW[:3])
        # 2 state layers x (8 x 16 x 16 x 4 B + 3 x (128 + 64) x 2 B)
        v = line["metrics"]["ssm_state_bytes_per_row.served"]["value"]
        assert v == 2 * (8 * 16 * 16 * 4 + 3 * 192 * 2)
        # 2 rows x 3 chosen of 8, 4 held: 0.75 a held expert a step
        v = line["metrics"]["moe_pairs_per_expert.served"]["value"]
        assert 0.0 < v < 1.5
        # prompts 20-100 on the 64 / 128 buckets
        p = line["metrics"]["prefill_padding_share.served"]["value"]
        assert 5.0 < p < 70.0
        host = json.loads([l for l in capsys.readouterr().out.splitlines()
                           if l.startswith("host: ")][0][6:])
        # ONE paged layer: 2 KV heads x (128-lane keys + 16) x 2 B
        assert host["kv_bytes_per_context_token"] >= 2 * (128 + 16) * 2
    else:
        assert set(line["metrics"]) == {"served_tokens_per_s", "setup_s"}


def test_altered_token_is_not_correct(root, monkeypatch):
    from benchmarks.harness.families import ssm_moe_serving
    from benchmarks.harness.runner import run_cell

    real_pop = ssm_moe_serving.System.pop_finished

    def altered(self):
        out = real_pop(self)
        for _, _, _, tokens in out:
            tokens[len(tokens) // 2] = (tokens[len(tokens) // 2] + 7) % 256
        return out

    monkeypatch.setattr(ssm_moe_serving.System, "pop_finished", altered)
    lines = []
    out = run_cell(root, CELL, seed=79, seconds=1.5, trace=False,
                   require_tpu=False, say=lines.append)
    assert out["correct"] is False, "\n".join(lines)
    assert any("widest gap" in l and "FAIL" in l for l in lines)


def test_control_runs_and_both_controls_read(root):
    """``family.control`` as ``tools/control.py`` drives it: the
    program's gaps pass the tiny cell's limits, the fp8 reference's
    best token lies further below the float32 reference's best, and the
    bfloat16-state reading is listed beside it."""
    from benchmarks.harness import runner

    c = runner.Cell(root, CELL)
    seed = 2 ** 31 + 48
    plan = c.kind.plan(c.traffic, seed, 1.5, c.cfg["vocab_size"])
    import jax

    system = c.family.build(c.cfg, c.traffic, plan, seed,
                            jax.devices()[:1])
    system.warm()
    phases = runner.Phases(False, 0.0, "", runner.CompileCounter(),
                           lambda: 0)
    result = c.kind.run(system, plan, 1.5, phases)
    out = c.family.control(system, result)
    for got in out["program"]:
        assert got["value"] <= got["limit"]
    assert out["control"][1]["value"] > out["program"][1]["value"]
    assert len(out["state_bf16"]) == 2
    assert out["state_bf16"][1]["value"] >= 0.0
    assert 0.0 <= out["program_choice_flips"] <= 1.0


def test_reference_imports_nothing_of_the_program():
    import subprocess

    code = ("import sys; import benchmarks.references.nemotron_h; "
            "assert not any(m.startswith('paddle_tpu') "
            "for m in sys.modules)")
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr


def test_parent_fails_at_import(monkeypatch):
    """A tree without ``models/ssm_moe.py`` fails where the family is
    imported, with an ImportError, before anything is allocated."""
    import importlib

    import benchmarks.harness.families.ssm_moe_serving as fam

    monkeypatch.setitem(sys.modules, "paddle_tpu.models.ssm_moe", None)
    try:
        with pytest.raises(ImportError):
            importlib.reload(fam)
    finally:
        monkeypatch.undo()
        importlib.reload(fam)


def test_reference_state_bytes_and_leaves():
    from benchmarks.references import nemotron_h as ref

    cfg = json.load(open(os.path.join(
        REPO, "benchmarks", "configs", NAME + ".json")))
    assert ref.state_bytes_per_row(cfg) == 21278720
    assert list(ref.held_experts(cfg)) == list(range(128))
    table = ref.leaf_table(cfg)
    n = sum(int(np.prod(s[0])) for s in table.values())
    # the issue's arithmetic: 4.648B parameters (norms and vectors in)
    assert abs(n - 4.648e9) < 0.002e9, n
    assert table["l.0.in_proj"][0] == (4096, 18560)
    assert table["l.1.e.5.up"][0] == (1024, 2688)
    assert "l.1.e.128.up" not in table
    assert table["l.7.k"][0] == (4096, 256)


def test_config_file_keeps_every_published_number():
    cfg = json.load(open(os.path.join(
        REPO, "benchmarks", "configs", NAME + ".json")))
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = [c for c in bench["configs"] if c["name"] == NAME][0]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] == cfg["source"]
    for k in cfg["reduced"]:
        assert cfg[k] < cfg["published"][k]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = [json.loads(l) for l in open(catalog)
               if json.loads(l)["source_url"] == cfg["source"]][0]
        assert row["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"
        for k, v in row["config"].items():
            assert k in cfg, k
            if k in cfg["reduced"]:
                assert cfg["published"][k] == v, k
            elif k == "hybrid_override_pattern":
                assert cfg["published"][k] == v
                assert cfg[k] == v[:cfg["num_hidden_layers"]]
            else:
                assert cfg[k] == v, k
    # one whole period in the published ratio, beginning as the model
    pat = cfg["hybrid_override_pattern"]
    assert pat == "MEMEMEM*EME"
    assert (pat.count("M"), pat.count("E"), pat.count("*")) == (5, 5, 1)
    full = cfg["published"]["hybrid_override_pattern"]
    assert (full.count("M"), full.count("E"), full.count("*"),
            len(full)) == (40, 40, 8, 88)
    assert cfg["layers_run"] == list(range(11))
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["router_experts"], cfg["expert_offset"],
            cfg["vocab_size"]) == (11, 128, 512, 0, 32768)
    assert cfg["published"]["chips_sharing_a_layer"] == 4
    assert cfg["published"]["n_routed_experts"] \
        == 4 * cfg["n_routed_experts"]
    assert cfg["vocab_size"] * 4 == cfg["published"]["vocab_size"]
    assert (cfg["hidden_size"], cfg["mamba_num_heads"],
            cfg["mamba_head_dim"], cfg["n_groups"], cfg["ssm_state_size"],
            cfg["conv_kernel"], cfg["chunk_size"], cfg["moe_latent_size"],
            cfg["moe_intermediate_size"],
            cfg["moe_shared_expert_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["routed_scaling_factor"],
            cfg["mlp_hidden_act"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["ssm_state_dtype"]) == (
        4096, 128, 64, 8, 128, 4, 128, 1024, 2688, 5376, 22, 5, "relu2",
        2, 128, "float32")
    for key in ("deployment", "assumed", "limits", "serving", "published"):
        assert key in cfg
    for key in ("num_hidden_layers", "n_routed_experts", "vocab_size",
                "mixer_order", "gated_group_norm", "router",
                "latent_experts", "attention", "ssm_state_dtype",
                "ssm_vectors", "weights", "mtp", "max_length", "pool",
                "limits"):
        assert key in cfg["assumed"], key
    assert set(cfg["limits"]) == {"served_logit_gap",
                                  "served_logit_mean_gap"}
    cell = [w for w in bench["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "reasongen-batch", 1)
    traffic = json.load(open(os.path.join(
        REPO, "benchmarks", "traffic", "reasongen-batch.json")))
    assert (traffic["kind"], traffic["max_batch"], traffic["clients"],
            traffic["cycle"], traffic["check_requests"], traffic["ramp_s"],
            traffic["trace_s"]) == ("closed_loop", 128, 192, 64, 4, 30.0,
                                    5.0)
    assert (traffic["prompt"]["min"], traffic["prompt"]["max"]) == (
        128, 1024)                                  # four buckets
    assert traffic["prompt"]["max"] + traffic["output"]["max"] \
        <= cfg["serving"]["max_length"]
    srv = cfg["serving"]
    pages = -(-srv["max_length"] // srv["page_size"])
    assert traffic["max_batch"] * pages + 1 <= srv["pool_pages"]
    # the mean pairs a held expert a step the cell's why states
    assert traffic["max_batch"] * cfg["num_experts_per_tok"] \
        / cfg["router_experts"] == 5.5


def test_benchmark_lists():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    lists = {m["name"]: m.get("workloads", []) for m in bench["per_layer"]}
    for name in NEW:
        assert lists[name] == [CELL]
        m = [m for m in bench["per_layer"] if m["name"] == name][0]
        assert m["moves"] == "served_tokens_per_s"
    assert [m["name"] for m in bench["per_layer"][-4:]] == list(NEW)
    for name in ("batch_occupancy.served", "prefill_device_share.served",
                 "pallas_time_share.served", "device_idle_share.served",
                 "peak_hbm_gib.served", "moe_pairs_per_expert.served",
                 "moe_load_max_over_mean.served",
                 "prefill_padding_share.served",
                 "idle_in_prefill_share.served",
                 "idle_in_decode_round_share.served",
                 "idle_in_bookkeeping_share.served",
                 "prefill_dispatch_ms_p50.served",
                 "decode_fetch_wait_share.served"):
        assert lists[name][-1] == CELL, name
    for name in ("global_decode_attention_roofline.served",
                 "window_decode_attention_roofline.served",
                 "mla_decode_attention_roofline.served",
                 "kv_bytes_per_context_token.served",
                 "sparse_selected_share.served",
                 "hybrid_attention_time_share.served"):
        assert CELL not in lists[name]
    e2e = {m["name"]: m.get("workloads") for m in bench["end_to_end"]}
    assert e2e["served_tokens_per_s"][-1] == CELL
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == NAME
    assert len(bench["workloads"][-1]["why"]) <= 200


def test_metric_files_on_a_hand_made_trace():
    """The three scope shares through ``named_scope_share``'s own
    functions over ops placed by hand, and the host value."""
    from benchmarks.harness import reduce as R
    from benchmarks.harness.reducers import host_value
    from benchmarks.harness.reducers import inner_scope_share as iss
    from benchmarks.harness.reducers import named_scope_share as nss

    specs = {n: json.load(open(os.path.join(
        REPO, "benchmarks", "metrics", n + ".json"))) for n in NEW}
    for n in NEW[:3]:
        assert specs[n]["reducer"] == "named_scope_share"
    known = tuple(specs[NEW[0]]["args"]["known"])
    assert known == tuple(specs[NEW[1]]["args"]["known"]) \
        == tuple(specs[NEW[2]]["args"]["known"])
    base = "jit(step)/ssm_moe/"
    paths = {
        "in": base + "layer0.ssm/dot_general:",
        "scan": base + "layer0.ssm/layer0.ssm.scan/while/body/mul:",
        "attn": base + "layer7.attn.full/flash_attention/pallas_call:",
        "router": base + "layer1.moe/dot_general:",
        "latent": base + "layer1.moe/layer1.moe.latent/dot_general:",
        "head": base + "head/dot_general:",
        "plain": base + "add:"}
    inner = {k: iss.innermost(p, known) for k, p in paths.items()}
    assert inner == {"in": "layer0.ssm", "scan": "layer0.ssm.scan",
                     "attn": "layer7.attn.full", "router": "layer1.moe",
                     "latent": "layer1.moe.latent", "head": "head",
                     "plain": "ssm_moe"}
    # 1 ms each, back to back on one device; the grouped matmul of a
    # prefill reaches the trace with no scope at all
    order = ["in", "scan", "attn", "router", "latent", "head", "plain"]
    ops = [R.Op("fusion", i * 1e-3, 1e-3, 0, inner[k], "")
           for i, k in enumerate(order)]
    ops.append(R.Op("ragged-dot-none", 7e-3, 1e-3, 0, "", ""))
    tr = R.Trace(ops, [], (0.0, 8e-3))
    got = {n: nss.share(tr, specs[n]["args"]["endings"],
                        specs[n]["args"].get("names", ()))
           for n in NEW[:3]}
    assert got[NEW[0]] == pytest.approx(100.0 * 2 / 8)     # in + scan
    assert got[NEW[1]] == pytest.approx(100.0 * 1 / 8)     # scan
    assert got[NEW[2]] == pytest.approx(100.0 * 2 / 8)     # latent + ragged
    assert specs[NEW[3]] == {"reducer": "host_value",
                             "args": {"key": "ssm_state_bytes_per_row"}}
    assert host_value.read({"host": {"ssm_state_bytes_per_row": 21278720}},
                           **specs[NEW[3]]["args"]) == 21278720
    assert host_value.read({"host": {}}, **specs[NEW[3]]["args"]) is None
