"""The sparse-context cell end to end on the CPU at a tiny size, through
the harness's own functions (``run_cell``): the family builds, the check
passes, an altered token and a planted wrong kept count fail it, both
controls (fp8 operands; the selection skipped) fail a judged number, the
parent-fails-fast contract of the family, the configuration file's
published numbers, the cost function and the scope reducer against
hand-worked cases. CPU numbers prove the control flow and the contract's
shape, never a speed.
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

CELL = "serve-keye-sparsectx-batch"
KEYE = {
    "family": "sparse_moe_serving", "reference": "keye",
    "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "rope_theta": 10000, "num_experts": 4, "router_experts": 16,
    "expert_offset": 4, "num_experts_per_tok": 4, "norm_topk_prob": True,
    "mlp_only_layers": [], "decoder_sparse_step": 1,
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 16,
                  "q_chunk_size": 16, "topk": 16},
    "vocab_size": 256, "rms_norm_eps": 1e-6, "torch_dtype": "bfloat16",
    "initializer_range": 0.1,
    "serving": {"page_size": 16, "max_length": 192, "pool_pages": None,
                "decode_chunk": 1, "prefill_chunk": None,
                "prefix_cache": False},
    # the tiny program reads 0.13-0.37 | 0.007-0.017 | 0.035-0.066 (gap,
    # mean gap, 1 - agreement; three seeds), the fp8 control 0.059 on the
    # mean and 0.18 on the agreement
    "limits": {"served_logit_gap": 1.0, "served_logit_mean_gap": 0.04,
               "kept_keys_wrong": 0, "selection_agreement": 0.88}}
TRAFFIC = {"kind": "closed_loop", "max_batch": 2, "clients": 4,
           "ramp_s": 0.5, "cycle": 4, "trace_s": 1.0,
           "prompt": {"dist": "lognormal", "median": 60, "sigma": 0.5,
                      "min": 32, "max": 128},
           "output": {"dist": "uniform", "min": 6, "max": 12},
           "check_requests": 2}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A benchmark of the one tiny cell: the real BENCHMARK.json's
    entries for it, the real metric files, tiny configuration and
    traffic."""
    root = tmp_path_factory.mktemp("bench_keye")
    data = root / "b"
    for d in ("configs", "traffic", "metrics"):
        (data / d).mkdir(parents=True)
    (data / "configs" / "keye-tiny.json").write_text(json.dumps(KEYE))
    (data / "traffic" / "sparsectx-tiny.json").write_text(
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
             "configs": [{"name": "keye-tiny", "source": "test",
                          "file": "b/configs/keye-tiny.json",
                          "reduced": [], "why": "tiny"}],
             "workloads": [{"name": CELL, "config": "keye-tiny",
                            "traffic": "sparsectx-tiny", "chips": 1,
                            "why": "tiny"}],
             "end_to_end": mine(real["end_to_end"]),
             "per_layer": per_layer}
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_on_cpu(root, trace):
    from benchmarks.harness.runner import run_cell

    lines = []
    out = run_cell(root, CELL, seed=2 ** 31 + 3939 + trace, seconds=2.0,
                   trace=bool(trace), require_tpu=False, say=lines.append)
    text = "\n".join(lines)
    line = json.loads(json.dumps(out))
    assert line["correct"] is True, text
    assert line["attempted"] > 0 and line["failed"] == 0, text
    assert any("routed pairs the expert layers dropped: 0 " in l
               for l in lines), text
    # the decode steps' own count on the device, and the probe's
    for name in ("kept_keys_wrong, the decode steps' own count",
                 "probe's kept_keys_wrong"):
        assert any(l.startswith("check: " + name) and ": 0 " in l
                   and " ok" in l for l in lines), text
    assert any("selection_agreement" in l and " ok" in l
               for l in lines), text
    if trace:
        got = set(line["metrics"])
        # host counters read on any backend; the device-trace shares
        # have nothing to read on the CPU and are left out
        assert {"moe_pairs_per_expert.served",
                "moe_load_max_over_mean.served", "batch_occupancy.served",
                "kv_bytes_per_context_token.served",
                "sparse_selected_share.served",
                "prefill_padding_share.served"} <= got, text
        assert "sparse_decode_attention_roofline.served" not in got
        # contexts of 32..140 keep 16 keys: between 16/140 and 16/32
        kept = line["metrics"]["sparse_selected_share.served"]["value"]
        assert 100 * 16 / 140 <= kept <= 50.0
        # 2 KV heads of K (16 columns pooled 128 wide) and V, and the
        # 128-lane index key, of 3 layers, 2 B each
        kv = line["metrics"]["kv_bytes_per_context_token.served"]["value"]
        assert kv >= 3 * (2 * 128 + 2 * 16 + 128) * 2
    else:
        assert set(line["metrics"]) == {"served_tokens_per_s", "setup_s"}


def test_altered_token_is_not_correct(root, monkeypatch):
    from benchmarks.harness.families import sparse_moe_serving
    from benchmarks.harness.runner import run_cell

    real_pop = sparse_moe_serving.System.pop_finished

    def altered(self):
        out = real_pop(self)
        for _, _, _, tokens in out:
            tokens[len(tokens) // 2] = (tokens[len(tokens) // 2] + 7) % 256
        return out

    monkeypatch.setattr(sparse_moe_serving.System, "pop_finished", altered)
    lines = []
    out = run_cell(root, CELL, seed=79, seconds=1.5, trace=False,
                   require_tpu=False, say=lines.append)
    assert out["correct"] is False, "\n".join(lines)
    assert any("widest gap" in l and "FAIL" in l for l in lines)


@pytest.mark.parametrize("where", ["probe", "decode"])
def test_planted_wrong_kept_count_is_not_correct(root, monkeypatch, where):
    """A selection that keeps one key too few somewhere fails
    ``kept_keys_wrong`` (limit 0): in the probe's kept sets, and in what
    the decode steps counted on the device."""
    from benchmarks.harness.families import sparse_moe_serving
    from benchmarks.harness.runner import run_cell
    from paddle_tpu.inference import ServingEngine

    if where == "probe":
        real = sparse_moe_serving.System.program_probe

        def one_short(self, picks):
            choices, kept = real(self, picks)
            kept[0][0] = kept[0][0].copy()
            row = kept[0][0][0]
            row[np.flatnonzero(row)[0]] = False
            return choices, kept

        monkeypatch.setattr(sparse_moe_serving.System, "program_probe",
                            one_short)
        name = "check: probe's kept_keys_wrong"
    else:
        real = ServingEngine.selection_stats

        def one_more(self):
            st = real(self)
            return dict(st, kept_keys_wrong=st["kept_keys_wrong"] + 1)

        monkeypatch.setattr(ServingEngine, "selection_stats", one_more)
        name = "check: kept_keys_wrong, the decode steps' own count"
    lines = []
    out = run_cell(root, CELL, seed=80, seconds=1.5, trace=False,
                   require_tpu=False, say=lines.append)
    assert out["correct"] is False, "\n".join(lines)
    assert any(l.startswith(name) and ": 1 " in l and "FAIL" in l
               for l in lines), "\n".join(lines)
    assert sum("FAIL" in l for l in lines) == 1, "\n".join(lines)


def test_both_controls_fail_a_judged_number():
    """The reference in fp8 in the program's place, and the reference
    with the selection skipped: each fails a number that the reference
    against itself passes (tiny size, the arithmetic ``tools/control.py``
    reads on the chip through ``family.control``)."""
    from benchmarks.harness.families import sparse_moe_serving as fam
    from benchmarks.references import keye as ref

    class Pick:
        def __init__(self, prompt, tokens):
            self.prompt, self.tokens = prompt, tokens

    cfg = dict(KEYE)
    rng = np.random.default_rng(5)
    picks = [Pick(rng.integers(0, 256, 70), rng.integers(0, 256, 24))
             for _ in range(3)]
    reqs = [(q.prompt, q.tokens) for q in picks]
    r = ref.ServeReference(cfg, 11)
    want = r.logits(reqs)
    topk, limits = cfg["sa_config"]["topk"], cfg["limits"]

    def numbers(precision):
        lo = ref.ServeReference(cfg, 11, precision)
        low = lo.logits(reqs)
        gaps = [ref.served_gap(w, l.argmax(-1)) for w, l in zip(want, low)]
        return fam._gap_numbers(gaps, picks, limits) \
            + fam.selection_numbers(lo.kept, r.kept, picks, topk, limits)

    same = numbers("float32")
    assert all(n["value"] <= n["limit"] for n in same), same
    assert same[0]["value"] == 0.0 and same[3]["value"] == 0.0
    fp8 = numbers("fp8")
    assert fp8[0]["value"] > 0.05               # the widest gap moves
    assert any(n["value"] > n["limit"] for n in fp8), fp8
    dense = numbers("dense")
    # every earlier key kept: the count is wrong wherever t + 1 > topk
    assert dense[2]["value"] == 3 * 3 * 24 and dense[2]["limit"] == 0
    assert dense[3]["value"] > dense[3]["limit"]
    assert dense[0]["value"] > 0.0              # and the logits move


def test_reference_imports_nothing_of_the_program():
    import subprocess

    code = ("import sys; import benchmarks.references.keye; "
            "assert not any(m.startswith('paddle_tpu') "
            "for m in sys.modules)")
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr


def test_parent_fails_at_import(monkeypatch):
    """A tree whose ``HybridMoEConfig`` lacks the index's fields fails
    where the family is imported, before anything is allocated."""
    import dataclasses
    import importlib

    import benchmarks.harness.families.sparse_moe_serving as fam
    from paddle_tpu.models import hybrid_moe

    @dataclasses.dataclass
    class Parent:                   # fields the parent commit had
        vocab_size: int = 32000
        qk_norm: bool = False
        head_on_last_row: bool = False

    monkeypatch.setattr(hybrid_moe, "HybridMoEConfig", Parent)
    try:
        with pytest.raises(ImportError, match="lacks index_head_dim"):
            importlib.reload(fam)
    finally:
        monkeypatch.undo()
        importlib.reload(fam)


def test_config_file_keeps_every_published_number():
    cfg = json.load(open(os.path.join(
        REPO, "benchmarks", "configs", "keye-vl-2.0-30b-a3b.json")))
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = [c for c in bench["configs"]
             if c["name"] == "keye-vl-2.0-30b-a3b"][0]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_experts"]
    assert entry["source"] == cfg["source"]
    for k, v in cfg["published"].items():
        if k in cfg["reduced"]:
            assert cfg[k] < v
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["router_experts"],
            cfg["num_experts_per_tok"], cfg["moe_intermediate_size"],
            cfg["intermediate_size"], cfg["vocab_size"], cfg["rope_theta"],
            cfg["max_position_embeddings"], cfg["num_local_experts"],
            cfg["rms_norm_eps"], cfg["norm_topk_prob"]) == (
        2048, 128, 32, 4, 128, 8, 768, 6144, 151936, 10000000, 262144,
        128, 1e-6, True)
    assert cfg["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048}
    assert cfg["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["expert_offset"]) == (6, 16, 0)
    for key in ("deployment", "assumed", "limits", "serving"):
        assert key in cfg
    for key in ("qk_norm", "rotary", "indexer", "router", "weights",
                "pool", "num_experts", "num_hidden_layers"):
        assert key in cfg["assumed"]
    assert set(cfg["limits"]) == {
        "served_logit_gap", "served_logit_mean_gap", "kept_keys_wrong",
        "selection_agreement"}
    assert cfg["limits"]["kept_keys_wrong"] == 0
    cell = [w for w in bench["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "keye-vl-2.0-30b-a3b", "sparsectx-batch", 1)
    traffic = json.load(open(os.path.join(
        REPO, "benchmarks", "traffic", "sparsectx-batch.json")))
    assert (traffic["clients"], traffic["max_batch"], traffic["cycle"],
            traffic["check_requests"]) == (96, 64, 64, 4)
    assert traffic["prompt"]["min"] == cfg["sa_config"]["topk"]
    assert traffic["prompt"]["max"] + traffic["output"]["max"] \
        == cfg["serving"]["max_length"]


def test_sparse_decode_cost_by_hand():
    from benchmarks.harness.sparse_cost import sparse_decode

    # one row at context 5000 keeps 2048 keys: 32 heads x 2048 x 256
    # multiply-adds; 2048 keys x 4 heads x 256 x 2 B, q in and o out
    f, b = sparse_decode([(1, 5000)], 32, 4, 128, 128, 2048)
    assert f == 2 * 32 * 2048 * 256
    assert b == 2048 * 4 * 256 * 2 + 32 * 256 * 2
    # under the top-k a row keeps its whole context
    f, b = sparse_decode([(1, 100)], 32, 4, 128, 128, 2048)
    assert f == 2 * 32 * 100 * 256
    assert b == 100 * 4 * 256 * 2 + 32 * 256 * 2
    # rows add up; the context beyond the top-k costs nothing more
    two = sparse_decode([(1, 5000), (1, 100)], 32, 4, 128, 128, 2048)
    assert two == (2 * 32 * 2148 * 256,
                   2148 * 4 * 256 * 2 + 2 * 32 * 256 * 2)
    assert sparse_decode([(1, 16768)], 32, 4, 128, 128, 2048) \
        == sparse_decode([(1, 2048)], 32, 4, 128, 128, 2048)


def test_innermost_scope_by_hand():
    from benchmarks.harness import reduce as R
    from benchmarks.harness.reducers import inner_scope_share as iss

    base = "jit(step)/hybrid_moe/layer3.attn.sparse"
    assert iss.innermost(base + "/dot_general:") == "layer3.attn.sparse"
    assert iss.innermost(
        base + "/layer3.attn.sparse.select/while/body/reduce_sum:") \
        == "layer3.attn.sparse.select"
    assert iss.innermost(
        base + "/layer3.attn.sparse.attend/paged_sparse_decode_attention"
        "/pallas_call:") == "layer3.attn.sparse.attend"
    assert iss.innermost("jit(step)/hybrid_moe/layer3.moe/dot:") == ""
    assert iss.innermost(None) == ""
    # window 0..10 s, one device busy 8 s: 1 s of index, 2 s of select,
    # 1 s of attend, 0.5 s of the layer's own projections, 3.5 s else
    ops = [R.Op("a", 0.0, 1.0, 0, "layer0.attn.sparse.index"),
           R.Op("b", 1.0, 2.0, 0, "layer1.attn.sparse.select"),
           R.Op("c", 3.0, 1.0, 0, "layer0.attn.sparse.attend"),
           R.Op("d", 4.0, 0.5, 0, "layer5.attn.sparse"),
           R.Op("e", 5.0, 3.5, 0, "")]
    tr = R.Trace(ops, [], (0.0, 10.0))
    assert iss.share(tr, ["attn.sparse.index", "attn.sparse.select"]) \
        == pytest.approx(100 * 3.0 / 8.0)
    assert iss.share(tr, iss.KNOWN) == pytest.approx(100 * 4.5 / 8.0)
    assert iss.share(tr, ["attn.sparse"]) == pytest.approx(100 * 0.5 / 8.0)
    assert iss.share(R.Trace([], [], (0.0, 1.0)), iss.KNOWN) is None


def test_scope_reducer_on_a_recorded_trace():
    """A sample of the cell's own traced run on a v5e (one decode step
    and 400 ops of a 16,384 prefill around a selecting tier, each op
    with the XLA ``op_name`` the chip recorded:
    a one-off script of PR 39 wrote it): the reducer's
    shares equal those summed over the op names by a regular
    expression when the sample was taken."""
    from benchmarks.harness.reducers import inner_scope_share as iss

    path = os.path.join(REPO, "benchmarks", "tests", "data",
                        "sparse_scopes_v5e.json")
    rec = json.load(open(path))
    ops = [iss.R.Op(o["name"], o["start"], o["dur"], 0,
                    iss.innermost(o["tf_op"]), "") for o in rec["ops"]]
    tr = iss.R.Trace(ops, [], tuple(rec["window"]))
    for endings, want in rec["shares"].items():
        assert iss.share(tr, endings.split(",")) == pytest.approx(want)
