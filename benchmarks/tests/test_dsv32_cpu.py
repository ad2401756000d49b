"""The long-document cell end to end on the CPU at a tiny size, through
the harness's own functions (``run_cell``): the family builds, the check
passes, an altered token and a planted wrong kept count fail it, both
controls (fp8 operands; the selection skipped) fail a judged number, the
parent-fails-fast contract of the family, the configuration file's
published numbers against the catalog's, the cost function and its
reducer against hand-worked cases. CPU numbers prove the control flow
and the contract's shape, never a speed.
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

CELL = "serve-dsv32-longdoc-batch"
NAME = "deepseek-v3.2-exp"
TINY = {
    "family": "sparse_mla_moe_serving", "reference": "deepseek_v32",
    "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 8,
    "q_lora_rank": 48, "kv_lora_rank": 128, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "index_n_heads": 2,
    "index_head_dim": 16, "index_topk": 16, "n_routed_experts": 4,
    "router_experts": 16, "expert_offset": 0, "num_experts_per_tok": 4,
    "n_group": 4, "topk_group": 2, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "norm_topk_prob": True, "topk_method": "noaux_tc",
    "moe_layer_freq": 1, "attention_bias": False, "rope_theta": 10000,
    "rope_scaling": {"type": "yarn", "factor": 4,
                     "original_max_position_embeddings": 32,
                     "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                     "mscale_all_dim": 1},
    "vocab_size": 256, "rms_norm_eps": 1e-6, "torch_dtype": "bfloat16",
    "initializer_range": 0.1,
    "serving": {"page_size": 16, "max_length": 192, "pool_pages": None,
                "decode_chunk": 1, "prefill_chunk": None,
                "prefix_cache": False},
    # limits of the TINY program (bf16 on the CPU), wide of its readings
    # and inside the controls': see test_both_controls_fail...
    "limits": {"served_logit_gap": 1.0, "served_logit_mean_gap": 0.04,
               "kept_keys_wrong": 0, "selection_agreement": 0.85}}
TRAFFIC = {"kind": "closed_loop", "max_batch": 2, "clients": 4,
           "ramp_s": 0.5, "cycle": 4, "trace_s": 1.0,
           "prompt": {"dist": "uniform", "min": 48, "max": 128},
           "output": {"dist": "uniform", "min": 6, "max": 12},
           "check_requests": 2}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A benchmark of the one tiny cell: the real BENCHMARK.json's
    entries for it, the real metric files, tiny configuration and
    traffic."""
    root = tmp_path_factory.mktemp("bench_dsv32")
    data = root / "b"
    for d in ("configs", "traffic", "metrics"):
        (data / d).mkdir(parents=True)
    (data / "configs" / "dsv32-tiny.json").write_text(json.dumps(TINY))
    (data / "traffic" / "longdoc-tiny.json").write_text(
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
             "configs": [{"name": "dsv32-tiny", "source": "test",
                          "file": "b/configs/dsv32-tiny.json",
                          "reduced": [], "why": "tiny"}],
             "workloads": [{"name": CELL, "config": "dsv32-tiny",
                            "traffic": "longdoc-tiny", "chips": 1,
                            "why": "tiny"}],
             "end_to_end": mine(real["end_to_end"]),
             "per_layer": per_layer}
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_on_cpu(root, trace):
    from benchmarks.harness.runner import run_cell

    lines = []
    out = run_cell(root, CELL, seed=2 ** 31 + 4141 + trace, seconds=2.0,
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
    # listed, never judged: experts and groups
    for what in ("expert", "group"):
        assert any(f"{what} choices" in l and "(limit 1)" in l
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
        assert "sparse_mla_decode_attention_roofline.served" not in got
        assert "kept_flash_attention_roofline.served" not in got
        assert "sparse_decode_attention_roofline.served" not in got
        assert "mla_decode_attention_roofline.served" not in got
        # contexts of 48..140 keep 16 rows: between 16/140 and 16/48
        kept = line["metrics"]["sparse_selected_share.served"]["value"]
        assert 100 * 16 / 140 <= kept <= 100 * 16 / 48
        # the latent, the 128-lane rotated key and the 128-lane index
        # key of 3 layers, 2 B each
        kv = line["metrics"]["kv_bytes_per_context_token.served"]["value"]
        assert kv >= 3 * (128 + 128 + 128) * 2
    else:
        assert set(line["metrics"]) == {"served_tokens_per_s", "setup_s"}


def test_altered_token_is_not_correct(root, monkeypatch):
    from benchmarks.harness.families import sparse_mla_moe_serving as fam
    from benchmarks.harness.runner import run_cell

    real_pop = fam.System.pop_finished

    def altered(self):
        out = real_pop(self)
        for _, _, _, tokens in out:
            tokens[len(tokens) // 2] = (tokens[len(tokens) // 2] + 7) % 256
        return out

    monkeypatch.setattr(fam.System, "pop_finished", altered)
    lines = []
    out = run_cell(root, CELL, seed=79, seconds=1.5, trace=False,
                   require_tpu=False, say=lines.append)
    assert out["correct"] is False, "\n".join(lines)
    assert any("widest gap" in l and "FAIL" in l for l in lines)


@pytest.mark.parametrize("where", ["probe", "decode"])
def test_planted_wrong_kept_count_is_not_correct(root, monkeypatch, where):
    """A selection that keeps one row too few somewhere fails
    ``kept_keys_wrong`` (limit 0): in the probe's kept sets, and in what
    the decode steps counted on the device."""
    from benchmarks.harness.families import sparse_mla_moe_serving as fam
    from benchmarks.harness.runner import run_cell
    from paddle_tpu.inference import ServingEngine

    if where == "probe":
        real = fam.System.program_probe

        def one_short(self, picks):
            choices, kept = real(self, picks)
            kept[0][0] = kept[0][0].copy()
            row = kept[0][0][0]
            row[np.flatnonzero(row)[0]] = False
            return choices, kept

        monkeypatch.setattr(fam.System, "program_probe", one_short)
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
    from benchmarks.harness.families import sparse_moe_serving as sparse
    from benchmarks.references import deepseek_v32 as ref

    class Pick:
        def __init__(self, prompt, tokens):
            self.prompt, self.tokens = prompt, tokens

    cfg = dict(TINY)
    rng = np.random.default_rng(5)
    picks = [Pick(rng.integers(0, 256, 70), rng.integers(0, 256, 24))
             for _ in range(3)]
    reqs = [(q.prompt, q.tokens) for q in picks]
    r = ref.ServeReference(cfg, 11)
    want = r.logits(reqs)
    topk, limits = cfg["index_topk"], cfg["limits"]

    def numbers(precision):
        lo = ref.ServeReference(cfg, 11, precision)
        low = lo.logits(reqs)
        gaps = [ref.served_gap(w, l.argmax(-1)) for w, l in zip(want, low)]
        return sparse._gap_numbers(gaps, picks, limits) \
            + sparse.selection_numbers(lo.kept, r.kept, picks, topk,
                                       limits)

    same = numbers("float32")
    assert all(n["value"] <= n["limit"] for n in same), same
    assert same[0]["value"] == 0.0 and same[3]["value"] == 0.0
    assert len(r.chosen()) == len(r.kept_groups()) == 2     # expert layers
    assert r.kept_groups()[0].shape == (3 * 93, 2)
    fp8 = numbers("fp8")
    assert fp8[0]["value"] > 0.05               # the widest gap moves
    assert any(n["value"] > n["limit"] for n in fp8), fp8
    dense = numbers("dense")
    # every earlier row kept: the count is wrong wherever t + 1 > topk
    assert dense[2]["value"] == 3 * 3 * 24 and dense[2]["limit"] == 0
    assert dense[3]["value"] > dense[3]["limit"]
    assert dense[0]["value"] > 0.0              # and the logits move


def test_reference_imports_nothing_of_the_program():
    import subprocess

    code = ("import sys; import benchmarks.references.deepseek_v32; "
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

    import benchmarks.harness.families.sparse_mla_moe_serving as fam
    from paddle_tpu.models import mla_moe

    @dataclasses.dataclass
    class Parent:                   # fields the parent commit had
        vocab_size: int = 32000
        kv_lora_rank: int = 512
        use_qk_norm: bool = True

    monkeypatch.setattr(mla_moe, "MLAMoEConfig", Parent)
    try:
        with pytest.raises(ImportError, match="lacks head_on_last_row, "
                                              "index_head_dim"):
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
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size"]
    assert entry["source"] == cfg["source"]
    for k in cfg["reduced"]:
        assert cfg[k] < cfg["published"][k]
    # every key of the catalog's config, unchanged unless reduced
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = [json.loads(l) for l in open(catalog)
               if json.loads(l)["source_url"] == cfg["source"]][0]
        for k, v in row["config"].items():
            assert k in cfg, k
            if k in cfg["reduced"]:
                assert cfg["published"][k] == v, k
            else:
                assert cfg[k] == v, k
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["q_lora_rank"], cfg["kv_lora_rank"],
            cfg["index_n_heads"], cfg["index_head_dim"],
            cfg["index_topk"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["n_group"], cfg["topk_group"],
            cfg["intermediate_size"], cfg["router_experts"],
            cfg["routed_scaling_factor"], cfg["n_shared_experts"],
            cfg["num_nextn_predict_layers"],
            cfg["max_position_embeddings"]) == (
        7168, 128, 128, 64, 128, 1536, 512, 64, 128, 2048, 2048, 8, 8, 4,
        18432, 256, 2.5, 1, 1, 163840)
    assert cfg["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["n_routed_experts"], cfg["expert_offset"],
            cfg["vocab_size"]) == (5, 1, 16, 0, 16160)
    assert cfg["published"]["chips_sharing_a_layer"] == 16
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    for key in ("deployment", "assumed", "limits", "serving"):
        assert key in cfg
    for key in ("num_hidden_layers", "first_k_dense_replace",
                "n_routed_experts", "vocab_size", "query", "indexer",
                "router", "rope", "num_nextn_predict_layers", "fp8",
                "weights", "max_length", "pool", "limits"):
        assert key in cfg["assumed"], key
    assert set(cfg["limits"]) == {
        "served_logit_gap", "served_logit_mean_gap", "kept_keys_wrong",
        "selection_agreement"}
    assert cfg["limits"]["kept_keys_wrong"] == 0
    cell = [w for w in bench["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "longdoc-batch", 1)
    traffic = json.load(open(os.path.join(
        REPO, "benchmarks", "traffic", "longdoc-batch.json")))
    assert (traffic["cycle"], traffic["check_requests"],
            traffic["ramp_s"], traffic["trace_s"]) == (64, 4, 30.0, 5.0)
    assert traffic["prompt"]["max"] + traffic["output"]["max"] \
        == cfg["serving"]["max_length"]
    # every prompt in ONE prefill bucket, every context past the top-k
    assert traffic["prompt"]["min"] > traffic["prompt"]["max"] // 2
    assert traffic["prompt"]["min"] > cfg["index_topk"]
    srv = cfg["serving"]
    pages = -(-srv["max_length"] // srv["page_size"])
    assert traffic["max_batch"] * pages + 1 <= srv["pool_pages"]
    # the new cell is on every accepted metric's list it reports, and
    # on none of the other kernels'
    lists = {m["name"]: m.get("workloads", []) for m in bench["per_layer"]}
    for name in ("sparse_mla_decode_attention_roofline.served",
                 "sparse_mla_decode_time_share.served",
                 "kept_flash_attention_roofline.served"):
        assert lists[name] == [CELL]
    for name in ("mla_decode_attention_roofline.served",
                 "mla_decode_time_share.served",
                 "sparse_decode_attention_roofline.served"):
        assert CELL not in lists[name]
    for name in ("sparse_attention_time_share.served",
                 "index_select_time_share.served",
                 "sparse_selected_share.served",
                 "kv_bytes_per_context_token.served"):
        assert lists[name][-1] == CELL


def test_sparse_latent_decode_cost_by_hand():
    from benchmarks.harness.kernel_cost import least_seconds
    from benchmarks.harness.peaks import peaks_for
    from benchmarks.harness.sparse_mla_cost import sparse_latent_decode

    H, DC, DR, TOPK = 128, 512, 64, 2048
    q_bytes = H * (2 * DC + DR) * 2     # q in (576 a head) + u out (512)
    # one row at context 7000 keeps 2048 cache rows: 128 heads x 2048 x
    # (576 score + 512 value) multiply-adds; 2048 rows x 576 x 2 B read
    # ONCE for all heads
    f, b = sparse_latent_decode([(1, 7000)], H, DC, DR, TOPK)
    assert f == 2 * 128 * 2048 * 1088 == 570_425_344
    assert b == 2048 * 1152 + q_bytes == 2_359_296 + 278_528
    # under the top-k a row keeps its whole context
    f, b = sparse_latent_decode([(1, 100)], H, DC, DR, TOPK)
    assert f == 2 * 128 * 100 * 1088
    assert b == 100 * 1152 + q_bytes
    # rows add up; the context beyond the top-k costs nothing more
    two = sparse_latent_decode([(1, 7000), (1, 100)], H, DC, DR, TOPK)
    assert two == (2 * 128 * 2148 * 1088, 2148 * 1152 + 2 * q_bytes)
    assert sparse_latent_decode([(1, 8448)], H, DC, DR, TOPK) \
        == sparse_latent_decode([(1, 2048)], H, DC, DR, TOPK)
    # heads do not multiply the cache bytes
    _, b_half = sparse_latent_decode([(1, 7000)], H // 2, DC, DR, TOPK)
    assert b - 100 * 1152 - q_bytes >= 0 and \
        sparse_latent_decode([(1, 7000)], H, DC, DR, TOPK)[1] - b_half \
        == q_bytes // 2
    # at 128 heads the kept rows' work sits AT the v5e's ridge: 242
    # FLOP a byte of cache row against 197e12 / 819e9 = 240
    f, b = sparse_latent_decode([(1, 7000)], H, DC, DR, TOPK)
    pk = peaks_for("TPU v5 lite")
    assert 0.85 < (f / pk.flops) / (b / pk.hbm_bytes) < 1.15
    assert least_seconds(f, b, pk) == max(f / pk.flops, b / pk.hbm_bytes)


def test_sparse_mla_roofline_reducer_by_hand():
    from benchmarks.harness import reduce as R
    from benchmarks.harness.kernel_cost import least_seconds
    from benchmarks.harness.peaks import peaks_for
    from benchmarks.harness.reducers import (op_time_share,
                                             sparse_mla_roofline)
    from benchmarks.harness.sparse_mla_cost import sparse_latent_decode

    pk = peaks_for("TPU v5 lite")
    cfg = {"num_attention_heads": 128, "kv_lora_rank": 512,
           "qk_rope_head_dim": 64, "index_topk": 2048,
           "num_hidden_layers": 5, "serving": {"decode_chunk": 1}}
    rows = [(1, 7000), (1, 6400)]
    f, b = sparse_latent_decode(rows, 128, 512, 64, 2048)
    least = least_seconds(f, b, pk)
    # 5 calls of the kernel (one a layer) of 4x the least time each, one
    # call of sarvam's kernel and one of keye's, which it must not read
    ops = [R.Op("custom-call", float(i), 4 * least, 0, "jit_step",
                "mla_paged_sparse_decode_attention") for i in range(5)]
    ops.append(R.Op("custom-call", 6.0, 1.0, 0, "jit_step",
                    "mla_paged_decode_attention"))
    ops.append(R.Op("fusion", 8.0, 1.0, 0, "jit_prefill", ""))
    tr = R.Trace(ops, [], (0.0, 10.0))
    ctx = {"trace": tr, "cfg": cfg, "peaks": pk,
           "host": {"decode_rows": rows}}
    got = sparse_mla_roofline.read(ctx, "jit_step", "decode_rows")
    assert got == pytest.approx(25.0)
    share = op_time_share.read(ctx, ["mla_paged_sparse_decode_attention"])
    assert share == pytest.approx(100 * 20 * least / (20 * least + 2.0))
    # sarvam's metric does not read this kernel
    assert op_time_share.read(ctx, ["mla_paged_decode_attention"]) \
        == pytest.approx(100 * 1.0 / (20 * least + 2.0))
    # nothing to read: no rows, no such kernel, another configuration
    assert sparse_mla_roofline.read(
        dict(ctx, host={}), "jit_step", "decode_rows") is None
    assert sparse_mla_roofline.read(
        dict(ctx, trace=R.Trace(ops[5:], [], (0.0, 10.0))), "jit_step",
        "decode_rows") is None
    assert sparse_mla_roofline.read(
        dict(ctx, cfg={"kv_lora_rank": 512, "serving": {}}), "jit_step",
        "decode_rows") is None


def test_kept_prefill_cost_and_its_reducer_by_hand():
    from benchmarks.harness import reduce as R
    from benchmarks.harness.kernel_cost import least_seconds
    from benchmarks.harness.peaks import peaks_for
    from benchmarks.harness.reducers import kept_flash_roofline
    from benchmarks.harness.sparse_mla_cost import kept_prefill

    # 8,192 rows, 2,048 kept: the first 2,048 rows keep t + 1 keys
    # (2,048 x 2,049 / 2 pairs), the other 6,144 keep 2,048 each; 128
    # heads x (192 score + 128 value) multiply-adds a pair; q and k in,
    # v in and the result out, every head its own
    f, b = kept_prefill(8192, 128, 192, 128, 2048)
    pairs = 2048 * 2049 // 2 + 6144 * 2048
    assert pairs == 14_681_088
    assert f == 2 * 128 * pairs * 320 == 1_202_674_728_960
    assert b == 2 * 8192 * 128 * 320 * 2 == 1_342_177_280
    # under the top-k every row keeps its whole past: plain causal pairs
    f, _ = kept_prefill(1024, 128, 192, 128, 2048)
    assert f == 2 * 128 * (1024 * 1025 // 2) * 320
    # compute bound on a v5e, by far
    pk = peaks_for("TPU v5 lite")
    f, b = kept_prefill(8192, 128, 192, 128, 2048)
    assert least_seconds(f, b, pk) == f / pk.flops > 3 * b / pk.hbm_bytes
    cfg = {"num_attention_heads": 128, "kv_lora_rank": 512,
           "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
           "v_head_dim": 128, "index_topk": 2048}
    least = least_seconds(f, b, pk)
    # 10 calls (5 layers of 2 prompts) at 4x the least time each, inside
    # prefill programs; one call inside another program is not read
    ops = [R.Op("custom-call", float(i), 4 * least, 0, "jit_prefill",
                "kept_flash_attention") for i in range(10)]
    ops.append(R.Op("custom-call", 11.0, 1.0, 0, "jit_step",
                    "kept_flash_attention"))
    ctx = {"trace": R.Trace(ops, [], (0.0, 20.0)), "cfg": cfg,
           "peaks": pk, "host": {},
           "traffic": {"prompt": {"min": 6144, "max": 8192}}}
    assert kept_flash_roofline.read(ctx, "jit_prefill") \
        == pytest.approx(25.0)
    # a mix over two buckets, another configuration, no kernel: nothing
    assert kept_flash_roofline.read(
        dict(ctx, traffic={"prompt": {"min": 2048, "max": 8192}}),
        "jit_prefill") is None
    assert kept_flash_roofline.read(
        dict(ctx, cfg={"kv_lora_rank": 512}), "jit_prefill") is None
    assert kept_flash_roofline.read(
        dict(ctx, trace=R.Trace(ops[10:], [], (0.0, 20.0))),
        "jit_prefill") is None
