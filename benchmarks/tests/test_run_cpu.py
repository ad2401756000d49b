"""Each cell end to end on the CPU, through the harness's own functions,
with tiny configuration and traffic files written here (the pp2 x mp2 one
on four virtual devices). The command itself has no such option and
fails without a TPU, which is checked too. CPU numbers prove the control
flow and the contract's shape, never a speed.

Run: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
     python -m pytest benchmarks/tests -q
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=4")

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

GPT = {"family": "gpt_fleet", "reference": "gpt", "n_layers": 2,
       "d_model": 64, "n_heads": 4, "d_head": 16, "d_ff": 256, "n_ctx": 32,
       "vocab_size": 256, "dtype": "bfloat16", "layer_norm_eps": 1e-5,
       "initializer_range": 0.02,
       "optimizer": {"name": "AdamW", "learning_rate": 2e-4, "beta1": 0.9,
                     "beta2": 0.95, "epsilon": 1e-8, "weight_decay": 0.1,
                     "state_dtype": "bfloat16"},
       "parallel": {"dp_degree": 1, "mp_degree": 1, "pp_degree": 1},
       "limits": {"loss_abs": 0.05, "grad_norm_gap": 0.2, "grad_diff": 0.5,
                  "update_norm_gap": 0.6}}
GPT_PP = dict(GPT, n_layers=4,
              parallel={"dp_degree": 1, "mp_degree": 2, "pp_degree": 2})
MISTRAL = {"family": "llama_serving", "reference": "mistral",
           "hidden_size": 64, "intermediate_size": 128,
           "num_hidden_layers": 2, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
           "rope_theta": 1e6, "rms_norm_eps": 1e-5,
           "tie_word_embeddings": False, "torch_dtype": "bfloat16",
           "initializer_range": 0.3,      # wide logits at a tiny width
           "serving": {"page_size": 16, "max_length": 160,
                       "pool_pages": None, "decode_chunk": 1,
                       "prefill_chunk": None, "prefix_cache": False},
           "limits": {"served_logit_gap": 1.0}}
TRAFFIC = {
    "steps": {"kind": "train_steps", "batch": 2, "seq": 32, "distinct": 4,
              "trace_s": 0.5},
    "steps-pp": {"kind": "train_steps", "batch": 4, "seq": 32,
                 "micro_batch": 1, "distinct": 4, "trace_s": 0.5},
    "chat": {"kind": "open_loop", "max_batch": 4, "rate_per_s": 6.0,
             "ramp_s": 1.0, "cooldown_s": 5.0, "trace_s": 1.0,
             "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                        "min": 8, "max": 100},
             "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                        "min": 4, "max": 16},
             "ttft_limit_ms": 60000, "check_requests": 3},
    "batch": {"kind": "closed_loop", "max_batch": 2, "clients": 4,
              "ramp_s": 0.5, "cycle": 4, "trace_s": 1.0,
              "prompt": {"dist": "uniform", "min": 70, "max": 120},
              "output": {"dist": "uniform", "min": 3, "max": 6},
              "check_requests": 2},
}
CELLS = [("train-tiny", "gpt-tiny", "steps", 1),
         ("chat-tiny", "mistral-tiny", "chat", 1),
         ("batch-tiny", "mistral-tiny", "batch", 1),
         ("train-pp-tiny", "gpt-pp-tiny", "steps-pp", 4)]
E2E = {"train-tiny": "train_tokens_per_s_chip",
       "train-pp-tiny": "train_tokens_per_s_chip",
       "chat-tiny": "tpot_p50_ms", "batch-tiny": "served_tokens_per_s"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A benchmark of tiny cells in a temporary directory: its own
    BENCHMARK.json, configurations, traffic and metric files; the
    harness's code is the repo's, unedited."""
    root = tmp_path_factory.mktemp("bench")
    data = root / "b"
    for d in ("configs", "traffic", "metrics"):
        (data / d).mkdir(parents=True)
    for name, cfg in (("gpt-tiny", GPT), ("gpt-pp-tiny", GPT_PP),
                      ("mistral-tiny", MISTRAL)):
        (data / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    for name, t in TRAFFIC.items():
        (data / "traffic" / f"{name}.json").write_text(json.dumps(t))
    real = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    rename = {"train-1.3b-1chip": "train-tiny",
              "train-6.7b-pp2mp2": "train-pp-tiny",
              "serve-chat-steady": "chat-tiny",
              "serve-longprompt-batch": "batch-tiny"}

    def cells(m):
        m = dict(m)
        if "workloads" in m:
            m["workloads"] = [rename[w] for w in m["workloads"]]
        return m

    for m in real["per_layer"]:
        spec = json.load(open(os.path.join(
            REPO, "benchmarks", "metrics", m["name"] + ".json")))
        (data / "metrics" / f"{m['name']}.json").write_text(
            json.dumps(spec))
    # a metric added as a file and an entry, with no edit to the harness
    (data / "metrics" / "steps_counted.train.json").write_text(json.dumps(
        {"reducer": "host_value", "args": {"key": "steps"}}))
    per_layer = [cells(m) for m in real["per_layer"]] + [
        {"name": "steps_counted.train", "unit": "steps", "better": "higher",
         "source": "program_counter", "layer": "load generator",
         "moves": "train_tokens_per_s_chip", "workloads": ["train-tiny"]}]
    bench = {"command": real["command"], "paths": ["b"], "run_seconds": 2,
             "configs": [{"name": n, "source": "test",
                          "file": f"b/configs/{n}.json", "reduced": [],
                          "why": "tiny"}
                         for n in ("gpt-tiny", "gpt-pp-tiny",
                                   "mistral-tiny")],
             "workloads": [{"name": w, "config": c, "traffic": t,
                            "chips": n, "why": "tiny"}
                           for w, c, t, n in CELLS],
             "end_to_end": [cells(m) for m in real["end_to_end"]],
             "per_layer": per_layer}
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


@pytest.mark.parametrize("workload", [c[0] for c in CELLS])
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_on_cpu(root, workload, trace):
    from benchmarks.harness.runner import run_cell

    lines = []
    out = run_cell(root, workload, seed=2 ** 31 + 12345 + trace,
                   seconds=2.0, trace=bool(trace), require_tpu=False,
                   say=lines.append)
    text = "\n".join(lines)
    line = json.loads(json.dumps(out))          # what the command prints
    want = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line) == want | ({"breakdown"} if trace else set()), text
    assert line["correct"] is True, text
    assert line["attempted"] > 0 and line["failed"] == 0, text
    assert {"platform", "kind", "count",
            "memory_peak_bytes"} <= set(line["device"])
    for v in line["metrics"].values():
        assert set(v) == {"value", "unit"} and v["value"] == v["value"]
    if trace:
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert "setup_s" not in line["metrics"]
        assert len(line["breakdown"]["device_ops"]) <= 10
        if workload == "train-tiny":
            assert line["metrics"]["steps_counted.train"]["value"] >= 2
    else:
        assert set(line["metrics"]) == {E2E[workload], "setup_s"}, text
        assert all(v["value"] > 0 for v in line["metrics"].values())


def test_command_fails_without_a_tpu():
    """The command has no CPU option: held to the CPU it exits non-zero
    and prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload",
         "train-1.3b-1chip", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert not any(l.startswith('{"correct"')
                   for l in p.stdout.splitlines())


def test_broken_step_is_not_correct(root, monkeypatch):
    """The rest of a run with the timed path broken underneath: a train
    step that returns its state unchanged must come out not correct."""
    from benchmarks.harness.families import gpt_fleet
    from benchmarks.harness.runner import run_cell

    real_build = gpt_fleet.build

    def broken(*a):
        system = real_build(*a)
        step = system._step

        def frozen(x, y):
            import jax.numpy as jnp

            snap = [jnp.array(p._value, copy=True) for p in system.params]
            loss = step(x, y)
            for p, v in zip(system.params, snap):
                p._value = v                    # the step leaves no trace
            return loss
        system._step = frozen
        return system

    monkeypatch.setattr(gpt_fleet, "build", broken)
    lines = []
    out = run_cell(root, "train-tiny", seed=77, seconds=1.0, trace=False,
                   require_tpu=False, say=lines.append)
    assert out["correct"] is False, "\n".join(lines)
    assert any("parameter change" in l and "FAIL" in l for l in lines)


def test_altered_token_is_not_correct(root, monkeypatch):
    """A served token altered where it is produced: not correct."""
    from benchmarks.harness.families import llama_serving
    from benchmarks.harness.runner import run_cell

    real_pop = llama_serving.System.pop_finished

    def altered(self):
        out = real_pop(self)
        for _, _, _, tokens in out:
            tokens[len(tokens) // 2] = (tokens[len(tokens) // 2] + 7) % 256
        return out

    monkeypatch.setattr(llama_serving.System, "pop_finished", altered)
    lines = []
    out = run_cell(root, "chat-tiny", seed=78, seconds=1.5, trace=False,
                   require_tpu=False, say=lines.append)
    assert out["correct"] is False, "\n".join(lines)
    assert any("widest gap" in l and "FAIL" in l for l in lines)
