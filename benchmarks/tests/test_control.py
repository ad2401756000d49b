"""The control of "How ``correct`` is decided", at a size a test run can
hold: the plain reference put in the program's place and computed in fp8,
the nearest precision below the configurations' bf16, must come out NOT
correct, while the program itself passes, on three seeds. At the cells'
own sizes this was read on the chip (``tools/control.py``; readings and
limits in PERF.md); the limits below are this tiny size's own.
"""
import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks.tests.test_run_cpu import GPT, MISTRAL, TRAFFIC  # noqa: E402

SEEDS = [11, 2 ** 31 + 12, 13]


def ok(numbers):
    return all(n["value"] <= n["limit"] for n in numbers)


@pytest.mark.parametrize("seed", SEEDS)
def test_training_control_fails(seed):
    import jax

    from benchmarks.harness.families import gpt_fleet
    from benchmarks.harness.traffic import train_steps

    cfg = dict(GPT, limits={"loss_abs": 0.002, "grad_norm_gap": 0.02,
                            "grad_diff": 0.025, "update_norm_gap": 0.6})
    plan = train_steps.plan(TRAFFIC["steps"], seed, 1.0, cfg["vocab_size"])
    system = gpt_fleet.build(cfg, TRAFFIC["steps"], plan, seed,
                             jax.devices()[:1])
    system.warm()
    got = gpt_fleet.control(system, {})
    assert ok(got["program"]), got["program"]
    assert not ok(got["control"]), got["control"]
    diff = {k: [n["value"] for n in got[k] if "difference" in n["name"]][0]
            for k in got}
    assert diff["control"] > 3 * diff["program"], diff


@pytest.mark.parametrize("seed", SEEDS)
def test_serving_control_fails(seed):
    import jax

    from benchmarks.harness import runner
    from benchmarks.harness.families import llama_serving
    from benchmarks.harness.traffic import open_loop

    cfg = dict(MISTRAL, limits={"served_logit_gap": 1.0})   # sound runs
    # of this size read up to 0.30, the control from 2.8 (five seeds)
    traffic = dict(TRAFFIC["chat"], check_requests=6)
    plan = open_loop.plan(traffic, seed, 2.0, cfg["vocab_size"])
    system = llama_serving.build(cfg, traffic, plan, seed,
                                 jax.devices()[:1])
    system.warm()
    phases = runner.Phases(False, 0.0, "", runner.CompileCounter(),
                           lambda: 0)
    result = open_loop.run(system, plan, 2.0, phases)
    got = llama_serving.control(system, result)
    assert ok(got["program"]), got
    assert not ok(got["control"]), got
    assert got["control"][0]["value"] > 3 * got["program"][0]["value"], got


def test_update_norm_skip_leaves_out_the_named_leaves():
    from benchmarks.harness.families.gpt_fleet import worst_leaf_gap

    want = {"h.0.qkv.b": 1.0, "h.0.qkv.w": 1.0, "h.1.fc1.w": 1.0}
    prog = {"h.0.qkv.b": 1.2, "h.0.qkv.w": 1.05, "h.1.fc1.w": 1.01}
    every = worst_leaf_gap(prog, want)
    assert every["leaf"] == "h.0.qkv.b" and every["next"] == "h.0.qkv.w:0.05"
    rest = worst_leaf_gap(prog, want, "qkv.b")
    assert rest["leaf"] == "h.0.qkv.w"
    assert rest["value"] == pytest.approx(0.05)
    assert rest["next"].startswith("h.1.fc1.w:")
