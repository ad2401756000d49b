"""tpulint — the trace-safety & API-fidelity static analyzer (tools/
tpulint) wired into tier-1.

Under test:
- each shipped rule fires on a positive fixture and stays silent on the
  clean equivalent (the enforce-or-implement / bucketed versions)
- suppression pragmas (same line, comment line above, whole file)
- baseline fingerprint matching (line-number shifts don't break it,
  fixed findings surface as stale)
- the WHOLE-TREE GATE: paddle_tpu/ has zero findings outside the
  checked-in baseline — this is the CI teeth; a new silent-ignore knob
  or unbucketed jit-factory int fails tier-1
- CLI exit codes incl. a seeded violation (acceptance criteria)
"""
import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:                     # direct pytest invocation
    sys.path.insert(0, str(REPO))

from tools.tpulint import (ALL_RULES, RULES_BY_ID, Project,  # noqa: E402
                           baseline_entry, lint_paths, lint_project,
                           lint_source, load_baseline, select_rules,
                           split_by_baseline)


def run_rule(rule_id, src, relpath="fixture.py", resources=None):
    return lint_source(src, relpath, select_rules([rule_id]),
                       resources=resources)


def run_project(rule_id, sources, resources=None):
    """Lint a multi-file in-memory project with one rule (the
    interprocedural fixtures)."""
    project = Project.from_sources(sources, resources=resources)
    return lint_project(project, select_rules([rule_id]))


def rule_ids(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# rule fixtures: positive fires, negative is silent
# ---------------------------------------------------------------------------
class TestUnusedKnob:
    POS = """
def pool3d(x, kernel_size, ceil_mode=False):
    return x + kernel_size
"""
    NEG_READ = """
def pool3d(x, kernel_size, ceil_mode=False):
    return x + kernel_size + (1 if ceil_mode else 0)
"""
    NEG_ENFORCED = """
from paddle_tpu.core.enforce import enforce

def pool3d(x, kernel_size, ceil_mode=False):
    enforce(not ceil_mode, "ceil_mode is not served here")
    return x + kernel_size
"""

    def test_positive(self):
        fs = run_rule("unused-knob", self.POS)
        assert rule_ids(fs) == ["unused-knob"]
        assert "'ceil_mode'" in fs[0].message and fs[0].symbol == "pool3d"

    def test_negative_read(self):
        assert run_rule("unused-knob", self.NEG_READ) == []

    def test_negative_enforce_guard(self):
        assert run_rule("unused-knob", self.NEG_ENFORCED) == []

    def test_name_param_and_private_fn_exempt(self):
        src = """
def rank(x, name=None):
    return x.ndim

def _helper(x, internal_knob=3):
    return x
"""
        assert run_rule("unused-knob", src) == []

    def test_stub_exempt(self):
        src = """
class BaseTransform:
    def _apply_image(self, img):
        raise NotImplementedError
"""
        assert run_rule("unused-knob", src) == []


class TestHostSyncInJit:
    POS = """
import jax
import jax.numpy as jnp
import numpy as np

def body(x):
    s = jnp.sum(x)
    return np.asarray(s)

step = jax.jit(body)
"""
    NEG_NOT_JITTED = """
import jax.numpy as jnp
import numpy as np

def body(x):
    s = jnp.sum(x)
    return np.asarray(s)
"""
    NEG_STAYS_TRACED = """
import jax
import jax.numpy as jnp

def body(x):
    return jnp.sum(x)

step = jax.jit(body)
"""

    def test_positive(self):
        fs = run_rule("host-sync-in-jit", self.POS)
        assert rule_ids(fs) == ["host-sync-in-jit"]
        assert "np.asarray" in fs[0].message

    def test_negative_outside_jit(self):
        assert run_rule("host-sync-in-jit", self.NEG_NOT_JITTED) == []

    def test_negative_pure_jnp(self):
        assert run_rule("host-sync-in-jit", self.NEG_STAYS_TRACED) == []

    def test_item_in_def_op_kernel(self):
        src = """
from paddle_tpu.core.dispatch import def_op

@def_op("bad_kernel")
def bad_kernel(x):
    return x.item()
"""
        fs = run_rule("host-sync-in-jit", src)
        assert rule_ids(fs) == ["host-sync-in-jit"]
        assert ".item()" in fs[0].message

    def test_int_of_static_knob_allowed(self):
        # int() on a static Python knob inside a traced kernel is fine;
        # only tainted (traced-array) expressions count
        src = """
from paddle_tpu.core.dispatch import def_op
import jax.numpy as jnp

@def_op("k")
def k(x, sampling_ratio=-1):
    sr = int(sampling_ratio)
    return jnp.sum(x) * sr
"""
        assert run_rule("host-sync-in-jit", src) == []

    def test_float_of_traced_value_flagged(self):
        src = """
import jax
import jax.numpy as jnp

def body(x):
    return float(jnp.max(x))

f = jax.jit(body)
"""
        fs = run_rule("host-sync-in-jit", src)
        assert rule_ids(fs) == ["host-sync-in-jit"]


class TestTracedBool:
    POS = """
import jax
import jax.numpy as jnp

def body(x):
    y = jnp.sum(x)
    if y > 0:
        return x
    return -x

f = jax.jit(body)
"""
    NEG_STATIC_KNOB = """
import jax
import jax.numpy as jnp

def body(x, ceil_mode=False):
    if ceil_mode:
        return jnp.ceil(x)
    return x

f = jax.jit(body)
"""
    NEG_SHAPE_AND_NONE = """
import jax
import jax.numpy as jnp

def body(x, mask=None):
    y = jnp.abs(x)
    if y.ndim == 2:
        y = y[None]
    if mask is not None:
        y = y * mask
    return y

f = jax.jit(body)
"""

    def test_positive(self):
        fs = run_rule("traced-bool", self.POS)
        assert rule_ids(fs) == ["traced-bool"]
        assert "'y'" in fs[0].message

    def test_negative_static_knob(self):
        assert run_rule("traced-bool", self.NEG_STATIC_KNOB) == []

    def test_negative_shape_and_none_checks(self):
        assert run_rule("traced-bool", self.NEG_SHAPE_AND_NONE) == []

    def test_while_on_traced(self):
        src = """
import jax
import jax.numpy as jnp

def body(x):
    n = jnp.sum(x)
    while n > 0:
        n = n - 1
    return n

f = jax.jit(body)
"""
        fs = run_rule("traced-bool", src)
        assert rule_ids(fs) == ["traced-bool"]
        assert "`while`" in fs[0].message


class TestNonhashableStatic:
    POS_DECORATOR = """
from functools import partial
import jax

@partial(jax.jit, static_argnames=("sizes",))
def f(x, sizes=[1, 2]):
    return x
"""
    POS_ARGNUMS = """
import jax

def f(x, sizes=[8, 16]):
    return x

g = jax.jit(f, static_argnums=(1,))
"""
    NEG_TUPLE = """
from functools import partial
import jax

@partial(jax.jit, static_argnames=("sizes",))
def f(x, sizes=(1, 2)):
    return x
"""

    def test_positive_decorator(self):
        fs = run_rule("nonhashable-static", self.POS_DECORATOR)
        assert rule_ids(fs) == ["nonhashable-static"]
        assert "'sizes'" in fs[0].message

    def test_positive_call_form(self):
        fs = run_rule("nonhashable-static", self.POS_ARGNUMS)
        assert rule_ids(fs) == ["nonhashable-static"]

    def test_negative_tuple_default(self):
        assert run_rule("nonhashable-static", self.NEG_TUPLE) == []


class TestRecompileHazard:
    POS = """
def serve(pred, prompts):
    B = len(prompts)
    prefill = pred._prefill_fn(B, 128)
    return prefill(prompts)
"""
    NEG_BUCKETED = """
def _bucket(n, lo=64):
    b = lo
    while b < n:
        b *= 2
    return b

def serve(pred, prompts):
    B = _bucket(len(prompts))
    prefill = pred._prefill_fn(B, 128)
    return prefill(prompts)
"""
    NEG_SANITIZING_HELPER = """
def _max_len(self, S0):
    return _bucket(S0)

def serve(self, pred, ids):
    B, S0 = ids.shape
    M = self._max_len(S0)
    fn = pred._decode_fn(M, 4)
    return fn(ids)
"""

    def test_positive(self):
        fs = run_rule("recompile-hazard", self.POS)
        assert rule_ids(fs) == ["recompile-hazard"]
        assert "'B'" in fs[0].message and "_prefill_fn" in fs[0].message

    def test_negative_bucketed(self):
        assert run_rule("recompile-hazard", self.NEG_BUCKETED) == []

    def test_negative_bucketing_helper_sanitizes(self):
        assert run_rule("recompile-hazard", self.NEG_SANITIZING_HELPER) \
            == []

    def test_shape_attr_direct_arg(self):
        src = """
def serve(pred, ids):
    fn = pred._decode_fn(ids.shape[0], 4)
    return fn(ids)
"""
        fs = run_rule("recompile-hazard", src)
        assert rule_ids(fs) == ["recompile-hazard"]

    def test_jitted_callable_args_not_boundaries(self):
        # python ints into the RETURNED jitted fn become weak-typed
        # traced scalars — no recompile, no finding
        src = """
def serve(pred, ids):
    fn = pred._decode_fn(4, 128)
    pos = ids.shape[1]
    return fn(ids, pos)
"""
        assert run_rule("recompile-hazard", src) == []


# ---------------------------------------------------------------------------
# interprocedural contract rules (the tpulint v2 Project pass)
# ---------------------------------------------------------------------------
class TestRawCollective:
    POS = """
import jax
from jax import lax

def grad_sync(g):
    return lax.psum(g, "dp")
"""
    NEG_SHIM = """
from ..distributed.collective import t_psum

def grad_sync(g):
    return t_psum(g, "dp")
"""

    def test_positive(self):
        fs = run_rule("raw-collective", self.POS,
                      relpath="paddle_tpu/models/foo.py")
        assert rule_ids(fs) == ["raw-collective"]
        assert "t_psum" in fs[0].message

    def test_negative_through_shim(self):
        assert run_rule("raw-collective", self.NEG_SHIM,
                        relpath="paddle_tpu/models/foo.py") == []

    def test_allowlisted_modules(self):
        # the shim itself and the ledger's ablation/replay lowering
        # are the two places that must touch lax
        for rel in ("paddle_tpu/distributed/collective.py",
                    "paddle_tpu/observability/commledger.py"):
            assert run_rule("raw-collective", self.POS, relpath=rel) == []

    def test_all_wrapped_ops_flagged(self):
        src = """
from jax import lax

def f(x):
    a = lax.all_gather(x, "mp", axis=0, tiled=True)
    b = lax.psum_scatter(x, "mp")
    c = lax.all_to_all(x, "ep", 0, 1)
    d = lax.ppermute(x, "pp", [(0, 1)])
    return a, b, c, d
"""
        fs = run_rule("raw-collective", src,
                      relpath="paddle_tpu/models/foo.py")
        assert len(fs) == 4

    def test_local_helper_named_psum_not_flagged(self):
        src = """
def psum(x, axes):
    return x

def f(x):
    return psum(x, "dp")
"""
        assert run_rule("raw-collective", src,
                        relpath="paddle_tpu/models/foo.py") == []


class TestUnregisteredMetric:
    SCHEMA = {"pt_requests_total": {"type": "counter"},
              "pt_depth": {"type": "gauge"}}
    CATALOG = """
from .metrics import get_registry

def serving_metrics():
    r = get_registry()
    return {
        "requests": r.counter("pt_requests_total", "requests"),
        "depth": r.gauge("pt_depth", "queue depth"),
    }
"""

    def test_clean_when_in_sync(self):
        fs = run_project(
            "unregistered-metric",
            {"pkg/observability/catalog.py": self.CATALOG},
            resources={"metric_schema": self.SCHEMA})
        assert fs == []

    def test_direction1_unknown_registration(self):
        # a registration anywhere in the tree outside the schema —
        # including a module that is NOT the catalog (cross-module)
        extra = """
from .observability.metrics import get_registry

def init():
    get_registry().counter("pt_rogue_total", "untracked")
"""
        fs = run_project(
            "unregistered-metric",
            {"pkg/observability/catalog.py": self.CATALOG,
             "pkg/engine.py": extra},
            resources={"metric_schema": self.SCHEMA})
        assert rule_ids(fs) == ["unregistered-metric"]
        assert fs[0].path == "pkg/engine.py"
        assert "pt_rogue_total" in fs[0].message

    def test_direction2_stale_schema_entry(self):
        schema = dict(self.SCHEMA)
        schema["pt_dead_gauge"] = {"type": "gauge"}
        fs = run_project(
            "unregistered-metric",
            {"pkg/observability/catalog.py": self.CATALOG},
            resources={"metric_schema": schema})
        assert rule_ids(fs) == ["unregistered-metric"]
        assert fs[0].symbol == "<schema>"
        assert "pt_dead_gauge" in fs[0].message and "stale" in \
            fs[0].message

    def test_jnp_histogram_not_a_registration(self):
        src = """
import jax.numpy as jnp

def h(x):
    return jnp.histogram(x, bins=10)
"""
        fs = run_project(
            "unregistered-metric",
            {"pkg/observability/catalog.py": self.CATALOG,
             "pkg/ops.py": src},
            resources={"metric_schema": self.SCHEMA})
        assert fs == []

    def test_silent_without_schema_resource(self):
        assert run_project("unregistered-metric",
                           {"pkg/catalog.py": self.CATALOG}) == []


class TestVjpLedgerSymmetry:
    def test_mirrored_ring_accepted_cross_module(self):
        # fwd/bwd collective facts resolved through an impl helper in
        # ANOTHER module (the collective_matmul delegation shape)
        rings = """
def ring_fwd_impl(x, axes):
    return t_ppermute(x, axes, [(0, 1)])

def ring_bwd_impl(g, axes):
    return t_ppermute(g, axes, [(1, 0)])
"""
        op = """
import jax
from functools import partial
from .rings import ring_fwd_impl, ring_bwd_impl

@partial(jax.custom_vjp, nondiff_argnums=(1,))
def ring_op(x, axes):
    return ring_fwd_impl(x, axes)

def _fwd(x, axes):
    return ring_fwd_impl(x, axes), None

def _bwd(axes, res, g):
    return (ring_bwd_impl(g, axes),)

ring_op.defvjp(_fwd, _bwd)
"""
        assert run_project("vjp-ledger-symmetry",
                           {"pkg/rings.py": rings,
                            "pkg/op.py": op}) == []

    def test_missing_bwd_shim_rejected(self):
        src = """
import jax
from functools import partial

@partial(jax.custom_vjp, nondiff_argnums=(1,))
def dispatch(x, axes):
    return t_all_to_all(x, axes, 0, 1)

def _fwd(x, axes):
    return dispatch(x, axes), None

def _bwd(axes, res, g):
    return (g,)

dispatch.defvjp(_fwd, _bwd)
"""
        fs = run_project("vjp-ledger-symmetry", {"pkg/op.py": src})
        assert rule_ids(fs) == ["vjp-ledger-symmetry"]
        assert "no t_* collective" in fs[0].message

    def test_non_mirrored_bwd_rejected(self):
        src = """
import jax
from functools import partial

@partial(jax.custom_vjp, nondiff_argnums=(1,))
def gather(x, axes):
    return t_all_gather(x, axes, axis=0, tiled=True)

def _fwd(x, axes):
    return gather(x, axes), None

def _bwd(axes, res, g):
    return (t_all_gather(g, axes, axis=0, tiled=True),)

gather.defvjp(_fwd, _bwd)
"""
        fs = run_project("vjp-ledger-symmetry", {"pkg/op.py": src})
        assert rule_ids(fs) == ["vjp-ledger-symmetry"]
        assert "mirrored" in fs[0].message

    def test_psum_identity_pairing_accepted(self):
        # the Megatron pairing: reduce-family fwd, identity bwd
        src = """
import jax
from functools import partial

@partial(jax.custom_vjp, nondiff_argnums=(1,))
def mp_allreduce(x, axes):
    return t_psum(x, axes)

mp_allreduce.defvjp(lambda x, axes: (t_psum(x, axes), None),
                    lambda axes, res, g: (g,))
"""
        assert run_project("vjp-ledger-symmetry",
                           {"pkg/op.py": src}) == []

    def test_gather_slice_pairing_accepted(self):
        # the _c_concat pairing: replicated cotangent, local slice bwd
        src = """
import jax
from functools import partial
from jax import lax

@partial(jax.custom_vjp, nondiff_argnums=(1,))
def concat(x, axes):
    return t_all_gather(x, axes, axis=0, tiled=True)

def _fwd(x, axes):
    return concat(x, axes), x.shape[0]

def _bwd(axes, local, g):
    return (lax.dynamic_slice_in_dim(g, 0, local, axis=0),)

concat.defvjp(_fwd, _bwd)
"""
        assert run_project("vjp-ledger-symmetry",
                           {"pkg/op.py": src}) == []

    def test_collective_free_vjp_skipped(self):
        src = """
import jax
from functools import partial

@partial(jax.custom_vjp, nondiff_argnums=())
def sq(x):
    return x * x

sq.defvjp(lambda x: (x * x, x), lambda x, g: (2 * x * g,))
"""
        assert run_project("vjp-ledger-symmetry",
                           {"pkg/op.py": src}) == []

    def test_quantized_allreduce_keeps_psum_identity_pairing(self):
        # the quant_comm wrappers map to their LOGICAL collective kind
        # in the shim table (an int8 allreduce lowers to a2a+all_gather
        # internally, but the contract is a psum) — so the Megatron
        # psum/identity pairing stays recognizable through a quantized
        # forward. Without the mapping this fwd would read as
        # {all_to_all, all_gather} vs an identity bwd and flag.
        src = """
import jax
from functools import partial
from . import quant_comm as _qc

@partial(jax.custom_vjp, nondiff_argnums=(1,))
def mp_allreduce(x, axes):
    out, _ = _qc.quantized_allreduce(x, axes, None)
    return out

mp_allreduce.defvjp(
    lambda x, axes: (mp_allreduce(x, axes), None),
    lambda axes, res, g: (g,))
"""
        helper = """
def quantized_allreduce(v, axes, cfg):
    q = v
    qq = t_all_to_all(q, axes, 0, 0, tiled=True)
    full = t_all_gather(qq, axes, axis=0, tiled=True)
    return full, v
"""
        assert run_project("vjp-ledger-symmetry",
                           {"pkg/quant_comm.py": helper,
                            "pkg/op.py": src}) == []

    def test_quantized_ring_mirrored_pairing_accepted(self):
        # quantized rings ship (payload, scales) pairs through
        # permute_packed -> t_ppermute: the ppermute<->ppermute mirror
        # must resolve through the packing helper
        helper = """
def permute_packed(q, s, name, perm, ratio):
    return t_ppermute(q, name, perm), t_ppermute(s, name, perm)
"""
        op = """
import jax
from functools import partial
from .quant_comm import permute_packed

@partial(jax.custom_vjp, nondiff_argnums=(1,))
def qring(x, axes):
    q, s = permute_packed(x, x, axes, [(0, 1)], 0.25)
    return q

def _fwd(x, axes):
    return qring(x, axes), None

def _bwd(axes, res, g):
    q, s = permute_packed(g, g, axes, [(1, 0)], 0.25)
    return (q,)

qring.defvjp(_fwd, _bwd)
"""
        assert run_project("vjp-ledger-symmetry",
                           {"pkg/quant_comm.py": helper,
                            "pkg/op.py": op}) == []


class TestDonationReuse:
    STORE = """
import jax

class Engine:
    def _step_fn(self):
        self._fns = {}
        self._fns["k"] = jax.jit(lambda p, x, c: (x, c),
                                 donate_argnums=(2,))
        return self._fns["k"]

    def _run(self, site, fn, *args):
        return fn(*args)
"""

    def test_read_after_donation_flagged(self):
        src = self.STORE + """
    def bad(self, p, x, cache):
        fn = self._step_fn()
        out = fn(p, x, cache)
        return out, cache.sum()
"""
        fs = run_project("donation-reuse", {"pkg/engine.py": src})
        assert rule_ids(fs) == ["donation-reuse"]
        assert "'cache'" in fs[0].message

    def test_rebound_from_results_clean(self):
        src = self.STORE + """
    def good(self, p, x, cache):
        fn = self._step_fn()
        out, cache = fn(p, x, cache)
        return out, cache.sum()
"""
        assert run_project("donation-reuse",
                           {"pkg/engine.py": src}) == []

    def test_through_forwarder_wrapper(self):
        # self._run(site, fn, *payload) shifts the donated position —
        # the ServingEngine._run_captured shape
        src = self.STORE + """
    def bad(self, p, x, cache):
        fn = self._step_fn()
        out = self._run("site", fn, p, x, cache)
        return out, cache
"""
        fs = run_project("donation-reuse", {"pkg/engine.py": src})
        assert rule_ids(fs) == ["donation-reuse"]

    def test_forwarder_rebind_clean(self):
        src = self.STORE + """
    def good(self, p, x, cache):
        fn = self._step_fn()
        out, cache = self._run("site", fn, p, x, cache)
        return out, cache
"""
        assert run_project("donation-reuse",
                           {"pkg/engine.py": src}) == []

    def test_direct_call_of_store_subscript(self):
        src = self.STORE + """
    def bad(self, p, x, cache):
        self._step_fn()
        out = self._fns["k"](p, x, cache)
        return out, cache
"""
        fs = run_project("donation-reuse", {"pkg/engine.py": src})
        assert rule_ids(fs) == ["donation-reuse"]

    def test_undonated_jit_clean(self):
        src = """
import jax

def go(p, cache):
    fn = jax.jit(lambda a, b: b)
    out = fn(p, cache)
    return out, cache
"""
        assert run_project("donation-reuse", {"pkg/m.py": src}) == []


class TestUnguardedSharedMutation:
    POS = """
import threading

class Agg:
    def __init__(self):
        self.count = 0
        self._lock = threading.Lock()

    def start(self):
        threading.Thread(target=self._loop, daemon=True).start()

    def _loop(self):
        self.count += 1

    def read_and_reset(self):
        v = self.count
        self.count = 0
        return v
"""
    NEG_LOCKED = """
import threading

class Agg:
    def __init__(self):
        self.count = 0
        self._lock = threading.Lock()

    def start(self):
        threading.Thread(target=self._loop, daemon=True).start()

    def _loop(self):
        with self._lock:
            self.count += 1

    def read_and_reset(self):
        with self._lock:
            v = self.count
            self.count = 0
        return v
"""

    def test_positive(self):
        fs = run_project("unguarded-shared-mutation",
                         {"pkg/observability/agg.py": self.POS})
        assert rule_ids(fs) == ["unguarded-shared-mutation"]
        assert "'self.count'" in fs[0].message
        assert fs[0].symbol == "Agg._loop"

    def test_negative_common_lock(self):
        assert run_project(
            "unguarded-shared-mutation",
            {"pkg/observability/agg.py": self.NEG_LOCKED}) == []

    def test_out_of_scope_module_not_reported(self):
        # reachability is whole-tree but findings are scoped to the
        # concurrent subsystems
        assert run_project("unguarded-shared-mutation",
                           {"pkg/models/agg.py": self.POS}) == []

    def test_cross_module_thread_target(self):
        # Thread target in one module reaches a mutating method of a
        # class defined in a scoped module two hops away
        driver = """
import threading
from .observability.sink import SINK

def _work():
    SINK.record(1)

def start():
    threading.Thread(target=_work, daemon=True).start()
"""
        sink = """
class Sink:
    def __init__(self):
        self.total = 0

    def record(self, n):
        self.total += n

    def flush(self):
        v = self.total
        self.total = 0
        return v

SINK = Sink()
"""
        fs = run_project("unguarded-shared-mutation",
                         {"pkg/driver.py": driver,
                          "pkg/observability/sink.py": sink})
        assert rule_ids(fs) == ["unguarded-shared-mutation"]
        assert fs[0].path == "pkg/observability/sink.py"
        assert "Sink.record" in fs[0].message or \
            fs[0].symbol == "Sink.record"

    def test_lock_held_on_entry_fixpoint(self):
        # a private helper only ever called under the lock is guarded
        # even though its own body shows no `with` (goodput.py's
        # _close_interval shape)
        src = """
import threading

class Led:
    def __init__(self):
        self.total = 0
        self._lock = threading.Lock()

    def start(self):
        threading.Thread(target=self._loop, daemon=True).start()

    def _loop(self):
        with self._lock:
            self._bump()

    def _bump(self):
        self.total += 1

    def read(self):
        with self._lock:
            return self.total
"""
        assert run_project("unguarded-shared-mutation",
                           {"pkg/observability/led.py": src}) == []

    def test_init_only_mutation_exempt(self):
        src = """
import threading

class Led:
    def __init__(self):
        self._setup()
        threading.Thread(target=self._loop, daemon=True).start()

    def _setup(self):
        self.total = 0

    def _loop(self):
        with self._lock:
            pass

    def read(self):
        return self.total
"""
        assert run_project("unguarded-shared-mutation",
                           {"pkg/observability/led.py": src}) == []

    def test_threadsafe_attr_exempt(self):
        src = """
import queue
import threading

class Pump:
    def __init__(self):
        self._q = queue.Queue()

    def start(self):
        threading.Thread(target=self._loop, daemon=True).start()

    def _loop(self):
        self._q.put(1)

    def drain(self):
        return self._q.get_nowait()
"""
        assert run_project("unguarded-shared-mutation",
                           {"pkg/observability/pump.py": src}) == []


# ---------------------------------------------------------------------------
# lock-order-cycle (ISSUE 17): the lock-graph deadlock prover
# ---------------------------------------------------------------------------
class TestLockOrderCycle:
    # two module-level locks, two Thread entrypoints, opposite
    # acquisition order across modules: the classic AB/BA deadlock
    A_THEN_B = """
import threading
from pkg import beta

_lock_a = threading.Lock()

def start():
    threading.Thread(target=loop_a, daemon=True).start()

def loop_a():
    with _lock_a:
        with beta._lock_b:
            pass
"""
    B_THEN_A = """
import threading
from pkg import alpha

_lock_b = threading.Lock()

def start():
    threading.Thread(target=loop_b, daemon=True).start()

def loop_b():
    with _lock_b:
        with alpha._lock_a:
            pass
"""

    def test_two_thread_ab_ba_cycle_across_modules(self):
        fs = run_project("lock-order-cycle",
                         {"pkg/alpha.py": self.A_THEN_B,
                          "pkg/beta.py": self.B_THEN_A})
        assert rule_ids(fs) == ["lock-order-cycle"]
        msg = fs[0].message
        assert "pkg/alpha.py:_lock_a" in msg
        assert "pkg/beta.py:_lock_b" in msg
        # both thread entrypoints named as the interleaving witnesses
        assert "loop_a" in msg and "loop_b" in msg

    def test_acyclic_nested_locks_clean(self):
        # same two threads, same two locks, CONSISTENT A-then-B order
        b_same_order = """
import threading
from pkg import alpha

_lock_b = threading.Lock()

def start():
    threading.Thread(target=loop_b, daemon=True).start()

def loop_b():
    with alpha._lock_a:
        with _lock_b:
            pass
"""
        assert run_project("lock-order-cycle",
                           {"pkg/alpha.py": self.A_THEN_B,
                            "pkg/beta.py": b_same_order}) == []

    def test_single_thread_cycle_not_flagged(self):
        # both orders exercised, but from ONE entrypoint — a single
        # thread acquires sequentially and cannot deadlock itself
        src = """
import threading

_a = threading.Lock()
_b = threading.Lock()

def start():
    threading.Thread(target=loop, daemon=True).start()

def loop():
    with _a:
        with _b:
            pass
    with _b:
        with _a:
            pass
"""
        assert run_project("lock-order-cycle", {"pkg/m.py": src}) == []

    def test_cycle_through_entry_held_helper(self):
        # three locks, three contexts: Pump._loop holds self._lock and
        # calls a helper that takes beta._lock_b (interprocedural
        # edge); beta's watch thread orders _lock_b -> _lock_c; the
        # main-thread flush() closes the cycle _lock_c -> Pump._lock
        src_a = """
import threading
from pkg import beta

class Pump:
    def __init__(self):
        self._lock = threading.Lock()

    def start(self):
        threading.Thread(target=self._loop, daemon=True).start()

    def _loop(self):
        with self._lock:
            self._drain()

    def _drain(self):
        with beta._lock_b:
            pass

    def flush(self):
        with beta._lock_c:
            with self._lock:
                pass
"""
        src_b = """
import threading

_lock_b = threading.Lock()
_lock_c = threading.Lock()

def start():
    threading.Thread(target=watch, daemon=True).start()

def watch():
    with _lock_b:
        with _lock_c:
            pass
"""
        fs = run_project("lock-order-cycle",
                         {"pkg/alpha.py": src_a, "pkg/beta.py": src_b})
        assert rule_ids(fs) == ["lock-order-cycle"]
        assert "Pump._lock" in fs[0].message


# ---------------------------------------------------------------------------
# blocking-under-lock (ISSUE 17)
# ---------------------------------------------------------------------------
class TestBlockingUnderLock:
    def test_jit_dispatch_under_lock(self):
        src = """
import jax
import threading

class Engine:
    def __init__(self):
        self._lock = threading.RLock()
        self._step = jax.jit(lambda x: x)

    def run(self, x):
        with self._lock:
            return self._step(x)
"""
        fs = run_project("blocking-under-lock",
                         {"pkg/inference/serving.py": src})
        assert rule_ids(fs) == ["blocking-under-lock"]
        assert "jitted dispatch" in fs[0].message
        assert fs[0].symbol == "Engine.run"

    def test_rebind_under_lock_dispatch_after_release_clean(self):
        # the sanctioned pattern: grab the callable reference under
        # the lock, pay compile + device time outside it
        src = """
import jax
import threading

class Engine:
    def __init__(self):
        self._lock = threading.RLock()
        self._step = jax.jit(lambda x: x)

    def run(self, x):
        with self._lock:
            fn = self._step
        return fn(x)
"""
        assert run_project("blocking-under-lock",
                           {"pkg/inference/serving.py": src}) == []

    def test_local_jit_alias_under_lock_still_flagged(self):
        src = """
import jax
import threading

class Engine:
    def __init__(self):
        self._lock = threading.RLock()
        self._step = jax.jit(lambda x: x)

    def run(self, x):
        fn = self._step
        with self._lock:
            return fn(x)
"""
        fs = run_project("blocking-under-lock",
                         {"pkg/inference/serving.py": src})
        assert rule_ids(fs) == ["blocking-under-lock"]

    def test_cv_wait_outside_predicate_loop(self):
        src = """
import threading

class Box:
    def __init__(self):
        self._cv = threading.Condition()
        self.ready = False

    def take(self):
        with self._cv:
            if not self.ready:
                self._cv.wait()
"""
        fs = run_project("blocking-under-lock",
                         {"pkg/observability/box.py": src})
        assert rule_ids(fs) == ["blocking-under-lock"]
        assert "predicate loop" in fs[0].message

    def test_cv_wait_in_predicate_loop_clean(self):
        src = """
import threading

class Box:
    def __init__(self):
        self._cv = threading.Condition()
        self.ready = False

    def take(self):
        with self._cv:
            while not self.ready:
                self._cv.wait()

    def put(self):
        with self._cv:
            self.ready = True
            self._cv.notify_all()
"""
        assert run_project("blocking-under-lock",
                           {"pkg/observability/box.py": src}) == []

    def test_notify_without_cv_held(self):
        src = """
import threading

class Box:
    def __init__(self):
        self._cv = threading.Condition()
        self.ready = False

    def put(self):
        self.ready = True
        self._cv.notify_all()
"""
        fs = run_project("blocking-under-lock",
                         {"pkg/observability/box.py": src})
        assert rule_ids(fs) == ["blocking-under-lock"]
        assert "without holding" in fs[0].message

    def test_timeoutless_queue_get_under_lock(self):
        src = """
import queue
import threading

class Pump:
    def __init__(self):
        self._lock = threading.Lock()
        self._q = queue.Queue()

    def drain(self):
        with self._lock:
            return self._q.get()
"""
        fs = run_project("blocking-under-lock",
                         {"pkg/observability/pump.py": src})
        assert rule_ids(fs) == ["blocking-under-lock"]
        assert "timeout-less" in fs[0].message

    def test_bounded_queue_get_clean(self):
        src = """
import queue
import threading

class Pump:
    def __init__(self):
        self._lock = threading.Lock()
        self._q = queue.Queue()

    def drain(self):
        with self._lock:
            return self._q.get(timeout=0.5)
"""
        assert run_project("blocking-under-lock",
                           {"pkg/observability/pump.py": src}) == []

    def test_thread_reachable_timeoutless_get_no_lock(self):
        # the CheckpointManager._writer_loop shape: no lock held, but
        # the loop can never observe shutdown -> close() hangs
        src = """
import queue
import threading

class Writer:
    def __init__(self):
        self._q = queue.Queue()
        threading.Thread(target=self._loop, daemon=True).start()

    def _loop(self):
        while True:
            job = self._q.get()
            if job is None:
                return
"""
        fs = run_project("blocking-under-lock",
                         {"pkg/distributed/checkpoint/w.py": src})
        assert rule_ids(fs) == ["blocking-under-lock"]
        assert "Thread-reachable" in fs[0].message

    def test_file_io_under_lock(self):
        src = """
import threading

class Dump:
    def __init__(self):
        self._lock = threading.Lock()

    def write(self, path, rows):
        with self._lock:
            with open(path, "w") as fh:
                fh.write(str(rows))
"""
        fs = run_project("blocking-under-lock",
                         {"pkg/observability/dump.py": src})
        assert rule_ids(fs) == ["blocking-under-lock"]
        assert "file I/O" in fs[0].message

    def test_out_of_scope_module_not_reported(self):
        src = """
import jax
import threading

class Engine:
    def __init__(self):
        self._lock = threading.Lock()
        self._step = jax.jit(lambda x: x)

    def run(self, x):
        with self._lock:
            return self._step(x)
"""
        assert run_project("blocking-under-lock",
                           {"pkg/nn/functional.py": src}) == []


# ---------------------------------------------------------------------------
# mesh-axis-contract (ISSUE 17)
# ---------------------------------------------------------------------------
class TestMeshAxisContract:
    def test_unknown_axis_literal_in_collective(self):
        src = """
from paddle_tpu.distributed.collective import t_psum

def allreduce(x):
    return t_psum(x, "model")
"""
        fs = run_project("mesh-axis-contract", {"pkg/layers.py": src})
        assert rule_ids(fs) == ["mesh-axis-contract"]
        assert "'model'" in fs[0].message

    def test_canonical_axis_clean(self):
        src = """
from paddle_tpu.distributed.collective import t_psum, t_all_gather

def allreduce(x):
    x = t_psum(x, "dp")
    return t_all_gather(x, ("sharding",), axis=0, tiled=True)
"""
        assert run_project("mesh-axis-contract",
                           {"pkg/layers.py": src}) == []

    def test_shard_map_scoped_axis_clean(self):
        # an axis declared by an in-tree Mesh is in scope everywhere,
        # including a shard_map body that names it in specs
        src = """
import jax
from jax.sharding import Mesh, PartitionSpec as P
from jax.experimental.shard_map import shard_map

mesh = Mesh(jax.devices(), ("x", "y"))

def f(v):
    return shard_map(lambda a: a, mesh=mesh,
                     in_specs=P("x", None), out_specs=P("x", None))(v)
"""
        assert run_project("mesh-axis-contract",
                           {"pkg/maps.py": src}) == []

    def test_unknown_axis_in_partition_spec(self):
        src = """
from jax.sharding import PartitionSpec as P

def spec():
    return P("modle", None)
"""
        fs = run_project("mesh-axis-contract", {"pkg/specs.py": src})
        assert rule_ids(fs) == ["mesh-axis-contract"]
        assert "'modle'" in fs[0].message

    def test_nested_tuple_spec_entry_checked(self):
        src = """
from jax.sharding import PartitionSpec as P

def spec():
    return P(("dp", "zz"), None)
"""
        fs = run_project("mesh-axis-contract", {"pkg/specs.py": src})
        assert rule_ids(fs) == ["mesh-axis-contract"]
        assert "'zz'" in fs[0].message

    def test_dynamic_axis_skipped(self):
        src = """
from paddle_tpu.distributed.collective import t_psum

def allreduce(x, axis_name):
    return t_psum(x, axis_name)
"""
        assert run_project("mesh-axis-contract",
                           {"pkg/layers.py": src}) == []

    def test_order_constant_extends_vocabulary(self):
        topo = 'CUSTOM_AXIS_ORDER = ("rowwise", "colwise")\n'
        use = """
from paddle_tpu.distributed.collective import t_psum

def allreduce(x):
    return t_psum(x, "rowwise")
"""
        assert run_project("mesh-axis-contract",
                           {"pkg/topo.py": topo, "pkg/use.py": use}) == []

    def test_scatter_dim_contradicts_spec(self):
        src = """
from jax.sharding import PartitionSpec as P
from paddle_tpu.distributed.collective import t_psum_scatter

def shard(g):
    spec = P(None, "sharding")
    return t_psum_scatter(g, "sharding", scatter_dimension=0,
                          tiled=True)
"""
        fs = run_project("mesh-axis-contract", {"pkg/zero.py": src})
        assert rule_ids(fs) == ["mesh-axis-contract"]
        assert "scatter_dimension=0" in fs[0].message

    def test_scatter_dim_matches_spec_clean(self):
        src = """
from jax.sharding import PartitionSpec as P
from paddle_tpu.distributed.collective import t_psum_scatter

def shard(g):
    spec = P(None, "sharding")
    return t_psum_scatter(g, "sharding", scatter_dimension=1,
                          tiled=True)
"""
        assert run_project("mesh-axis-contract",
                           {"pkg/zero.py": src}) == []


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------
class TestSuppression:
    def test_same_line_pragma(self):
        src = """
def pool3d(x, ceil_mode=False):  # tpulint: disable=unused-knob
    return x
"""
        assert run_rule("unused-knob", src) == []

    def test_comment_line_above(self):
        src = """
# static-graph-only knob, meaningless eagerly
# tpulint: disable=unused-knob
def pool3d(x, ceil_mode=False):
    return x
"""
        assert run_rule("unused-knob", src) == []

    def test_disable_file(self):
        src = """
# tpulint: disable-file=unused-knob

def pool3d(x, ceil_mode=False):
    return x
"""
        assert run_rule("unused-knob", src) == []

    def test_wrong_rule_id_does_not_suppress(self):
        src = """
def pool3d(x, ceil_mode=False):  # tpulint: disable=traced-bool
    return x
"""
        assert rule_ids(run_rule("unused-knob", src)) == ["unused-knob"]


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------
class TestBaseline:
    SRC_V1 = """
def pool3d(x, ceil_mode=False):
    return x
"""
    # same violation, shifted three lines down — must still match
    SRC_V2 = "\n# moved\n# around\n" + SRC_V1

    def test_fingerprint_survives_line_shift(self):
        f1 = run_rule("unused-knob", self.SRC_V1)
        f2 = run_rule("unused-knob", self.SRC_V2)
        base = [baseline_entry(f) for f in f1]
        new, matched, stale = split_by_baseline(f2, base)
        assert new == [] and len(matched) == 1 and stale == []

    def test_new_violation_not_absorbed(self):
        f1 = run_rule("unused-knob", self.SRC_V1)
        base = [baseline_entry(f) for f in f1]
        src = self.SRC_V1 + """
def pool2d(x, exclusive=True):
    return x
"""
        new, matched, stale = split_by_baseline(
            run_rule("unused-knob", src), base)
        assert len(matched) == 1
        assert [f.symbol for f in new] == ["pool2d"]

    def test_fixed_violation_reports_stale(self):
        f1 = run_rule("unused-knob", self.SRC_V1)
        base = [baseline_entry(f) for f in f1]
        new, matched, stale = split_by_baseline([], base)
        assert new == [] and matched == [] and len(stale) == 1


# ---------------------------------------------------------------------------
# the tier-1 whole-tree gate
# ---------------------------------------------------------------------------
class TestWholeTreeGate:
    def test_tree_clean_outside_baseline(self):
        """THE gate: paddle_tpu/ must produce zero findings that are
        not in tools/tpulint/baseline.json. To fix a failure here:
        enforce-or-implement the knob (preferred), add a justified
        `# tpulint: disable=<rule>` pragma, or — for pre-existing debt
        only — regenerate the baseline with --write-baseline."""
        findings = lint_paths([REPO / "paddle_tpu"], ALL_RULES,
                              root=REPO)
        baseline = load_baseline(REPO / "tools/tpulint/baseline.json")
        new, _matched, _stale = split_by_baseline(findings, baseline)
        msg = "\n".join(
            f"{f.path}:{f.line}: {f.rule}: {f.message}" for f in new)
        assert not new, f"new tpulint violations:\n{msg}"

    def test_rule_catalog_complete(self):
        # five per-module trace-safety rules (ISSUE 2) + five
        # interprocedural contract rules (ISSUE 13) + the lock-graph
        # and mesh-axis contract rules (ISSUE 17 acceptance)
        assert set(RULES_BY_ID) == {
            "unused-knob", "host-sync-in-jit", "traced-bool",
            "nonhashable-static", "recompile-hazard",
            "raw-collective", "unregistered-metric",
            "vjp-ledger-symmetry", "donation-reuse",
            "unguarded-shared-mutation",
            "lock-order-cycle", "blocking-under-lock",
            "mesh-axis-contract"}


# ---------------------------------------------------------------------------
# baseline policy for the v2 contract rules
# ---------------------------------------------------------------------------
NEW_RULES = {"raw-collective", "unregistered-metric",
             "vjp-ledger-symmetry", "donation-reuse",
             "unguarded-shared-mutation"}
LOCK_MESH_RULES = {"lock-order-cycle", "blocking-under-lock",
                   "mesh-axis-contract"}
PINNED_ZERO_PREFIXES = ("paddle_tpu/observability/",
                        "paddle_tpu/distributed/checkpoint/",
                        "paddle_tpu/inference/serving.py",
                        # the page pool's allocator and its one lock
                        "paddle_tpu/inference/kv_cache.py",
                        # the disaggregated-serving data plane (ISSUE
                        # 20): the migration wire and the front door
                        # mutate shared engine state across replica
                        # boundaries — races or ledger bypasses here
                        # are fixed, never baselined
                        "paddle_tpu/inference/router.py",
                        "paddle_tpu/inference/disagg.py",
                        # the bidirectional bucketed-collective engine
                        # + the stage-3 gather paths in the train step:
                        # ledger bypasses / races here corrupt the
                        # exactness story, never baseline them
                        "paddle_tpu/distributed/grad_buckets.py",
                        "paddle_tpu/distributed/engine.py")


class TestContractRulePins:
    def test_pinned_subsystems_have_zero_new_rule_baseline(self):
        """The instrument-panel and checkpoint subsystems (and the
        serving engine) are pinned at ZERO baseline entries for the
        five contract rules: a new ledger bypass / unregistered metric
        / race there must be fixed, never baselined."""
        baseline = load_baseline(REPO / "tools/tpulint/baseline.json")
        bad = [e for e in baseline
               if e["rule"] in NEW_RULES
               and e["path"].startswith(PINNED_ZERO_PREFIXES)]
        assert bad == [], f"contract-rule debt in pinned dirs: {bad}"

    def test_lock_mesh_rules_have_zero_baseline_in_pinned_dirs(self):
        """ISSUE 17 pin: serving.py, distributed/checkpoint/ and
        observability/ carry ZERO baseline entries for the lock-graph
        and mesh-axis rules — a deadlock edge, a blocking call under
        the admission lock, or a bad axis literal there is fixed in
        the PR that introduces it, never grandfathered."""
        baseline = load_baseline(REPO / "tools/tpulint/baseline.json")
        bad = [e for e in baseline
               if e["rule"] in LOCK_MESH_RULES
               and e["path"].startswith(
                   ("paddle_tpu/inference/serving.py",
                    "paddle_tpu/inference/kv_cache.py",
                    "paddle_tpu/distributed/checkpoint/",
                    "paddle_tpu/observability/"))]
        assert bad == [], f"lock/mesh-rule debt in pinned dirs: {bad}"

    def test_lock_mesh_rules_whole_tree_clean(self):
        """Stronger than the pin: the three ISSUE 17 rules currently
        hold tree-wide with an EMPTY baseline (no grandfathered
        entries anywhere)."""
        baseline = load_baseline(REPO / "tools/tpulint/baseline.json")
        assert [e for e in baseline if e["rule"] in LOCK_MESH_RULES] == []
        findings = lint_paths([REPO / "paddle_tpu"],
                              select_rules(sorted(LOCK_MESH_RULES)),
                              root=REPO)
        assert findings == [], "\n".join(
            f"{f.path}:{f.line}: {f.rule}: {f.message}" for f in findings)

    def test_every_baseline_entry_is_justified(self):
        baseline = load_baseline(REPO / "tools/tpulint/baseline.json")
        missing = [e for e in baseline if not e.get("justification")]
        assert missing == [], (
            f"{len(missing)} baseline entries lack the mandatory "
            f"justification string")

    def test_whole_tree_runtime_budget(self):
        """Acceptance: the whole-tree run with every rule (the
        interprocedural pass included) stays well under the 60s CI
        budget."""
        import time

        t0 = time.monotonic()
        lint_paths([REPO / "paddle_tpu"], ALL_RULES, root=REPO)
        assert time.monotonic() - t0 < 60.0


# ---------------------------------------------------------------------------
# CLI (exit codes + JSON report)
# ---------------------------------------------------------------------------
def _cli(*args, cwd=REPO):
    env = dict(os.environ)
    env.setdefault("PYTHONPATH", str(REPO))
    return subprocess.run(
        [sys.executable, "-m", "tools.tpulint", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


class TestCLI:
    def test_json_clean_tree_exits_zero(self):
        r = _cli("paddle_tpu/", "--json")
        assert r.returncode == 0, r.stdout + r.stderr
        report = json.loads(r.stdout)
        assert report["new"] == 0
        assert report["baseline_size"] == report["baselined"]
        assert set(report["rules"]) == set(RULES_BY_ID)

    def test_seeded_violation_exits_nonzero(self, tmp_path):
        bad = tmp_path / "seeded.py"
        bad.write_text("def api(x, knob=False):\n    return x\n")
        r = _cli(str(bad))
        assert r.returncode == 1
        assert "unused-knob" in r.stdout

    def test_sarif_format(self, tmp_path):
        """--format sarif: valid SARIF 2.1.0 with the rule catalog as
        reportingDescriptors, new findings at warning level, and the
        same exit-code contract as text/json."""
        bad = tmp_path / "seeded.py"
        bad.write_text("def api(x, knob=False):\n    return x\n")
        r = _cli(str(bad), "--format", "sarif")
        assert r.returncode == 1
        doc = json.loads(r.stdout)
        assert doc["version"] == "2.1.0"
        run = doc["runs"][0]
        assert run["tool"]["driver"]["name"] == "tpulint"
        assert {d["id"] for d in run["tool"]["driver"]["rules"]} \
            == set(RULES_BY_ID)
        res = [x for x in run["results"] if x["level"] == "warning"]
        assert res and res[0]["ruleId"] == "unused-knob"
        loc = res[0]["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"].endswith("seeded.py")
        assert loc["region"]["startLine"] == 1

    def test_sarif_clean_tree_exits_zero_with_notes(self):
        r = _cli("paddle_tpu/", "--format", "sarif")
        assert r.returncode == 0, r.stdout[-2000:] + r.stderr
        doc = json.loads(r.stdout)
        results = doc["runs"][0]["results"]
        # every result is a baselined note, none a new warning
        assert all(x["level"] == "note"
                   and x["baselineState"] == "unchanged"
                   for x in results)

    def test_select_and_list_rules(self, tmp_path):
        bad = tmp_path / "seeded.py"
        bad.write_text("def api(x, knob=False):\n    return x\n")
        # narrowed to an unrelated rule the file is clean → exit 0
        r = _cli(str(bad), "--select", "traced-bool")
        assert r.returncode == 0
        r = _cli("--list-rules")
        assert r.returncode == 0 and "recompile-hazard" in r.stdout

    def test_prune_baseline_drops_unmatched(self, tmp_path):
        """--prune-baseline drops entries whose fingerprints no longer
        match any linted file (fixed violations, deleted files) and
        keeps live + out-of-scope-but-existing ones."""
        tree = tmp_path / "pkg"
        tree.mkdir()
        bad = tree / "bad.py"
        bad.write_text("def api(x, knob=False):\n    return x\n")
        other = tmp_path / "outside.py"
        other.write_text("def api2(y, flag=False):\n    return y\n")
        baseline = tmp_path / "baseline.json"
        entries = [
            # live: matches bad.py's unused-knob finding
            {"rule": "unused-knob", "path": "pkg/bad.py", "symbol": "api",
             "line_text": "def api(x, knob=False):"},
            # fixed: fingerprint matches nothing anymore
            {"rule": "unused-knob", "path": "pkg/bad.py", "symbol": "gone",
             "line_text": "def gone(x, dead_knob=False):"},
            # deleted file: can never match again
            {"rule": "traced-bool", "path": "pkg/removed.py",
             "symbol": "f", "line_text": "if x:"},
            # out of linted scope but still on disk: kept
            {"rule": "unused-knob", "path": "outside.py", "symbol": "api2",
             "line_text": "def api2(y, flag=False):"},
        ]
        baseline.write_text(json.dumps({"findings": entries}))

        r = _cli("pkg", "--baseline", str(baseline), "--root",
                 str(tmp_path), cwd=tmp_path)
        assert r.returncode == 0, r.stdout + r.stderr  # all baselined

        r = _cli("pkg", "--baseline", str(baseline), "--prune-baseline",
                 "--root", str(tmp_path), cwd=tmp_path)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "pruned 2" in r.stdout
        kept = json.loads(baseline.read_text())["findings"]
        assert {(e["path"], e["symbol"]) for e in kept} == {
            ("pkg/bad.py", "api"), ("outside.py", "api2")}

        # pruned baseline still matches: clean run, zero stale
        r = _cli("pkg", "--baseline", str(baseline), "--root",
                 str(tmp_path), "--json", cwd=tmp_path)
        assert r.returncode == 0
        report = json.loads(r.stdout)
        assert report["new"] == 0 and report["baseline_stale"] == []

    def test_changed_mode_lints_only_changed_files(self, tmp_path):
        """--changed <ref>: findings only for files changed vs the
        ref (facts still whole-tree); an untouched violation stays
        unreported."""
        import subprocess

        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "old.py").write_text(
            "def api(x, knob=False):\n    return x\n")
        (pkg / "touched.py").write_text("def ok(x):\n    return x\n")

        def git(*a):
            return subprocess.run(
                ["git", "-c", "user.email=t@t", "-c", "user.name=t",
                 *a], cwd=tmp_path, capture_output=True, text=True,
                timeout=60)

        assert git("init", "-q").returncode == 0
        git("add", "-A")
        assert git("commit", "-qm", "seed").returncode == 0
        (pkg / "touched.py").write_text(
            "def api2(y, flag=False):\n    return y\n")

        r = _cli("pkg", "--changed", "HEAD", "--no-baseline", "--json",
                 cwd=tmp_path)
        assert r.returncode == 1, r.stdout + r.stderr
        report = json.loads(r.stdout)
        assert report["changed_files"] == ["pkg/touched.py"]
        assert {f["path"] for f in report["findings"]} == \
            {"pkg/touched.py"}
        # the ref itself clean vs HEAD when nothing changed
        git("add", "-A")
        git("commit", "-qm", "fix")
        r = _cli("pkg", "--changed", "HEAD", "--no-baseline", "--json",
                 cwd=tmp_path)
        assert r.returncode == 0
        assert json.loads(r.stdout)["total"] == 0

    def test_changed_mode_bad_ref_is_usage_error(self, tmp_path):
        r = _cli(str(tmp_path), "--changed", "no-such-ref",
                 cwd=tmp_path)
        assert r.returncode == 2
        assert "--changed" in r.stderr

    def test_stats_summary(self, tmp_path):
        bad = tmp_path / "seeded.py"
        bad.write_text(
            "def api(x, knob=False):\n    return x\n\n"
            "def quiet(x, other=False):  "
            "# tpulint: disable=unused-knob\n    return x\n")
        r = _cli(str(bad), "--no-baseline", "--stats", "--json")
        assert r.returncode == 1
        report = json.loads(r.stdout)
        s = report["stats"]["unused-knob"]
        assert s["total"] == 1 and s["new"] == 1
        assert s["suppressed"] == 1
        # human output carries the same table
        r = _cli(str(bad), "--no-baseline", "--stats")
        assert "per-rule stats" in r.stdout
        assert "unused-knob" in r.stdout

    def test_write_baseline_requires_justification(self, tmp_path):
        """--write-baseline refuses entries lacking a justification;
        --justification TEXT supplies one for new entries and existing
        justifications are carried over by fingerprint."""
        bad = tmp_path / "seeded.py"
        bad.write_text("def api(x, knob=False):\n    return x\n")
        bl = tmp_path / "bl.json"
        r = _cli(str(bad), "--baseline", str(bl), "--write-baseline")
        assert r.returncode == 2
        assert "justification" in r.stderr and not bl.exists()

        r = _cli(str(bad), "--baseline", str(bl), "--write-baseline",
                 "--justification", "legacy stub kept for API parity")
        assert r.returncode == 0, r.stdout + r.stderr
        entries = json.loads(bl.read_text())["findings"]
        assert entries[0]["justification"] == \
            "legacy stub kept for API parity"

        # second write WITHOUT --justification succeeds: the existing
        # justification is carried over by fingerprint
        bad.write_text("# moved\n" + bad.read_text())
        r = _cli(str(bad), "--baseline", str(bl), "--write-baseline")
        assert r.returncode == 0, r.stdout + r.stderr
        entries = json.loads(bl.read_text())["findings"]
        assert entries[0]["justification"] == \
            "legacy stub kept for API parity"

    def test_prune_preserves_justifications(self, tmp_path):
        bad = tmp_path / "seeded.py"
        bad.write_text("def api(x, knob=False):\n    return x\n"
                       "def gone(x, dead=False):\n    return x\n")
        bl = tmp_path / "bl.json"
        r = _cli("seeded.py", "--baseline", str(bl), "--write-baseline",
                 "--justification", "grandfathered",
                 "--root", str(tmp_path), cwd=tmp_path)
        assert r.returncode == 0
        bad.write_text("def api(x, knob=False):\n    return x\n")
        r = _cli("seeded.py", "--baseline", str(bl), "--prune-baseline",
                 "--root", str(tmp_path), cwd=tmp_path)
        assert r.returncode == 0 and "pruned 1" in r.stdout
        entries = json.loads(bl.read_text())["findings"]
        assert len(entries) == 1
        assert entries[0]["justification"] == "grandfathered"

    def test_prune_baseline_noop_on_live_tree(self, tmp_path):
        """Pruning the checked-in baseline against the real tree drops
        nothing (every entry is live) and leaves the gate green."""
        import shutil

        from tools.tpulint.cli import DEFAULT_BASELINE

        copy = tmp_path / "baseline.json"
        shutil.copy(DEFAULT_BASELINE, copy)
        r = _cli("paddle_tpu/", "--baseline", str(copy),
                 "--prune-baseline")
        assert r.returncode == 0, r.stdout + r.stderr
        assert "pruned 0" in r.stdout
        before = json.loads(DEFAULT_BASELINE.read_text())["findings"]
        after = json.loads(copy.read_text())["findings"]
        assert len(before) == len(after)
