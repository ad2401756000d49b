"""``paged_kv_write``: the one way new K/V rows reach the page pool.

Under test (ops/pallas/decode_attention.py, called by the paged branch
of models/llama.py and by incubate's block_multihead_attention):
- both forms (whole pages for prefill programs, rows of the flat view
  otherwise) leave exactly the pool the old advanced-index scatter left,
  rows past the block table included: they are dropped, not landed in
  some other request's page;
- the prefill programs take the whole-page form (a concrete 0 offset of
  any integer type picks it), the decode program the row form;
- no serving program still carries that scatter (two scatter dims over
  the 4-D pool), which on the TPU costs two whole-pool layout copies
  per pool per call;
- the property itself, for a v5e, with the real compiler and no chip:
  zero pool-shaped ``copy`` ops in write + ``paged_decode_attention``
  (tools/paged_write_aot.py, in a process of its own).
"""
import json
import os
import re
import subprocess
import sys
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.inference import Config, ServingEngine, create_predictor
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
from paddle_tpu.ops.pallas.decode_attention import paged_kv_write

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def scatter_reference(pool, new, tables, offset, valid=None):
    """The write as it was before PR 26 (models/llama.py): one
    advanced-index scatter of [B,S] page ids and slots, plain jax.numpy.
    The reference here, and ``tools/paged_write_aot.py --old``."""
    B, S = new.shape[:2]
    page = pool.shape[2]
    off = jnp.broadcast_to(jnp.asarray(offset, jnp.int32).reshape(-1), (B,))
    pos = off[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
    if valid is not None:
        alive = jnp.arange(S, dtype=jnp.int32)[None] \
            < jnp.asarray(valid, jnp.int32).reshape(B, 1)
        pos = jnp.where(alive, pos, (tables.shape[1] - 1) * page)
    pid = jnp.take_along_axis(tables, pos // page, axis=1)
    return pool.at[pid, :, pos % page, :].set(new.astype(pool.dtype))


# name -> (B, S, page, KV, D, npages, offset, valid, lens, pool dtype).
# ``offset`` "zero" is the Python int 0 of the prefill programs; a list
# is per-row and traced. ``lens`` = tokens a row owns pages for (the
# rest of its table is the trash page), None = every page its own.
CASES = {
    "decode_ragged_offsets": (4, 1, 8, 2, 16, 5, [0, 7, 8, 30], None,
                              None, "float32"),
    "decode_dead_rows_on_trash": (4, 1, 8, 2, 16, 5, [3, 0, 17, 0], None,
                                  [4, 0, 18, 0], "float32"),
    "decode_scalar_offset": (3, 1, 8, 2, 16, 4, 5, None, None, "float32"),
    "prefill_short_of_a_page": (1, 5, 8, 2, 16, 4, "zero", None, None,
                                "float32"),
    "prefill_whole_pages": (1, 24, 8, 2, 16, 4, "zero", None, None,
                            "float32"),
    "prefill_batch_padded_to_trash": (3, 16, 8, 2, 16, 4, "zero", None,
                                      [16, 3, 9], "float32"),
    "prefill_batch_short_of_a_page": (3, 6, 8, 2, 16, 4, "zero", None,
                                      [6, 2, 4], "float32"),
    "prefill_ragged_tail_takes_rows": (2, 12, 8, 2, 16, 4, "zero", None,
                                       None, "float32"),
    "chunk_at_traced_offset": (2, 8, 8, 2, 16, 5, [8, 16], None, None,
                               "float32"),
    "unified_valid_trash_column": (4, 6, 8, 2, 16, 5, [0, 13, 8, 0],
                                   [6, 1, 3, 0], None, "float32"),
    # positions past the table (a decode scan stepping on after a row's
    # last token at the context limit, a speculative write-ahead): no
    # page is written for them, page 0 least of all
    "decode_past_the_table": (4, 1, 8, 2, 16, 4, [31, 32, 33, 47], None,
                              None, "float32"),
    "chunk_runs_past_the_table": (2, 6, 8, 2, 16, 4, [28, 30], None, None,
                                  "float32"),
    "unified_valid_past_the_table": (3, 6, 8, 2, 16, 4, [27, 36, 42],
                                     [6, 6, 2], None, "float32"),
    "page128_bf16_decode_past_the_table": (3, 1, 128, 8, 128, 3,
                                           [383, 384, 1000], None, None,
                                           "bfloat16"),
    "page128_bf16_decode": (3, 1, 128, 8, 128, 3, [0, 127, 300], None,
                            None, "bfloat16"),
    "page128_bf16_prefill": (2, 256, 128, 8, 128, 3, "zero", None,
                             [256, 100], "bfloat16"),
    "page16_f32_rows_into_bf16_pool": (2, 3, 16, 4, 32, 3, [15, 30], None,
                                       None, "bfloat16"),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_write_matches_advanced_index_scatter(name):
    B, S, page, KV, D, npages, offset, valid, lens, dtype = CASES[name]
    r = np.random.RandomState(sum(map(ord, name)))
    P = B * npages + 2
    trash = P - 1
    # scrambled physical pages; logical pages past a row's tokens, and
    # the unified step's extra trailing column, map to the trash page
    tables = r.permutation(P - 1)[:B * npages].reshape(B, npages)
    if lens is not None:
        owned = -(-np.asarray(lens) // page)
        tables = np.where(np.arange(npages)[None] < owned[:, None],
                          tables, trash)
    if valid is not None:
        tables = np.concatenate(
            [tables, np.full((B, 1), trash, tables.dtype)], axis=1)
    tables = jnp.asarray(tables, jnp.int32)
    k_pool = jnp.asarray(r.randn(P, KV, page, D), dtype)
    v_pool = jnp.asarray(r.randn(P, KV, page, D), dtype)
    k_new = jnp.asarray(r.randn(B, S, KV, D), jnp.float32)
    v_new = jnp.asarray(r.randn(B, S, KV, D), jnp.float32)
    nv = None if valid is None else jnp.asarray(valid, jnp.int32)

    if offset == "zero":            # static, as Predictor._prefill_fn
        got_k, got_v = jax.jit(partial(paged_kv_write, offset=0))(
            k_pool, v_pool, k_new, v_new, tables)
        off = 0
    else:                           # traced, as every other program
        off = jnp.asarray(offset, jnp.int32)
        got_k, got_v = jax.jit(paged_kv_write)(
            k_pool, v_pool, k_new, v_new, tables, off, nv)
    want_k = scatter_reference(k_pool, k_new, tables, off, nv)
    want_v = scatter_reference(v_pool, v_new, tables, off, nv)

    assert got_k.dtype == k_pool.dtype and got_k.shape == k_pool.shape
    # several rows may name one slot of the trash page, and any of them
    # may win there; nothing reads it. Every other page: bit for bit
    live = np.arange(P) != trash
    for got, want, pool in ((got_k, want_k, k_pool), (got_v, want_v, v_pool)):
        np.testing.assert_array_equal(np.asarray(got)[live],
                                      np.asarray(want)[live])
        assert (np.asarray(got)[live] != np.asarray(pool)[live]).any()


# -- the programs the engine compiles ---------------------------------------
_TWO_DIM_SCATTER = re.compile(
    r"scatter_dims_to_operand_dims\s*=\s*\[\s*\d+\s*,\s*\d+")
_ONE_DIM_SCATTER = re.compile(
    r"scatter_dims_to_operand_dims\s*=\s*\[\s*\d+\s*\]")
_WINDOW_DIMS = re.compile(r"update_window_dims\s*=\s*\[([\d,\s]*)\]")


def window_dims(text):
    """The update-window dims of every scatter in a lowered program."""
    return {tuple(int(d) for d in m.split(",") if d.strip())
            for m in _WINDOW_DIMS.findall(text)}


def test_detector_sees_the_old_scatter():
    pool = jnp.zeros((6, 2, 8, 16))
    text = jax.jit(scatter_reference).lower(
        pool, jnp.zeros((2, 1, 2, 16)), jnp.zeros((2, 3), jnp.int32),
        jnp.zeros((2,), jnp.int32)).as_text()
    assert _TWO_DIM_SCATTER.search(text)


@pytest.fixture(scope="module")
def served_engine():
    paddle.seed(11)
    model = LlamaForCausalLM(llama_tiny())
    pred = create_predictor(
        Config().set_model(model).enable_paged_kv(page_size=8))
    eng = ServingEngine(pred, max_batch=2, decode_chunk=2)
    r = np.random.RandomState(0)
    for L in (5, 16):               # buckets below and at whole pages
        eng.submit(r.randint(1, model.config.vocab_size, (L,)),
                   max_new_tokens=4)
    eng.run()
    return eng


# the form each program must take: whole [KV, rows, D] windows by page id
# in the prefill programs (the row form there is 67 times slower at 2048
# tokens: PERF.md, PR 26), rows of D everywhere else
FORMS = {"decode": {(1,)}, "prefill": {(1, 2, 3)}}


@pytest.mark.parametrize("kind", sorted(FORMS))
def test_serving_programs_scatter_along_one_dim(served_engine, kind):
    sites = [s for s in served_engine.program_sites() if s[0] == kind]
    assert sites, served_engine.program_sites()
    for site in sites:
        text = served_engine.lowered_text(site)
        assert _ONE_DIM_SCATTER.search(text), site      # the write is there
        assert not _TWO_DIM_SCATTER.search(text), site
        assert window_dims(text) == FORMS[kind], site


@pytest.mark.parametrize("zero", [0, np.int32(0), np.array(0), np.int64(0),
                                  jnp.int32(0)],
                         ids=lambda z: type(z).__name__ + str(np.ndim(z)))
def test_any_concrete_zero_offset_takes_whole_pages(zero):
    pool = jnp.zeros((6, 2, 8, 16))
    new = jnp.zeros((1, 16, 2, 16))
    tables = jnp.zeros((1, 3), jnp.int32)
    text = jax.jit(partial(paged_kv_write, offset=zero)).lower(
        pool, pool, new, new, tables).as_text()
    assert window_dims(text) == {(1, 2, 3)}
    # a traced zero cannot be seen, and takes rows
    text = jax.jit(paged_kv_write).lower(
        pool, pool, new, new, tables, jnp.int32(0)).as_text()
    assert window_dims(text) == {(1,)}


def test_row_ending_at_the_context_limit_leaves_its_neighbour_alone():
    """``_decode_step_fn`` scans ``decode_chunk`` steps whatever a row
    has left: a row that finishes at the context limit steps past its
    block table. Those writes name no page; before the fix they wrapped
    onto physical page 0, here the neighbour's."""
    paddle.seed(11)
    model = LlamaForCausalLM(llama_tiny())
    cfg = Config().set_model(model).enable_paged_kv(page_size=8)
    cfg.max_length = 32
    pred = create_predictor(cfg)
    r = np.random.RandomState(3)
    ends_at_limit = r.randint(1, model.config.vocab_size, (29,))
    neighbour = r.randint(1, model.config.vocab_size, (17,))

    def serve(*requests):
        eng = ServingEngine(pred, max_batch=2, pool_pages=8, decode_chunk=4)
        rids = [eng.submit(p, max_new_tokens=n) for p, n in requests]
        eng._admit()                # pages are handed out at admission
        tables = eng.cache.tables.copy()
        done = eng.run()
        return eng, tables, [done[rid].new_tokens for rid in rids]

    both, tables, (_, with_neighbour) = serve((ends_at_limit, 3),
                                              (neighbour, 6))
    alone, tables1, (by_itself,) = serve((neighbour, 6))
    assert 0 in tables[1, :3], tables      # the neighbour holds page 0
    assert with_neighbour == by_itself
    # and its K/V, every layer, every position it wrote, bit for bit
    for (k2, v2), (k1, v1) in zip(both.pools, alone.pools):
        for got, want in ((k2, k1), (v2, v1)):
            got = np.asarray(got)[tables[1, :3]]        # [3, KV, page, D]
            want = np.asarray(want)[tables1[0, :3]]
            written = 17 + 6 - 1                        # positions 0..21
            got = np.swapaxes(got, 0, 1).reshape(got.shape[1], 24, -1)
            want = np.swapaxes(want, 0, 1).reshape(want.shape[1], 24, -1)
            np.testing.assert_array_equal(got[:, :written],
                                          want[:, :written])


# -- the property, on the TPU's own compiler, without a chip ----------------
@pytest.mark.parametrize("form", ["new", "old"])
def test_v5e_compile_has_no_pool_sized_copy(form):
    """AOT for v5e:2x2 in a child (the TPU library belongs to one process
    at a time): the forms in use compile with no pool-shaped copy; the
    old scatter, same harness, shows some (the positive control)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "paged_write_aot.py")]
        + (["--old"] if form == "old" else []),
        capture_output=True, text=True, timeout=600, cwd=ROOT)
    lines = [l for l in proc.stdout.splitlines() if l.startswith("{")]
    assert proc.returncode == 0 and lines, proc.stderr[-2000:]
    res = json.loads(lines[-1])
    if "skipped" in res:
        pytest.skip(f"no v5e:2x2 topology here: {res['skipped']}")
    cases = res["cases"]
    assert len(cases) >= 4
    assert "decode_48x1_round" in [c["case"] for c in cases]
    for c in cases:
        assert c["kernel"] == ("dense" not in c["case"]), c
        if form == "new":
            assert c["pool_copies"] == 0, c
            # the pools are written in place, and nothing else is: not
            # the tables, nor the decode program's one round array
            assert len(c["donated"]) == 4 and all(
                n.startswith("pools_") for n in c["donated"]), c
            if c["case"].startswith("decode"):  # XLA's own byte count:
                assert c["bytes_accessed"] < c["pool_bytes"], c  # < 1 pool
        else:
            assert c["pool_copies"] > 0, c      # 8 with libtpu 0.0.34
