"""``ops/pallas/grouped_matmul.py`` in interpret mode against
``lax.ragged_dot``, the table of visits it walks, its shape gate, and the
sorted expert products (``moe_layer.routed_swiglu_sorted``) on it."""
import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import paddle_tpu as paddle
from paddle_tpu.incubate.distributed.models.moe import moe_layer as ml
from paddle_tpu.ops.pallas import grouped_matmul as gm

# (rows M, held experts G, K, N): the rows a group, the row tile and the
# tiles of N of the cells' calls, cut to a CPU's size
SHAPES = {
    "longcat": (256, 16, 384, 256),      # 16 rows a group, K > N
    "nemotron": (768, 8, 128, 384),      # 96 a group, N = 3 x 128
    "sarvam": (512, 4, 256, 128),        # 128 a group: the last tm-128 call
    "mimo": (384, 4, 128, 256),          # 96 a group
    "dsv32": (1024, 2, 128, 256),        # 512 a group: tm 256
}


def operands(M, G, K, N, dtype=jnp.bfloat16, seed=0):
    r = np.random.RandomState(seed)
    return (jnp.asarray(r.randn(M, K), dtype),
            jnp.asarray(0.1 * r.randn(G, K, N), dtype))


def sizes(M, G, filled, seed=0):
    """``G`` group sizes that sum to ``filled * M`` rows, uneven."""
    r = np.random.RandomState(seed)
    return jnp.asarray(r.multinomial(int(filled * M), r.dirichlet(
        np.ones(G))), jnp.int32)


def check(x, w, gs, **kw):
    """The kernel's rows inside the groups are ``ragged_dot``'s."""
    got = gm.grouped_matmul(x, w, gs, interpret=True, **kw)
    want = lax.ragged_dot(x, w, gs, preferred_element_type=jnp.float32)
    assert got.shape == want.shape and got.dtype == jnp.float32
    n = int(gs.sum())
    np.testing.assert_allclose(np.asarray(got[:n]), np.asarray(want[:n]),
                               rtol=1e-5, atol=1e-5)
    return got


@pytest.mark.parametrize("cell", SHAPES)
def test_groups_of_the_cells_shapes(cell):
    """The routed mean of a window: two thirds of its rows in groups."""
    M, G, K, N = SHAPES[cell]
    assert gm.grouped_matmul_supported((M, K), (G, K, N), jnp.bfloat16)
    assert gm.row_tile(M, G) == (256 if cell == "dsv32" else 128)
    check(*operands(M, G, K, N), sizes(M, G, 2 / 3))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_operand_types(dtype):
    M, G, K, N = SHAPES["mimo"]
    assert gm.grouped_matmul_supported((M, K), (G, K, N), dtype)
    check(*operands(M, G, K, N, dtype), sizes(M, G, 0.9, seed=1))


def test_empty_groups_are_not_visited():
    M, G, K, N = SHAPES["longcat"]
    gs = jnp.asarray([0, 40, 0, 0, 7, 0, 0, 0, 90, 0, 0, 1, 0, 0, 0, 0],
                     jnp.int32)
    check(*operands(M, G, K, N), gs)
    group, row, start, end, n = gm.group_visits(gs, M)
    # 40 rows from 0: one visit; 7 from 40 and 1 from 137: one each; 90
    # from 47 start at row 32 and end at 137: one
    assert int(n) == 4
    assert list(np.asarray(group[:4])) == [1, 4, 8, 11]
    assert list(np.asarray(row[:4])) == [0, 32, 32, 128]


def test_no_group_at_all():
    """No visit: the grid is empty, the result unspecified, and the call
    returns."""
    M, G, K, N = SHAPES["mimo"]
    gs = jnp.zeros((G,), jnp.int32)
    assert int(gm.group_visits(gs, M)[-1]) == 0
    out = gm.grouped_matmul(*operands(M, G, K, N), gs, interpret=True)
    assert out.shape == (M, N)


@pytest.mark.parametrize("which", [0, 2, 3])
def test_one_group_holds_every_row(which):
    M, G, K, N = SHAPES["mimo"]
    gs = jnp.zeros((G,), jnp.int32).at[which].set(M)
    check(*operands(M, G, K, N), gs)
    assert int(gm.group_visits(gs, M)[-1]) == M // 128


def test_rows_past_the_last_group_are_the_callers_to_drop():
    """``sum(group_sizes) < M``: compared under the mask the layer
    applies (``used`` in ``routed_swiglu_sorted``), the two products
    are one."""
    M, G, K, N = SHAPES["sarvam"]
    x, w = operands(M, G, K, N)
    gs = jnp.asarray([100, 3, 0, 130], jnp.int32)
    got = check(x, w, gs)
    used = (jnp.arange(M) < gs.sum())[:, None]
    want = lax.ragged_dot(x, w, gs, preferred_element_type=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(jnp.where(used, got, 0.0)), np.asarray(want),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("p", [0, 1, 2])
def test_a_window_of_a_call_in_several_passes(p):
    """The groups of pass ``p`` as ``routed_swiglu_sorted`` cuts them:
    the part of every group inside rows ``p M .. (p + 1) M``. The first
    group of a middle window starts before it, the last ends after."""
    M, G, K, N = SHAPES["mimo"]
    total = sizes(3 * M - 50, 4, 1.0, seed=3)     # 2.9 windows of pairs
    ends = jnp.cumsum(total)
    starts = ends - total
    lo = p * M
    gw = jnp.clip(ends, lo, lo + M) - jnp.clip(starts, lo, lo + M)
    assert int(gw.sum()) == (M if p < 2 else M - 50)
    check(*operands(M, G, K, N, seed=p), gw)


def test_a_heavy_group_takes_tiles_from_its_own_first_row():
    """Six times the mean in one group, the rest spread: the heavy
    group's visits step by the row tile from its own start rounded down
    to 16, every other group is one visit, and the last tile is moved
    back inside the rows."""
    M, G, K, N = SHAPES["nemotron"]
    gs = jnp.asarray([30, 50, 390, 20, 60, 70, 40, 108], jnp.int32)
    assert int(gs.sum()) == M
    check(*operands(M, G, K, N), gs)
    group, row, start, end, n = gm.group_visits(gs, M)
    assert int(n) == 7 + 4           # 390 rows from row 80: four tiles
    assert list(np.asarray(group[:int(n)])) == [0, 1, 2, 2, 2, 2, 3, 4,
                                                5, 6, 7]
    assert list(np.asarray(row[2:6])) == [80, 208, 336, 464]
    assert int(row[int(n) - 1]) == M - 128      # from 656, moved back
    assert (np.asarray(row) % 16 == 0).all()


def test_several_tiles_of_n_over_visits_handed_in():
    """N above the largest column tile: two tiles of N walk the same
    visits, made once by the caller (an expert layer's products share
    them)."""
    M, G, K, N = 256, 4, 128, 2048
    assert gm.col_tile(M, K, N, gm.row_tile(M, G), 2) == 1024
    x, w = operands(M, G, K, N)
    gs = sizes(M, G, 0.7, seed=5)
    check(x, w, gs, visits=gm.group_visits(gs, M))


@pytest.mark.parametrize("lhs,rhs,dtype,why", [
    ((512, 192), (4, 192, 128), jnp.bfloat16, "K not a lane multiple"),
    ((512, 128), (4, 128, 320), jnp.bfloat16, "N not a lane multiple"),
    ((320, 128), (4, 128, 128), jnp.bfloat16, "rows not in tiles"),
    ((64, 128), (4, 128, 128), jnp.bfloat16, "fewer rows than a tile"),
    ((512, 128), (4, 256, 128), jnp.bfloat16, "K of the two differs"),
    ((512, 128), (4, 128, 128), jnp.float16, "another operand type"),
    ((4, 128, 128), (4, 128, 128), jnp.bfloat16, "rows of rank 3"),
    ((131072, 8192), (16, 8192, 128), jnp.bfloat16,
     "a result tile beyond the VMEM plan"),
])
def test_the_gate_refuses(lhs, rhs, dtype, why):
    assert not gm.grouped_matmul_supported(lhs, rhs, dtype), why


def test_tiles_at_the_published_widths():
    """(rows, held, K, N) of the cells' largest calls: the row tile, and
    a column tile that divides N, is at most 1,024 and fits the plan."""
    for M, G, K, N, tm, tn in [
            (3072, 32, 4096, 2048, 128, 1024),      # sarvam, gate and up
            (3072, 32, 2048, 4096, 128, 1024),      # sarvam, down
            (8448, 128, 1024, 2688, 128, 896),      # nemotron, up
            (8448, 128, 2688, 1024, 128, 1024),     # nemotron, down
            (256, 16, 6144, 2048, 128, 1024),       # longcat
            (1536, 16, 4096, 2048, 128, 1024),      # mimo
            (6144, 16, 7168, 2048, 256, 512),       # dsv32
            (6144, 16, 2048, 768, 256, 768)]:       # keye
        assert gm.grouped_matmul_supported((M, K), (G, K, N), jnp.bfloat16)
        assert gm.row_tile(M, G) == tm
        assert gm.col_tile(M, K, N, tm, 2) == tn


# ---------------------------------------------------------------------------
# the sorted expert products on it
# ---------------------------------------------------------------------------
@pytest.fixture
def kernel_on_cpu(monkeypatch):
    """What a TPU traces: the platform question answered yes, the kernel
    in interpret mode."""
    monkeypatch.setattr(ml._pallas, "is_tpu_platform", lambda: True)
    monkeypatch.setattr(ml, "grouped_matmul", functools.partial(
        gm.grouped_matmul, interpret=True))


@contextlib.contextmanager
def kernels_off():
    paddle.set_flags({"use_pallas_kernels": False})
    try:
        yield
    finally:
        paddle.set_flags({"use_pallas_kernels": True})


def routed(T, d, h, E, El, k, gated=True, dtype=jnp.bfloat16, seed=0):
    r = np.random.RandomState(seed)
    w = lambda *s: jnp.asarray(0.05 * r.randn(*s), dtype)
    idx = jnp.asarray(np.argsort(r.random_sample((T, E)))[:, :k], jnp.int32)
    return (jnp.asarray(r.randn(T, d), dtype), idx,
            jnp.asarray(r.uniform(0.05, 0.2, (T, k)), jnp.float32),
            w(El, d, h) if gated else None, w(El, d, h), w(El, h, d))


@pytest.mark.parametrize("gated", [True, False])
def test_sorted_products_on_the_kernel_are_xlas(kernel_on_cpu, gated):
    """One window, gated (three products) and not (two): the same sum,
    the same counts, and the trace says which product ran."""
    ops = routed(256, 128, 256, 16, 4, 4, gated)
    y, sizes_, trace = ml.routed_swiglu(*ops, 2, 16)
    assert trace["form"] == "sorted" and trace["grouped"] == "pallas"
    assert trace["rows"] == (512, 1024)
    with kernels_off():
        y0, sizes0, trace0 = ml.routed_swiglu(*ops, 2, 16)
    assert trace0["grouped"] == "xla"
    np.testing.assert_array_equal(np.asarray(sizes_), np.asarray(sizes0))
    # (a last bit of a bf16 activation turns with the order of a sum)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y0), rtol=1e-4,
                               atol=5e-5)


def test_sorted_products_in_several_passes(kernel_on_cpu):
    """A router that sends the held experts 820 pairs where the bound is
    512 rows: two windows, each with its own table of visits."""
    T, E, El, k = 256, 16, 4, 4
    ops = list(routed(T, 128, 128, E, El, k, dtype=jnp.float32, seed=2))
    r = np.random.RandomState(4)        # 0.8 of all pairs to the held 4
    ops[1] = jnp.asarray(np.where(
        r.random_sample((T, k)) < 0.8, r.randint(0, El, (T, k)),
        r.randint(El, E, (T, k))), jnp.int32)
    y, sizes_, passes, grouped = ml.routed_swiglu_sorted(*ops, 0, E)
    assert grouped == "pallas"
    assert int(passes) == 2 and int(sizes_[-1]) == int(sizes_[:El].sum())
    with kernels_off():
        y0, sizes0, passes0, grouped0 = ml.routed_swiglu_sorted(*ops, 0,
                                                                E)
    assert int(passes0) == 2 and grouped0 == "xla"
    np.testing.assert_array_equal(np.asarray(sizes_), np.asarray(sizes0))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y0), rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("d,h,dtype,want", [
    (128, 256, jnp.bfloat16, "pallas"),
    (64, 256, jnp.bfloat16, "xla"),         # a width the gate refuses
    (128, 256, jnp.float16, "xla"),         # an operand type it refuses
])
def test_the_product_is_a_function_of_the_shapes(kernel_on_cpu, d, h, dtype,
                                                 want):
    _, _, _, wg, wu, wd = routed(8, d, h, 16, 4, 4, dtype=dtype)
    assert ml.grouped_product(512, dtype, wg, wu, wd) == want
    assert ml.grouped_product(512, dtype, None, wu, wd) == want
    # rows and weights of two types: XLA's
    assert ml.grouped_product(512, jnp.float32, wg, wu, wd) == "xla"


def test_the_tpu_program_holds_the_kernel_and_not_xlas(kernel_on_cpu,
                                                       monkeypatch):
    """Lowered for a TPU, the sorted form is Mosaic calls named
    ``grouped_matmul`` (one lowered kernel a pair of widths) and no
    ``ragged_dot``; with kernels off it is ``ragged_dot`` alone."""
    monkeypatch.setattr(ml, "grouped_matmul", gm.grouped_matmul)
    ops = routed(256, 128, 256, 16, 4, 4)
    fn = lambda *a: ml.routed_swiglu_sorted(*a, 2, 16)[0]
    text = jax.jit(fn).trace(*ops).lower(
        lowering_platforms=("tpu",)).as_text()
    assert 'kernel_name = "grouped_matmul"' in text
    assert "ragged_dot" not in text
    with kernels_off():     # (another function: a jit keeps a trace)
        text = jax.jit(lambda *a: fn(*a)).trace(*ops).lower(
            lowering_platforms=("tpu",)).as_text()
    assert "grouped_matmul" not in text and "ragged_dot" in text
