"""The latent decode kernel compiled by the real Mosaic compiler for a
DESCRIBED v5e (no chip): what interpret mode cannot show — a slice the
tiling refuses, a step past the scoped VMEM — at the cells' own widths
and at the edges ``mla_paged_supported`` draws (PR 47).

The topology is described inside a fixture, never at import: only one
process at a time may load libtpu, and a worker that cannot skips."""
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.pallas import mla_attention as ma


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(dev, B, H, npages, P, dr=128, kept=False):
    """The kernel at d_c 512, pages of 128, bf16 -> (the gate's word,
    the compiled text or the compiler's error)."""
    dc, page, bf = 512, 128, jnp.bfloat16
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=dev)
    args = [S((B, H, dc), bf), S((B, H, dr), bf), S((P, 1, page, dc), bf),
            S((P, 1, page, dr), bf), S((B, npages), jnp.int32),
            S((B,), jnp.int32)]
    if kept:
        args.append(S((B, npages * page), jnp.bool_))

    def call(ql, qr, cp, rp, tbl, ln, keep=None):
        return ma.mla_paged_decode_attention(ql, qr, cp, rp, tbl, ln, 0.07,
                                             keep=keep)

    gate = ma.mla_paged_supported((B, H, dc), (P, 1, page, dc),
                                  (P, 1, page, dr))
    try:
        return gate, jax.jit(call).lower(*args).compile().as_text()
    except Exception as e:      # the compiler's refusal is the reading
        return gate, e


@pytest.mark.parametrize("B, H, npages, P, kept", [
    (128, 64, 10, 2048, False),     # serve-longcat-dialoggen-batch
    (128, 64, 11, 2048, False),     # serve-sarvam-longgen-batch
    (48, 128, 66, 4096, True),      # serve-dsv32-longdoc-batch
], ids=["longcat", "sarvam", "dsv32_kept"])
def test_the_cells_shapes_compile_under_their_kernel_names(
        one_chip, B, H, npages, P, kept):
    gate, text = _compile(one_chip, B, H, npages, P, kept=kept)
    assert gate and isinstance(text, str), text
    name = "mla_paged_sparse_decode_attention" if kept \
        else "mla_paged_decode_attention"
    assert text.count("tpu_custom_call") == 1 and f"/{name}/" in text


@pytest.mark.parametrize("H, dr, compiles", [
    (1448, 128, True),      # the last head count under the scoped VMEM
    (1536, 128, False),
    (64, 64, False),        # a page is copied whole: 64 columns fill no lane
], ids=["1448_heads", "1536_heads", "rope_64"])
def test_the_gate_says_what_the_compiler_says(one_chip, H, dr, compiles):
    gate, text = _compile(one_chip, 8, H, 10, 128, dr=dr)
    assert gate == compiles == isinstance(text, str), text
