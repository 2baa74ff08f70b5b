"""The grouped expert product's kernel (``ops/grouped_matmul.py``) against
``lax.ragged_dot``: on the CPU in interpreter mode at small shapes, over
the rows the groups cover (a row past them holds nothing that may be read),
and compiled (never run) for a described TPU v5e at the served shapes.
float32 operands: the tolerance is that of two orders of float32
summation."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from eventgpt_tpu.models import experts as ex
from eventgpt_tpu.ops import grouped_matmul as gm

pytestmark = pytest.mark.skipif(
    os.environ.get("JAX_PLATFORMS") != "cpu", reason="a CPU test")

D_IN, D_OUT = 32, 48


def operands(rows, sizes, d_in=D_IN, d_out=D_OUT, dtype=jnp.float32, seed=0):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(kx, (rows, d_in), jnp.float32).astype(dtype),
            (jax.random.normal(kw, (len(sizes), d_in, d_out), jnp.float32)
             / np.sqrt(d_in)).astype(dtype),
            jnp.asarray(sizes, jnp.int32))


def close(got, want, held, tol=1e-5):
    np.testing.assert_allclose(np.asarray(got[:held], np.float32),
                               np.asarray(want[:held], np.float32),
                               rtol=tol, atol=tol)


def pairs(sizes, tm=gm.ROW_TILE):
    """(row tile, group) pairs that hold a row of the group, reckoned row
    by row."""
    group = np.repeat(np.arange(len(sizes)), sizes)
    return sorted({(r // tm, int(g)) for r, g in enumerate(group)})


def against_ragged_dot(rows, sizes, **kw):
    x, w, s = operands(rows, sizes, **kw)
    got = gm.gmm(x, w, s)
    assert got.shape == (rows, w.shape[-1]) and got.dtype == x.dtype
    assert "expert_gmm" in str(jax.make_jaxpr(gm.gmm)(x, w, s))
    close(got, lax.ragged_dot(x, w, s), sum(sizes))
    assert int(gm.tiles(s)) == len(pairs(sizes))


def uneven_groups(_):
    against_ragged_dot(512, [100, 37, 250, 125])


def empty_groups(_):
    against_ragged_dot(512, [0, 200, 0, 0, 56, 0])


def a_group_that_starts_mid_tile(_):
    """The second group starts at row 50 and the third at 350: tiles 0 and
    2 are each visited by two groups in a row."""
    against_ragged_dot(512, [50, 300, 100])


def a_group_over_several_tiles(_):
    against_ragged_dot(640, [10, 400, 2, 0, 200])


def rows_past_the_held_sum(_):
    """300 held rows of 1,024: the tiles past them are never visited."""
    against_ragged_dot(1024, [120, 0, 90, 90])


def rows_that_are_not_whole_tiles(_):
    """300 rows are padded to three tiles inside the call and cut back."""
    against_ragged_dot(300, [1, 129, 0, 7, 100])


def no_group_holds_a_row(_):
    x, w, s = operands(256, [0, 0, 0])
    assert gm.gmm(x, w, s).shape == (256, D_OUT)
    assert int(gm.tiles(s)) == 0


def several_out_tiles(monkeypatch):
    """An out width of 384 in blocks of 128: three passes over the rows."""
    monkeypatch.setattr(gm, "WEIGHT_BLOCK_BYTES", D_IN * 128 * 4)
    assert gm.out_tile(512, D_IN, 384, 4) == 128
    against_ragged_dot(512, [60, 0, 300, 100], d_out=384)


def bfloat16_operands(_):
    """The operands' type out, each product accumulated in float32: within
    one bfloat16 rounding of the CPU's ``ragged_dot``."""
    x, w, s = operands(384, [70, 200, 30], dtype=jnp.bfloat16)
    got = gm.gmm(x, w, s)
    assert got.dtype == jnp.bfloat16
    close(got, lax.ragged_dot(x, w, s), 300, tol=1e-2)


@pytest.mark.parametrize("case", [
    uneven_groups, empty_groups, a_group_that_starts_mid_tile,
    a_group_over_several_tiles, rows_past_the_held_sum,
    rows_that_are_not_whole_tiles, no_group_holds_a_row, several_out_tiles,
    bfloat16_operands,
], ids=lambda case: case.__name__)
def test_kernel_against_ragged_dot(case, monkeypatch):
    case(monkeypatch)


@pytest.mark.parametrize("kind", ["swiglu", "relu2"])
def test_both_forms_of_the_expert_through_the_kernel(kind, monkeypatch):
    """``experts._grouped`` with the chip's product (the kernel, here in the
    interpreter) against its CPU product, for a gated and a relu^2 expert."""
    sizes = [90, 0, 170, 40]
    x, w, s = operands(384, sizes)
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    names = ("gate", "up") if kind == "swiglu" else ("up",)
    weights = {n: jax.random.normal(k, (4, D_IN, D_OUT)) / np.sqrt(D_IN)
               for n, k in zip(names, keys)}
    weights["down"] = jax.random.normal(keys[2], (4, D_OUT, D_IN)) / 7.0
    want = ex._grouped(x, weights, s)
    monkeypatch.setattr(ex, "backend_platform", lambda: "tpu")
    assert ex._product() is gm.gmm
    close(ex._grouped(x, weights, s), want, sum(sizes))


@pytest.mark.parametrize("seed", range(4))
def test_the_visits_and_their_count(seed):
    """The grid's visits are the (row tile, group) pairs in row order, each
    once, and ``tiles`` counts them; 512-row tiles count ``ragged_dot``'s."""
    rng = np.random.default_rng(seed)
    sizes = rng.integers(0, 300, 9) * (rng.random(9) < 0.7)
    rows = -(-max(int(sizes.sum()), 1) // 128) * 128 + 128 * seed
    s = jnp.asarray(sizes, jnp.int32)
    groups, row_tiles, n = gm._visits(s, rows, gm.ROW_TILE)
    want = pairs(sizes)
    assert int(n) == int(gm.tiles(s)) == len(want)
    assert groups.shape == (rows // 128 + 8,)
    got = list(zip(np.asarray(row_tiles)[:int(n)].tolist(),
                   np.asarray(groups)[:int(n)].tolist()))
    assert got == want
    assert int(gm.tiles(s, 512)) == len(pairs(sizes, 512))


def test_the_out_tile_at_the_served_shapes():
    """Trinity's slab takes blocks of 1,024 and its decode step (one row
    tile) of 512; the hybrid's wave the whole out width; a width that is no
    multiple of 128 is one block. What a call asks of VMEM stays far under
    the 128 MB of a v5e, whose rest XLA keeps the products' outputs in."""
    assert gm.out_tile(12288, 3072, 3072, 2) == 1024
    assert gm.out_tile(128, 3072, 3072, 2) == 512
    assert gm.out_tile(3584 * 22, 1024, 2688, 2) == 2688
    assert gm.out_tile(3584 * 22, 2688, 1024, 2) == 1024
    assert gm.out_tile(512, D_IN, D_OUT, 4) == D_OUT
    assert gm.vmem_bytes(3072, 1024, 2) == 19 * 2 ** 20
    assert gm.vmem_bytes(3072, 512, 2) == 11 * 2 ** 20
    assert gm.vmem_bytes(2688, 1024, 2) == 17 * 2 ** 20


# -- compiled for the chip, at the served shapes (no chip: nothing runs) -----

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("rows, experts, d_in, d_out", [
    (12288, 32, 3072, 3072),     # Trinity's prefill slab
    (128, 32, 3072, 3072),       # Trinity's decode step
    (3584 * 22, 128, 1024, 2688),  # the hybrid's wave, up
    (3584 * 22, 128, 2688, 1024),  # and down
], ids=["trinity-slab", "trinity-decode", "hybrid-up", "hybrid-down"])
def test_served_shape_compiles_for_a_v5e(one_chip, rows, experts, d_in,
                                         d_out):
    """Mosaic takes the kernel, under its name, within the VMEM it asks
    for; the program holds no copy of the rows or the weights."""
    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(lambda x, w, s: gm.gmm(x, w, s, interpret=False)).lower(
        spec(rows, d_in), spec(experts, d_in, d_out),
        spec(experts, dtype=jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "%expert_gmm" in text
    assert compiled.memory_analysis().temp_size_in_bytes < rows * d_in
